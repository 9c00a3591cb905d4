from harness.thread_readers import verify_work_ms_per_batch as read  # noqa: F401
