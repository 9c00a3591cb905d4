from harness.thread_readers import verify_offcall_ms_per_batch as read  # noqa: F401
