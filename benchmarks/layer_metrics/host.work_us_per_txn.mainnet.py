from harness.thread_readers import host_work_us_per_txn as read  # noqa: F401
