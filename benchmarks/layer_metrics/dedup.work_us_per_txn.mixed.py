from harness.thread_readers import dedup_work_us_per_txn as read  # noqa: F401
