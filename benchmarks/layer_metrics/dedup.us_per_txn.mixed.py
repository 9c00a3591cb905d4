from harness.mixed_readers import dedup_us_per_txn as read  # noqa: F401
