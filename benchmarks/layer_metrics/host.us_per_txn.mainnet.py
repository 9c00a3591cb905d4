from harness.readers import host_us_per_txn as read  # noqa: F401
