from harness.span_readers import in_crossing_us_per_txn as read  # noqa: F401
