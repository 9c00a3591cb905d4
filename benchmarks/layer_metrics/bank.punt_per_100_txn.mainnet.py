from harness.mix_readers import punt_per_100_txn as read  # noqa: F401
