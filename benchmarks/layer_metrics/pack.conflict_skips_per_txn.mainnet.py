from harness.mix_readers import conflict_skips_per_txn as read  # noqa: F401
