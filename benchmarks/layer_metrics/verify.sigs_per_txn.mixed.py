from harness.mixed_readers import sigs_per_txn as read  # noqa: F401
