from harness.mix_readers import mb_fill_txn as read  # noqa: F401
