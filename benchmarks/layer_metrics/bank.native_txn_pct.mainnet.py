from harness.mix_readers import native_txn_pct as read  # noqa: F401
