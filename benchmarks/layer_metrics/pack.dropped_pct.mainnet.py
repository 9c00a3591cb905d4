from harness.mix_readers import dropped_pct as read  # noqa: F401
