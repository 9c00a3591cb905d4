from harness.mix_readers import vote_failed_pct as read  # noqa: F401
