from harness.mix_readers import vote_share_pct as read  # noqa: F401
