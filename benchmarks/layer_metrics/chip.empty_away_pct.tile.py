from harness.thread_readers import chip_empty_away_pct as read  # noqa: F401
