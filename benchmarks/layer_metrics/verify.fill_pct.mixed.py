from harness.readers import fill_pct as read  # noqa: F401
