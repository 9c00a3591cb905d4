from harness.thread_readers import chip_empty_call_pct as read  # noqa: F401
