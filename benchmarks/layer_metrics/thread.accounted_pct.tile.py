from harness.thread_readers import thread_accounted_pct as read  # noqa: F401
