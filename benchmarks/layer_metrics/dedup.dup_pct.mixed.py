from harness.mixed_readers import dup_pct as read  # noqa: F401
