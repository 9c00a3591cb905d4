from harness.mixed_readers import in_dedup_ms as read  # noqa: F401
