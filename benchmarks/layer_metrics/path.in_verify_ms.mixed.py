from harness.mixed_readers import in_verify_ms as read  # noqa: F401
