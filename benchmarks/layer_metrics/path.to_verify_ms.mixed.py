from harness.span_readers import to_verify_ms as read  # noqa: F401
