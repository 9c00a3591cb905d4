from harness.span_readers import in_verify_ms_tile as read  # noqa: F401
