from harness.span_readers import in_verify_ms_leader as read  # noqa: F401
