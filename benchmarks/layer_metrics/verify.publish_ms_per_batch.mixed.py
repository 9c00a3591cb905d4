from harness.span_readers import publish_ms_per_batch as read  # noqa: F401
