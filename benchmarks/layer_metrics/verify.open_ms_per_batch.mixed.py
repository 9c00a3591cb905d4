from harness.span_readers import open_ms_per_batch as read  # noqa: F401
