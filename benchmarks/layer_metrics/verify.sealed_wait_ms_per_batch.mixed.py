from harness.span_readers import sealed_wait_ms_per_batch as read  # noqa: F401
