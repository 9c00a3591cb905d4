from harness.span_readers import h2d_ms_per_batch as read  # noqa: F401
