from harness.span_readers import launch_ms_per_batch as read  # noqa: F401
