from harness.span_readers import reap_ms_per_batch as read  # noqa: F401
