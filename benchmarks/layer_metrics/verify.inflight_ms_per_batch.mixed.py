from harness.span_readers import inflight_ms_per_batch as read  # noqa: F401
