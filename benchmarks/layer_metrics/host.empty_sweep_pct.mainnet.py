from harness.span_readers import empty_sweep_pct as read  # noqa: F401
