from harness.readers import late_ms_p95 as read  # noqa: F401
