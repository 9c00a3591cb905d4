from harness.readers import lat_ms_p95 as read  # noqa: F401
