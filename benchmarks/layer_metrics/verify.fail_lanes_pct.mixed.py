from harness.mixed_readers import fail_lanes_pct as read  # noqa: F401
