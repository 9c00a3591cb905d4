from harness.mixed_readers import late_dup_lanes_pct as read  # noqa: F401
