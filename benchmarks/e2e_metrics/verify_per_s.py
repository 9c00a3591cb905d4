from harness.readers import served_per_s as read  # noqa: F401
