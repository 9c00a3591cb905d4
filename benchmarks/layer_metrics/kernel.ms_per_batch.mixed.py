from harness.readers import kernel_ms_per_batch as read  # noqa: F401
