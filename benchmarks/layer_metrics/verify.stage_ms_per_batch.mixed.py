from harness.readers import verify_stage_ms_per_batch as read  # noqa: F401
