from harness.mixed_readers import fit_pad_pct as read  # noqa: F401
