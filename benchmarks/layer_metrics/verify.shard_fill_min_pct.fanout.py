from harness.mesh_readers import shard_fill_min_pct as read  # noqa: F401
