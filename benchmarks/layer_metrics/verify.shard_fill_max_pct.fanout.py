from harness.mesh_readers import shard_fill_max_pct as read  # noqa: F401
