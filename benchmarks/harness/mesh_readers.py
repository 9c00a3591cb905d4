"""Arithmetic of the readers over a verify stage's per-shard counters
(`shard_elems_s{i}`: the useful lanes dispatched to chip i of its mesh,
deltas over the measured window).  A program whose stage has no such
counters (one device, or an older commit) gives None, and the metric is
left out."""

from __future__ import annotations

VERIFY = "verify0"
_PREFIX = "shard_elems_s"


def shard_fill_pcts(run) -> list[float] | None:
    """Per chip: the share of its lanes, over the window's batches,
    that carried a signature.  A chip's lanes are the batch over the
    chips that have a counter."""
    v = run["counters"].get(VERIFY, {})
    shards = []
    while f"{_PREFIX}{len(shards)}" in v:
        shards.append(v[f"{_PREFIX}{len(shards)}"])
    if not shards or not v.get("batches"):
        return None
    lanes = v["batches"] * run["batch"] / len(shards)
    return [100.0 * s / lanes for s in shards]


def shard_fill_max_pct(run):
    """The fullest chip's fill."""
    pcts = shard_fill_pcts(run)
    return None if pcts is None else max(pcts)


def shard_fill_min_pct(run):
    """The emptiest chip's fill."""
    pcts = shard_fill_pcts(run)
    return None if pcts is None else min(pcts)
