"""Four chips behind one verify intake: generator -> shm ring -> one
VerifyStage(devices=4) -> shm ring -> the harness's sink.  BASELINE.json
configs[4], cut to one four-chip host.

The tile topology's with a mesh behind the stage (verify_tile.py's
`System` reads `mesh.devices` itself): what is added here is one more
check (every chip was dealt signatures), two notes on the check line
(per-chip useful lanes, the close counters) and the mesh's prewarm.
A program whose verify stage cannot take a mesh (a commit before
VerifyStage read `devices=`) cannot run this configuration: loading
this file refuses it by name, with exit code 2, before anything is
built, compiled or signed.
"""

from __future__ import annotations

import sys

from firedancer_tpu.runtime.verify import VerifyStage

from harness.manifest import Manifest

_tile = Manifest().topology("verify_tile")


if not hasattr(VerifyStage, "metrics_schema_n"):
    print("benchmark: this program's VerifyStage takes no mesh (devices= "
          "is not read): it cannot run a verify_fanout configuration",
          file=sys.stderr)
    raise SystemExit(2)


def _mesh(config: dict) -> int:
    """-> the number of chips; the configuration's two ways of saying
    the batch have to agree."""
    m = config["mesh"]
    if config["verify"]["batch"] != m["devices"] * m["lanes_per_device"]:
        raise ValueError("verify.batch is not mesh.devices x "
                         "mesh.lanes_per_device")
    return m["devices"]


class System(_tile.System):
    def _shard_elems(self) -> list[int]:
        c = self.verify.metrics.counters
        return [int(c.get(f"shard_elems_s{i}", 0))
                for i in range(self.verify.mesh_devices)]

    def extra_checks(self) -> dict:
        """A run's verdicts have to come from every chip: one that was
        dealt no signature all run long was held to nothing."""
        return {"chips_dealt_no_signature":
                (sum(not n for n in self._shard_elems()), 0)}

    def notes(self) -> dict:
        c = self.verify.metrics.counters
        return {"shard_elems": self._shard_elems(),
                "batch_closes": {k: int(c.get(f"batch_close_{k}", 0))
                                 for k in ("full", "deadline", "window")}}


def prewarm(config: dict, control: str | None) -> float:
    """Compile (or load) the mesh program at its dispatch shape before
    the system is built, as harness.stages.prewarm_verify does for one
    device: a ringless stage over the same mesh makes the call the
    served stage makes.  -> seconds."""
    n_dev = _mesh(config)
    if control == "allpass":
        return 0.0
    v = config["verify"]
    return VerifyStage("prewarm", batch=v["batch"],
                       max_msg_len=v["max_msg_len"], native_client=False,
                       devices=n_dev).warmup()
