"""Layered TOML configuration -> typed config (the fdctl config system).

The reference embeds a default.toml, overlays the operator's --config TOML,
and parses the result into one typed config_t struct, rejecting unknown
keys (/root/reference/src/app/fdctl/config_parse.c; defaults
src/app/fdctl/config/default.toml).  Same shape here: DEFAULTS below is
the embedded layer, `load_config` deep-merges an optional TOML file and
explicit overrides on top, validates every key against the dataclass
schema (unknown keys are hard errors — silent typos in operator config
are how validators die), and returns a typed `Config`.

Topology is *derived* from config by code (models/leader.py
build_leader_pipeline takes these values), not data — matching the
reference's split between config_parse and topos/fd_frankendancer.c.
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field


@dataclass
class LayoutConfig:
    verify_stage_count: int = 1
    bank_stage_count: int = 2
    # sender tiles in front of a quic tile (runtime/benchs.py): 0 = no
    # front, the generator publishes into verify's ring; n >= 1 = the
    # front-door topology benchs x n -> UDP/QUIC -> quic -> verify
    # (models/leader_topo.build_quic_topology_from_config)
    benchs_stage_count: int = 0
    # replay verify tiles (runtime/replay_verify.py): 0 = the leader's
    # side; 1 = the follower's verify phase, source -> verify0 -> out
    # over entry batches, as [replay] describes it
    # (models/leader_topo.build_replay_topology_from_config)
    replay_stage_count: int = 0


@dataclass
class VerifyConfig:
    batch: int = 256
    max_msg_len: int = 1232
    batch_deadline_ms: float = 2.0
    receive_buffer_depth: int = 1024
    # chips behind each verify stage: 1 = the default device, n > 1 = a
    # mesh of the first n local devices (runtime/verify.VerifyStage)
    devices: int = 1


@dataclass
class PackConfig:
    depth: int = 4096
    max_txn_per_microblock: int = 31
    min_pending: int = 8
    microblock_deadline_ms: float = 2.0
    # a pool without room for one more burst leaves its txn input
    # unpolled (backpressure up to the source) instead of evicting its
    # cheapest transaction for the newcomer (runtime/pack_stage.py; the
    # process topology reads it)
    hold_when_full: bool = False


@dataclass
class LinksConfig:
    # ring depths of the process topology's links, in frags, powers of
    # two (models/leader_topo.py; the ring in front of verify is
    # verify.receive_buffer_depth, and the cooperative pipeline sizes
    # every ring but shred_store from that)
    verify_pack: int = 1024
    pack_bank: int = 256
    bank_poh: int = 256
    bank_done: int = 256
    poh_shred: int = 1024
    shred_store: int = 4096


@dataclass
class PohConfig:
    hashes_per_tick: int = 64
    ticks_per_slot: int = 8
    hashes_per_iter: int = 16
    # the wall-clock slot cadence the process topology runs under
    # (runtime/slot_clock.py); 0: free-running, slots seal on drain
    slot_ms: float = 0.0


@dataclass
class ShredConfig:
    shred_version: int = 1
    batch_target_sz: int = 16384


@dataclass
class NetConfig:
    listen_host: str = "127.0.0.1"
    listen_port: int = 0
    rx_burst: int = 64


@dataclass
class QuicConfig:
    # the quic tile (runtime/net.QuicIngressStage); it listens where
    # [net] says (listen_port 0: a free port, written to the run's
    # directory for the senders) and drains net.rx_burst datagrams a sweep
    reasm_depth: int = 64        # reassembly slots (fd_tpu_reasm)
    max_conns: int = 64
    retry: bool = False          # stateless Retry before any handshake
    # unidirectional streams a connection may have open that the tile
    # has not handed to verify's ring yet (initial_max_streams_uni;
    # credit returns as transactions are published)
    stream_window: int = 64
    # the senders' largest UDP payload (1,200: RFC 9000's floor, a
    # client's packet size before path-MTU discovery)
    max_datagram: int = 1200


@dataclass
class ReplayConfig:
    # the follower's verify phase (layout.replay_stage_count = 1): the
    # stage takes one entry batch of a received slot a frag and hands
    # on, in block order, the entry batches all of whose signatures
    # verified and whose entries' hashes follow, and a verdict a slot.
    # Its device batch, row bound and deadline are [verify]'s; the ring
    # in front is verify.receive_buffer_depth deep.
    frag_mtu: int = 65536        # both rings' mtu: the largest entry batch
    out_depth: int = 1024        # the ring behind the stage, in frags
    # the blocks the source tile offers (a leader's here: pack's
    # microblock of 31, the shred tile's batch_target_sz of two such
    # entries; [poh] gives the ticks)
    txns_per_entry: int = 31
    entries_per_batch: int = 2
    slot_txns: int = 39990       # transactions a slot
    # one slot in this many is offered with a flipped signature bit
    # (0: none): that slot is dead from the entry batch holding it
    dead_one_in_slots: int = 16


@dataclass
class LedgerConfig:
    # empty = in-memory funk; a directory enables the write-ahead
    # journal + snapshot persistence (funk/persist.py)
    funk_dir: str = ""
    blockstore_dir: str = ""


@dataclass
class GenesisConfig:
    # what the leader's bank holds when its slot opens, beside the funded
    # payers (runtime/bank.genesis_bank_ctx): a validator set's vote
    # accounts and the SlotHashes sysvar their votes are checked against
    # (0 / 0: none, and every vote rejects)
    n_voters: int = 0
    slot_hashes: int = 0


@dataclass
class BenchConfig:
    # upstream's [development.bench] keys of its committed bench profile
    # (src/app/fdctl/config/bench-zen3-32core.toml), under its names
    # and off by default.  larger_max_cost_per_block: pack closes a
    # block at 18 x the stock 48M cost units (pack/cost.py
    # LARGER_MAX_COST_PER_BLOCK) — blocks no other validator would
    # accept, for measuring the tiles rather than the limit.
    # disable_status_cache: the bank keeps no status cache, so
    # exactly-once rests on dedup's and pack's signature tags (65,536
    # deep each) and a repeat that outlives them lands again; bank
    # tiles in processes of their own need it, since a cache a process
    # would let such a repeat land once a tile (models/leader_topo.py).
    larger_max_cost_per_block: bool = False
    disable_status_cache: bool = False


@dataclass
class DevelopmentConfig:
    bench: BenchConfig = field(default_factory=BenchConfig)


@dataclass
class LogConfig:
    path: str = ""
    level_stderr: str = "NOTICE"
    level_file: str = "INFO"


@dataclass
class Config:
    layout: LayoutConfig = field(default_factory=LayoutConfig)
    verify: VerifyConfig = field(default_factory=VerifyConfig)
    pack: PackConfig = field(default_factory=PackConfig)
    links: LinksConfig = field(default_factory=LinksConfig)
    poh: PohConfig = field(default_factory=PohConfig)
    shred: ShredConfig = field(default_factory=ShredConfig)
    net: NetConfig = field(default_factory=NetConfig)
    quic: QuicConfig = field(default_factory=QuicConfig)
    replay: ReplayConfig = field(default_factory=ReplayConfig)
    ledger: LedgerConfig = field(default_factory=LedgerConfig)
    genesis: GenesisConfig = field(default_factory=GenesisConfig)
    development: DevelopmentConfig = field(default_factory=DevelopmentConfig)
    log: LogConfig = field(default_factory=LogConfig)


class ConfigError(ValueError):
    pass


def _merge_into(obj, data: dict, path: str) -> None:
    """Apply a nested dict onto a dataclass tree, strictly typed."""
    names = {f.name: f for f in dataclasses.fields(obj)}
    for key, val in data.items():
        if key not in names:
            raise ConfigError(f"unknown config key '{path}{key}'")
        cur = getattr(obj, key)
        if dataclasses.is_dataclass(cur):
            if not isinstance(val, dict):
                raise ConfigError(f"'{path}{key}' must be a table")
            _merge_into(cur, val, f"{path}{key}.")
            continue
        want = type(cur)
        if want is float and isinstance(val, int):
            val = float(val)
        if not isinstance(val, want) or isinstance(val, bool) != (want is bool):
            raise ConfigError(
                f"'{path}{key}' must be {want.__name__}, "
                f"got {type(val).__name__}"
            )
        setattr(obj, key, val)


def load_config(
    path: str | None = None, overrides: dict | None = None
) -> Config:
    """defaults <- TOML file at `path` <- `overrides` dict, validated."""
    cfg = Config()
    if path is not None:
        with open(path, "rb") as f:
            # the framework's own TOML parser (protocol/toml.py) — the
            # config file is operator input parsed before anything else
            # is up, matching the reference's vendored-parser stance
            from firedancer_tpu.protocol import toml as _toml

            data = _toml.load(f)
        _merge_into(cfg, data, "")
    if overrides:
        _merge_into(cfg, overrides, "")
    _validate(cfg)
    return cfg


def _validate(cfg: Config) -> None:
    if cfg.layout.verify_stage_count < 1:
        raise ConfigError("layout.verify_stage_count must be >= 1")
    if not 1 <= cfg.layout.bank_stage_count <= 62:  # fd_pack.h MAX_BANK_TILES
        raise ConfigError("layout.bank_stage_count must be in [1, 62]")
    if not 0 <= cfg.layout.benchs_stage_count <= 64:
        raise ConfigError("layout.benchs_stage_count must be in [0, 64]")
    if not 0 <= cfg.layout.replay_stage_count <= 1:
        raise ConfigError("layout.replay_stage_count must be 0 or 1 (the "
                          "entry batches of a slot go to one tile)")
    if cfg.layout.replay_stage_count and cfg.layout.benchs_stage_count:
        raise ConfigError("layout.replay_stage_count and "
                          "layout.benchs_stage_count select two topologies")
    r = cfg.replay
    if not 1024 <= r.frag_mtu <= 65536:
        raise ConfigError("replay.frag_mtu must be in [1024, 65536]")
    if r.out_depth < 1 or r.out_depth & (r.out_depth - 1):
        raise ConfigError("replay.out_depth must be a power of 2")
    if r.txns_per_entry < 1 or r.entries_per_batch < 1 or r.slot_txns < 1 \
            or r.dead_one_in_slots < 0:
        raise ConfigError("replay.txns_per_entry, replay.entries_per_batch "
                          "and replay.slot_txns must be >= 1, "
                          "replay.dead_one_in_slots >= 0")
    q = cfg.quic
    if q.reasm_depth < 1 or q.max_conns < 1 or q.stream_window < 1:
        raise ConfigError("quic.reasm_depth, quic.max_conns and "
                          "quic.stream_window must be >= 1")
    if q.max_conns < cfg.layout.benchs_stage_count:
        raise ConfigError("quic.max_conns must hold every sender tile "
                          "(layout.benchs_stage_count)")
    if not 256 <= q.max_datagram <= 1452:
        raise ConfigError("quic.max_datagram must be in [256, 1452]")
    if cfg.verify.batch < 1 or cfg.verify.batch & (cfg.verify.batch - 1):
        raise ConfigError("verify.batch must be a power of 2")
    if cfg.verify.devices < 1 or cfg.verify.batch % cfg.verify.devices:
        raise ConfigError("verify.devices must be >= 1 and divide verify.batch")
    if cfg.poh.hashes_per_tick < 1 or cfg.poh.ticks_per_slot < 1 \
            or cfg.poh.slot_ms < 0:
        raise ConfigError("poh cadence must be positive")
    for f in dataclasses.fields(cfg.links):
        d = getattr(cfg.links, f.name)
        if d < 1 or d & (d - 1):
            raise ConfigError(f"links.{f.name} must be a power of 2")
    if cfg.genesis.n_voters < 0 or not 0 <= cfg.genesis.slot_hashes <= 512:
        raise ConfigError("genesis.n_voters must be >= 0 and "
                          "genesis.slot_hashes in [0, 512]")
    if cfg.shred.batch_target_sz < 1:
        raise ConfigError("shred.batch_target_sz must be positive")
