// Native verify-stage sweep client (ISSUE 13): the verify tile's HOST
// orchestration with zero Python per frag.
//
// The second client of the generic sweep harness (fd_ring.cpp's
// fdr_sweep; the shredder was the first): a registered verify stage's
// whole intake sweep — shard filter, txn parse (through a function
// pointer into fd_txn_parse.so: one parser implementation), the tiny
// per-stage tcache dedup guard, the msg-length / batch-fit guards, and
// fixed-shape batch assembly into reusable slot buffers — runs inside
// ONE FFI crossing.  Python's per-batch work shrinks to handing a sealed
// slot's packed rows (one contiguous array, what the device program
// takes) to the device and publishing the
// reaped frames (fdr_publish_burst straight out of the slot's
// preassembled frame arena: payload || packed-descriptor || u16 len,
// the verified-frag wire framing, built HERE so the emit path never
// touches frame bytes in Python).
//
// Slot ring = the async in-flight window: slots are acquired, sealed,
// dispatched and released in cyclic order, so batch submission and
// reaping stay in order by construction (the wiredancer discipline).
// When every slot is busy the intake stashes a bounded FIFO of frags
// and stops the sweep (cb < 0) — verify backpressures instead of
// dropping; only a dead/wedged consumer can overflow the stash, and
// those drops are counted.
//
// Semantics parity with runtime/verify.py's _intake/_accumulate is the
// contract (tests/test_verify_native.py stream-diffs the lanes):
// guards run in the same order (parse -> tcache -> msg-len -> fit),
// the tcache matches tango/rings.TCache (depth-16 ring, tag 0 never
// dedups), and a txn's elements always land in one batch.
//
// A second intake beside the per-frag one (below, "The replay
// intake"): a follower's replay tile hands the stage ENTRY BATCHES of a
// received block, not transactions; the slots, the fit rule, seal and
// acquire are this file's one set, the door and the way out differ.
//
// Build: g++ -O2 -shared -fPIC -o fd_verify.so fd_verify.cpp

#include <cstdint>
#include <cstdlib>
#include <cstring>

#include "fd_metrics.h"  // fdm_now_ns: the clock of time.monotonic_ns()
#include "fd_sha256.h"   // the replay intake's PoH check

namespace {

typedef int64_t (*fdv_parse_fn)(const uint8_t*, uint64_t, uint8_t*, uint64_t);

constexpr uint64_t TXN_MTU = 1232;
constexpr uint64_t DESC_CAP = 2048;  // packed desc max is 1863 bytes
constexpr uint64_t FRAME_CAP = TXN_MTU + DESC_CAP + 2;
constexpr int TC_DEPTH = 16;  // runtime/verify.VERIFY_TCACHE_DEPTH
constexpr int STASH_CAP = 8;

// One element's row in a slot, everything the device program reads of
// it, so a batch goes to the device as ONE contiguous array:
//   msg[mml] (zero past msg_len) | sig[64] | pk[32] | msg_len (u32 LE)
// The offsets count from the end of the message; ROW_TAIL is what a row
// holds past it.  runtime/verify_native mirrors them (fdlint FD305), as
// do ops/sigverify's on-device unpack and the Python lane's _assemble
// (tests/test_verify_kernels.py holds the three to the same bytes).
constexpr uint64_t ROW_SIG_OFF = 0;
constexpr uint64_t ROW_PK_OFF = 64;
constexpr uint64_t ROW_LEN_OFF = 96;
constexpr uint64_t ROW_TAIL = 100;

enum { SLOT_FREE = 0, SLOT_OPEN = 1, SLOT_SEALED = 2, SLOT_INFLIGHT = 3 };

// why a batch was sealed (runtime/verify.py CLOSE_*, the ids of its
// batch_close_{full,deadline,window} counters): full is decided here,
// in the crossing; the other two are the caller's, through fdv_seal
enum { CLOSE_FULL = 0, CLOSE_DEADLINE = 1, CLOSE_WINDOW = 2 };

// one row per slot, viewed zero-FFI from Python (u64 x META_NCOL;
// runtime/verify_native._META_NCOL mirrors it, fdlint FD305)
constexpr uint64_t META_NCOL = 7;
struct fdv_slot_meta {
  uint64_t state;
  uint64_t n_elems;
  uint64_t n_txn;
  uint64_t arena_off;
  // the batch's first two stamps (CLOCK_MONOTONIC ns, the clock of
  // time.monotonic_ns() and of tsorig): when its first element entered
  // the slot, and when the slot was sealed.  Two clock reads a batch.
  uint64_t opened_ns;
  uint64_t sealed_ns;
  uint64_t close;  // CLOSE_*: what sealed it
};
static_assert(sizeof(fdv_slot_meta) == META_NCOL * sizeof(uint64_t),
              "slot-meta row is META_NCOL u64s");

struct fdv_slot {
  uint8_t* rows;     // batch x (mml + ROW_TAIL), elem e in row e
  uint64_t* frames;  // batch x 4: (arena off, sz, sig_tag, tsorig) —
                     // fdr_publish_burst's frame-table format verbatim
  uint32_t* ranges;  // batch x 2: element [start, end) per txn
  uint8_t* arena;    // frame bytes (payload || packed || u16 payload_sz)
};

struct fdv_stash_ent {
  uint64_t sz;
  uint64_t tsorig;
  uint8_t buf[TXN_MTU];
};

struct fdv_replay;  // the entry-batch intake's state (below)

struct fdv_stage {
  uint64_t shard_idx, shard_cnt, batch, mml, n_slots;
  fdv_parse_fn parse;
  fdv_replay* rp;  // non-null: the stage takes entry batches, not txns
  uint64_t tc_ring[TC_DEPTH];
  uint64_t tc_oldest;
  fdv_slot* slots;
  fdv_slot_meta* meta;
  int64_t open;        // open slot index, -1 = none
  uint64_t next_open;  // cyclic acquire cursor (dispatch order)
  fdv_stash_ent stash[STASH_CAP];
  uint64_t stash_head, stash_n;
  uint8_t desc[DESC_CAP];
  // tail: flags + open_elems + open_ns + counters, contiguous u64s for
  // the Python view — keep declaration order in sync with
  // runtime/verify_native._COUNTERS
  uint64_t flags;       // bit0: stash nonempty
  uint64_t open_elems;  // elements in the open slot
  uint64_t open_ns;     // the open slot's opened_ns while it holds
                        // elements, else 0 (deadline probe: Python reads
                        // ONE word per pump; the stamp names the batch)
  uint64_t c_filtered, c_frags_in, c_parse_fail, c_dedup_dup,
      c_msg_too_long, c_too_many_sigs, c_txn_in, c_elems_in,
      c_intake_dropped, c_sealed_batches,
      // lanes batches sealed for want of room left empty: the next
      // txn's signatures did not fit (a txn's elements land in ONE batch)
      c_batch_fit_pad_lanes;
};

void rp_note_acquire(fdv_stage* s, uint64_t slot_idx);
bool rp_emit_ready(const fdv_stage* s);
bool rp_room(const fdv_stage* s);
bool rp_pending(const fdv_stage* s);

inline void set_flags(fdv_stage* s) {
  // bit0: stash nonempty; bit1: intake has room (the sweep gate Python
  // reads as ONE word instead of scanning the slot table per iteration)
  bool room = s->open >= 0 && s->meta[s->open].n_elems < s->batch;
  if (!room) {
    for (uint64_t i = 0; i < s->n_slots; i++) {
      if (s->meta[i].state == SLOT_FREE) {
        room = true;
        break;
      }
    }
  }
  if (s->rp) {
    // the replay intake: bit0 = an entry batch is part-way into the
    // slots; bit1 = the next frag can be held and started; bit2 = the
    // oldest held entry batch has its verdict (fdv_replay_collect)
    bool pending = rp_pending(s);
    s->flags = (pending ? 1u : 0u) |
               ((!pending && room && rp_room(s)) ? 2u : 0u) |
               (rp_emit_ready(s) ? 4u : 0u);
  } else {
    s->flags = (s->stash_n ? 1u : 0u) | ((!s->stash_n && room) ? 2u : 0u);
  }
  s->open_elems = s->open >= 0 ? s->meta[s->open].n_elems : 0;
  s->open_ns = s->open_elems ? s->meta[s->open].opened_ns : 0;
}

bool acquire_open(fdv_stage* s) {
  fdv_slot_meta* m = &s->meta[s->next_open];
  if (m->state != SLOT_FREE) return false;
  m->state = SLOT_OPEN;
  m->n_elems = 0;
  m->n_txn = 0;
  m->arena_off = 0;
  m->opened_ns = fdm_now_ns();
  m->sealed_ns = 0;
  m->close = CLOSE_FULL;
  s->open = (int64_t)s->next_open;
  if (s->rp) rp_note_acquire(s, s->next_open);
  s->next_open = (s->next_open + 1) % s->n_slots;
  return true;
}

void seal_open(fdv_stage* s, uint64_t why) {
  if (s->open < 0) return;
  fdv_slot_meta* m = &s->meta[s->open];
  if (!m->n_txn) return;  // nothing accumulated: stay open
  m->sealed_ns = fdm_now_ns();
  m->close = why;
  m->state = SLOT_SEALED;
  s->open = -1;
  s->c_sealed_batches++;
}

// one txn's elements into the open slot's packed rows, a row a
// signature, and its element range (both intakes' row writer)
inline void put_rows(fdv_stage* s, fdv_slot* sl, const fdv_slot_meta* m,
                     const uint8_t* payload, uint64_t sig_cnt,
                     uint64_t sig_off, uint64_t msg_off, uint64_t acct_off,
                     uint64_t msg_len) {
  for (uint64_t i = 0; i < sig_cnt; i++) {
    uint8_t* row = sl->rows + (m->n_elems + i) * (s->mml + ROW_TAIL);
    uint8_t* tail = row + s->mml;
    std::memcpy(row, payload + msg_off, msg_len);
    std::memset(row + msg_len, 0, s->mml - msg_len);
    std::memcpy(tail + ROW_SIG_OFF, payload + sig_off + 64 * i, 64);
    std::memcpy(tail + ROW_PK_OFF, payload + acct_off + 32 * i, 32);
    for (int k = 0; k < 4; k++)
      tail[ROW_LEN_OFF + k] = (uint8_t)(msg_len >> (8 * k));
  }
  sl->ranges[2 * m->n_txn] = (uint32_t)m->n_elems;
  sl->ranges[2 * m->n_txn + 1] = (uint32_t)(m->n_elems + sig_cnt);
}

// one txn through the guards + batch assembly; 0 = handled (accepted or
// counted drop), 1 = no slot room (caller stashes, order preserved)
int ingest(fdv_stage* s, const uint8_t* payload, uint64_t sz,
           uint64_t tsorig) {
  if (sz > TXN_MTU) {  // parser would reject; bound the stash/arena copy
    s->c_parse_fail++;
    return 0;
  }
  int64_t dn = s->parse(payload, sz, s->desc, DESC_CAP);
  if (dn < 0) {
    s->c_parse_fail++;
    return 0;
  }
  const uint8_t* d = s->desc;
  uint64_t sig_cnt = d[1];
  uint64_t sig_off = (uint64_t)d[2] | ((uint64_t)d[3] << 8);
  uint64_t msg_off = (uint64_t)d[4] | ((uint64_t)d[5] << 8);
  uint64_t acct_off = (uint64_t)d[9] | ((uint64_t)d[10] << 8);
  // room PROBE before any stateful guard: a no-room txn returns to the
  // stash untouched — if the tcache insert ran first, the retry would
  // see its own tag and self-deduplicate (a dropped txn, found by
  // test_stalled_consumer_backpressures_intake)
  bool need_new =
      s->open < 0 || s->meta[s->open].n_elems + sig_cnt > s->batch;
  if (need_new && s->meta[s->next_open].state != SLOT_FREE) return 1;
  // dedup tag: low 8 bytes of the first signature (sig_tag), BEFORE the
  // length/fit guards — the Python lane's guard order exactly
  uint64_t tag;
  std::memcpy(&tag, payload + sig_off, 8);
  if (!tag) tag = 1;
  for (int i = 0; i < TC_DEPTH; i++) {
    if (s->tc_ring[i] == tag) {
      s->c_dedup_dup++;
      return 0;
    }
  }
  s->tc_ring[s->tc_oldest] = tag;
  s->tc_oldest = (s->tc_oldest + 1) % TC_DEPTH;
  uint64_t msg_len = sz - msg_off;
  if (msg_len > s->mml) {
    s->c_msg_too_long++;
    return 0;
  }
  if (sig_cnt > s->batch) {
    s->c_too_many_sigs++;
    return 0;
  }
  if (s->open < 0) acquire_open(s);  // cannot fail: probed above
  fdv_slot_meta* m = &s->meta[s->open];
  if (m->n_elems + sig_cnt > s->batch) {
    s->c_batch_fit_pad_lanes += s->batch - m->n_elems;
    seal_open(s, CLOSE_FULL);
    acquire_open(s);  // cannot fail: probed above
    m = &s->meta[s->open];
  }
  fdv_slot* sl = &s->slots[s->open];
  put_rows(s, sl, m, payload, sig_cnt, sig_off, msg_off, acct_off, msg_len);
  uint64_t off = m->arena_off;
  std::memcpy(sl->arena + off, payload, sz);
  std::memcpy(sl->arena + off + sz, s->desc, (uint64_t)dn);
  sl->arena[off + sz + dn] = (uint8_t)(sz & 0xFF);
  sl->arena[off + sz + dn + 1] = (uint8_t)(sz >> 8);
  uint64_t* fr = sl->frames + 4 * m->n_txn;
  fr[0] = off;
  fr[1] = sz + (uint64_t)dn + 2;
  fr[2] = tag;
  fr[3] = tsorig;
  m->arena_off += sz + (uint64_t)dn + 2;
  m->n_txn++;
  m->n_elems += sig_cnt;
  s->c_txn_in++;
  s->c_elems_in += sig_cnt;
  if (m->n_elems >= s->batch) seal_open(s, CLOSE_FULL);
  return 0;
}

void pump(fdv_stage* s) {
  while (s->stash_n) {
    fdv_stash_ent* e = &s->stash[s->stash_head];
    if (ingest(s, e->buf, e->sz, e->tsorig)) break;  // still no room
    s->stash_head = (s->stash_head + 1) % STASH_CAP;
    s->stash_n--;
  }
  set_flags(s);
}

void stash_push(fdv_stage* s, const uint8_t* payload, uint64_t sz,
                uint64_t tsorig) {
  if (s->stash_n >= STASH_CAP) {
    // every slot busy AND the stash full: only a dead/wedged consumer
    // gets here (the emit side frees slots as credits return) — count
    // the loss instead of growing without bound
    s->c_intake_dropped++;
    return;
  }
  fdv_stash_ent* e = &s->stash[(s->stash_head + s->stash_n) % STASH_CAP];
  e->sz = sz;
  e->tsorig = tsorig;
  std::memcpy(e->buf, payload, sz);
  s->stash_n++;
  set_flags(s);
}

int append_one(fdv_stage* s, const uint8_t* payload, uint64_t sz,
               uint64_t tsorig) {
  s->c_frags_in++;
  int r = 0;
  if (sz > TXN_MTU) {  // stash entries are TXN_MTU-bounded
    s->c_parse_fail++;
  } else {
    pump(s);
    if (s->stash_n) {  // order: queued frags go first
      stash_push(s, payload, sz, tsorig);
      r = -1;
    } else if (ingest(s, payload, sz, tsorig)) {
      stash_push(s, payload, sz, tsorig);
      r = -1;
    }
  }
  set_flags(s);
  return r;
}

// ---------------------------------------------------------------------------
// The replay intake (runtime/replay_verify.py): a follower's verify
// phase.  A frag is one ENTRY BATCH of a received slot,
//
//   u64 slot | u32 batch idx | u32 flags | [32 B PoH seed iff SEED] |
//   (u32 len | u32 num_hashes | 32 B hash | u16 cnt | (u16 len | txn)*)*
//
// and what leaves is the same frag, byte for byte, once every signature
// of every transaction in it has passed on the device and every entry's
// hash follows — in block order — and one verdict frame a slot.  The
// device side is the per-frag intake's own: the same slots of packed
// rows, the same fit rule, seal and acquire; a device batch is filled
// across entry batches and slots.  No tag cache on this path: a
// follower verifies a repeated transaction like any other.
//
// An entry batch is walked ONCE on arrival (structure, one parse a
// transaction through the one parser, the PoH chain under fd_sha256.h),
// copied into a ring arena of held frags, and its transactions' rows
// are put into the slots from the table that walk made, resuming where
// it stalled for want of a slot.  A held frag leaves (or is rejected,
// or skipped) when the device batch that took its last lane has been
// reaped: fdv_replay_reap marks what failed, fdv_replay_collect writes
// the frame table of what is due out, in order, for fdr_publish_burst.
//
// A slot is dead from its first failing entry batch on (reason: a
// signature, a hash that does not follow, a transaction that does not
// parse): nothing of it at or after that batch leaves, what arrives of
// it later is dropped at the door, what is held of it is skipped, and
// both are counted (dead_slot_txn_skipped; dead_slot_lanes_spent for
// the lanes already in a slot).  The next slot starts clean.

constexpr uint64_t RP_HDR = 16;
constexpr uint64_t RP_SEED = 32;
constexpr uint64_t RP_VERDICT_SZ = 16;
constexpr uint64_t RP_FRAG_MAX = 65536;  // the in link's mtu
constexpr uint32_t RP_F_LAST = 1, RP_F_SEED = 2, RP_F_VERDICT = 4;
constexpr uint64_t RP_SIG_VERDICT = 1ull << 63;
// why a slot died (the verdict frame's reason byte; 0 = live)
enum { RP_OK = 0, RP_SIG = 1, RP_POH = 2, RP_PARSE = 3 };
constexpr uint64_t RP_MAX_TXN = 1024;  // 65,536 B / the shortest txn
constexpr uint64_t RP_MAX_ENT = 2048;  // 65,536 B / an empty entry
constexpr uint64_t RP_OUT_CAP = 1024;  // frame-table rows a collect

struct rp_txn {  // one parsed transaction of the frag being put in
  uint32_t off, sz;  // in the frag
  uint16_t sig_cnt, sig_off, msg_off, acct_off;
};

struct rp_ent {
  uint32_t num_hashes, hash_off, txn0, txn_n;
};

struct rp_rec {  // one held entry batch
  uint64_t off, adv, sz;  // its bytes in the arena; ring bytes it took
  uint64_t slot, tsorig;
  uint64_t last_seq;  // the device batch that took its newest lane
  uint32_t idx, flags, n_txn, n_lanes;
  uint32_t fail;  // RP_*
  uint32_t done;  // every lane it will ever get is in a slot
};

struct fdv_replay {
  uint8_t* arena;
  uint64_t cap, a_head, a_tail;  // ring positions, monotonic
  rp_rec* recs;
  uint64_t rec_mask, r_head, r_emit, r_tail;
  // the slot the door is in
  uint64_t cur_slot, next_idx;
  uint32_t have_slot, cur_dead;
  uint8_t chain[32];
  // the frag part-way into the slots (r_head - 1 while `pending`)
  uint32_t pending;
  uint64_t cur_txn, n_txns, n_ents;
  rp_txn txns[RP_MAX_TXN];
  rp_ent ents[RP_MAX_ENT];
  // device batches in acquire order (which is dispatch and reap order)
  uint64_t acq_seq, reaped_seq;
  uint64_t* slot_seq;  // n_slots
  uint32_t** rec_of;   // n_slots x batch: the txn's record (ring index)
  // the slot being skipped on the way out
  uint64_t emit_dead_slot;
  uint32_t emit_dead;
  uint64_t out_tbl[RP_OUT_CAP * 4];
  // counters, contiguous u64s for the Python view — keep declaration
  // order in sync with runtime/verify_native._REPLAY_COUNTERS
  uint64_t c_entry_batches_in, c_entries_in, c_slots_live, c_slots_dead_sig,
      c_slots_dead_poh, c_slots_dead_parse, c_dead_slot_txn_skipped,
      c_dead_slot_lanes_spent, c_poh_hashes, c_poh_check_ns,
      c_entry_unpack_ns, c_entry_batches_out, c_entry_txn_out,
      c_entry_txn_rejected, c_verify_fail, c_verify_fail_elems;
};

inline uint32_t rd32(const uint8_t* p) {
  return (uint32_t)p[0] | (uint32_t)p[1] << 8 | (uint32_t)p[2] << 16 |
         (uint32_t)p[3] << 24;
}
inline uint64_t rd64(const uint8_t* p) {
  return (uint64_t)rd32(p) | (uint64_t)rd32(p + 4) << 32;
}
inline void wr32(uint8_t* p, uint32_t v) {
  for (int k = 0; k < 4; k++) p[k] = (uint8_t)(v >> (8 * k));
}
inline void wr64(uint8_t* p, uint64_t v) {
  wr32(p, (uint32_t)v);
  wr32(p + 4, (uint32_t)(v >> 32));
}

inline rp_rec* rp_at(fdv_replay* r, uint64_t i) {
  return &r->recs[i & r->rec_mask];
}

void rp_note_acquire(fdv_stage* s, uint64_t slot_idx) {
  s->rp->slot_seq[slot_idx] = ++s->rp->acq_seq;
}

bool rp_pending(const fdv_stage* s) { return s->rp->pending != 0; }

bool rp_room(const fdv_stage* s) {
  // a frag of the largest size can be held wherever the ring's head
  // stands (a wrap pads at most a frag's worth), and has a record
  const fdv_replay* r = s->rp;
  return r->cap - (r->a_head - r->a_tail) >=
             2 * (RP_FRAG_MAX + RP_VERDICT_SZ) &&
         r->r_head - r->r_tail <= r->rec_mask;
}

bool rp_emit_ready(const fdv_stage* s) {
  const fdv_replay* r = s->rp;
  if (r->r_emit == r->r_head) return false;
  const rp_rec* rec = &r->recs[r->r_emit & r->rec_mask];
  return rec->done && rec->last_seq <= r->reaped_seq;
}

// is slot `slot` known dead to a record at ring position `at`: an
// earlier held record of it failed, or its verdict is out already
bool rp_slot_dead(fdv_replay* r, uint64_t slot, uint64_t at) {
  if (r->emit_dead && r->emit_dead_slot == slot) return true;
  for (uint64_t i = r->r_emit; i < at; i++) {
    rp_rec* e = rp_at(r, i);
    if (e->slot == slot && e->fail) return true;
  }
  return false;
}

// transactions the entries of `p` claim (the count fields alone): what
// a frag dropped at the door is counted by
uint64_t rp_claimed_txns(const uint8_t* p, uint64_t sz) {
  uint64_t o = 0, n = 0;
  while (o + 4 <= sz) {
    uint64_t len = rd32(p + o);
    o += 4;
    if (len < 38 || o + len > sz) break;
    n += (uint64_t)p[o + 36] | (uint64_t)p[o + 37] << 8;
    o += len;
  }
  return n;
}

// the walk: structure, one parse a transaction, the guards -> the txn
// and entry tables.  -> RP_OK or RP_PARSE.
int rp_unpack(fdv_stage* s, const uint8_t* f, uint64_t o, uint64_t sz) {
  fdv_replay* r = s->rp;
  r->n_txns = r->n_ents = 0;
  while (o < sz) {
    if (o + 4 > sz) return RP_PARSE;
    uint64_t len = rd32(f + o);
    o += 4;
    if (len < 38 || o + len > sz || r->n_ents >= RP_MAX_ENT) return RP_PARSE;
    const uint8_t* e = f + o;
    rp_ent* en = &r->ents[r->n_ents++];
    en->num_hashes = rd32(e);
    en->hash_off = (uint32_t)(o + 4);
    en->txn0 = (uint32_t)r->n_txns;
    uint64_t cnt = (uint64_t)e[36] | (uint64_t)e[37] << 8;
    uint64_t q = 38;
    for (uint64_t k = 0; k < cnt; k++) {
      if (q + 2 > len) return RP_PARSE;
      uint64_t tl = (uint64_t)e[q] | (uint64_t)e[q + 1] << 8;
      q += 2;
      if (q + tl > len || tl > TXN_MTU || r->n_txns >= RP_MAX_TXN)
        return RP_PARSE;
      int64_t dn = s->parse(e + q, tl, s->desc, DESC_CAP);
      if (dn < 0) {
        s->c_parse_fail++;
        return RP_PARSE;
      }
      const uint8_t* d = s->desc;
      rp_txn* t = &r->txns[r->n_txns];
      t->off = (uint32_t)(o + q);
      t->sz = (uint32_t)tl;
      t->sig_cnt = d[1];
      t->sig_off = (uint16_t)(d[2] | d[3] << 8);
      t->msg_off = (uint16_t)(d[4] | d[5] << 8);
      t->acct_off = (uint16_t)(d[9] | d[10] << 8);
      // a transaction the device cannot be given is one the slot
      // cannot be verified with
      if (tl - t->msg_off > s->mml) {
        s->c_msg_too_long++;
        return RP_PARSE;
      }
      if (t->sig_cnt > s->batch) {
        s->c_too_many_sigs++;
        return RP_PARSE;
      }
      r->n_txns++;
      q += tl;
    }
    if (q != len) return RP_PARSE;
    en->txn_n = (uint32_t)r->n_txns - en->txn0;
    o += len;
  }
  return RP_OK;
}

// the chain over the walked entries: num_hashes appends (the last of
// them the mixin for a transaction entry: sha256 over the entry's
// first signatures), compare.  A transaction entry with num_hashes 0
// does not follow.  -> RP_OK or RP_POH; the chain is left at the last
// entry's hash.
int rp_poh(fdv_stage* s, const uint8_t* f) {
  fdv_replay* r = s->rp;
  uint8_t h[32];
  std::memcpy(h, r->chain, 32);
  for (uint64_t i = 0; i < r->n_ents; i++) {
    const rp_ent* en = &r->ents[i];
    uint64_t n = en->num_hashes;
    if (en->txn_n) {
      if (n < 1) return RP_POH;
      n--;
    }
    for (uint64_t k = 0; k < n; k++) {
      fdsha::Sha256 a;
      a.update(h, 32);
      a.final(h);
    }
    r->c_poh_hashes += n;
    if (en->txn_n) {
      uint8_t mix[32];
      fdsha::Sha256 m;
      for (uint64_t k = 0; k < en->txn_n; k++) {
        const rp_txn* t = &r->txns[en->txn0 + k];
        m.update(f + t->off + t->sig_off, 64);
      }
      m.final(mix);
      fdsha::Sha256 a;
      a.update(h, 32);
      a.update(mix, 32);
      a.final(h);
      r->c_poh_hashes++;
    }
    if (std::memcmp(h, f + en->hash_off, 32) != 0) return RP_POH;
  }
  std::memcpy(r->chain, h, 32);
  return RP_OK;
}

// the pending frag's transactions into the slots, from where it
// stalled; 0 = all in (or its slot died meanwhile), 1 = no slot free
int rp_ingest(fdv_stage* s) {
  fdv_replay* r = s->rp;
  rp_rec* rec = rp_at(r, r->r_head - 1);
  const uint8_t* f = r->arena + rec->off;
  while (r->cur_txn < r->n_txns && !rec->fail &&
         !(r->cur_dead && rec->slot == r->cur_slot)) {
    const rp_txn* t = &r->txns[r->cur_txn];
    uint64_t sig_cnt = t->sig_cnt;
    bool need_new =
        s->open < 0 || s->meta[s->open].n_elems + sig_cnt > s->batch;
    if (need_new && s->meta[s->next_open].state != SLOT_FREE) return 1;
    if (s->open < 0) acquire_open(s);
    fdv_slot_meta* m = &s->meta[s->open];
    if (m->n_elems + sig_cnt > s->batch) {
      s->c_batch_fit_pad_lanes += s->batch - m->n_elems;
      seal_open(s, CLOSE_FULL);
      acquire_open(s);
      m = &s->meta[s->open];
    }
    put_rows(s, &s->slots[s->open], m, f + t->off, sig_cnt, t->sig_off,
             t->msg_off, t->acct_off, t->sz - t->msg_off);
    r->rec_of[s->open][m->n_txn] = (uint32_t)((r->r_head - 1) & r->rec_mask);
    m->n_txn++;
    m->n_elems += sig_cnt;
    s->c_txn_in++;
    s->c_elems_in += sig_cnt;
    rec->n_lanes += (uint32_t)sig_cnt;
    rec->last_seq = r->slot_seq[s->open];
    if (m->n_elems >= s->batch) seal_open(s, CLOSE_FULL);
    r->cur_txn++;
  }
  rec->done = 1;
  r->pending = 0;
  return 0;
}

// a record for the frag (its bytes held when they may still leave)
rp_rec* rp_hold(fdv_replay* r, const uint8_t* f, uint64_t sz, uint64_t slot,
                uint32_t idx, uint32_t flags, uint64_t tsorig, bool bytes) {
  rp_rec* rec = rp_at(r, r->r_head++);
  std::memset(rec, 0, sizeof(*rec));
  uint64_t want = (bytes ? sz : 0) + RP_VERDICT_SZ;
  uint64_t pos = r->a_head % r->cap;
  uint64_t pad = pos + want > r->cap ? r->cap - pos : 0;
  rec->off = pad ? 0 : pos;
  rec->adv = pad + want;
  rec->sz = bytes ? sz : 0;
  r->a_head += rec->adv;
  if (bytes) std::memcpy(r->arena + rec->off, f, sz);
  rec->slot = slot;
  rec->idx = idx;
  rec->flags = flags;
  rec->tsorig = tsorig;
  return rec;
}

// one frag at the door.  The caller saw room (set_flags bit1).
void rp_frag(fdv_stage* s, const uint8_t* f, uint64_t sz, uint64_t tsorig) {
  fdv_replay* r = s->rp;
  uint64_t t0 = fdm_now_ns();
  s->c_frags_in++;
  r->c_entry_batches_in++;
  uint64_t slot = 0;
  uint32_t idx = 0, flags = 0;
  uint64_t body = RP_HDR;
  bool framed = sz >= RP_HDR && sz <= RP_FRAG_MAX;
  if (framed) {
    slot = rd64(f);
    idx = rd32(f + 8);
    flags = rd32(f + 12);
    if (flags & RP_F_SEED) body += RP_SEED;
    framed = body <= sz && !(flags & RP_F_VERDICT) &&
             ((flags & RP_F_SEED) != 0) == (idx == 0);
  }
  if (framed && idx == 0) {  // a slot starts, clean
    r->cur_slot = slot;
    r->next_idx = 0;
    r->have_slot = 1;
    r->cur_dead = 0;
    std::memcpy(r->chain, f + RP_HDR, 32);
  }
  if (r->have_slot && r->cur_dead && (!framed || slot == r->cur_slot)) {
    // of a slot that is dead already: dropped at the door, counted
    r->c_dead_slot_txn_skipped +=
        framed ? rp_claimed_txns(f + body, sz - body) : 0;
    r->c_entry_unpack_ns += fdm_now_ns() - t0;
    return;
  }
  int fail = RP_OK;
  if (!framed || !r->have_slot || slot != r->cur_slot || idx != r->next_idx) {
    // not the frag that follows: the stream does not parse here
    fail = RP_PARSE;
    if (!r->have_slot) {
      r->cur_slot = framed ? slot : 0;
      r->have_slot = 1;
    }
    slot = r->cur_slot;
    idx = (uint32_t)r->next_idx;
    flags = 0;
    r->n_txns = r->n_ents = 0;
  } else {
    fail = rp_unpack(s, f, body, sz);
  }
  uint64_t t1 = fdm_now_ns();
  r->c_entry_unpack_ns += t1 - t0;
  if (!fail) {
    fail = rp_poh(s, f);
    uint64_t t2 = fdm_now_ns();
    r->c_poh_check_ns += t2 - t1;
    t1 = t2;
  }
  r->c_entries_in += r->n_ents;
  r->next_idx = (uint64_t)idx + 1;
  rp_rec* rec = rp_hold(r, f, sz, slot, idx, flags, tsorig, !fail);
  rec->n_txn = fail ? (uint32_t)rp_claimed_txns(f + body, framed ? sz - body : 0)
                    : (uint32_t)r->n_txns;
  rec->last_seq = 0;
  if (fail) {
    // dead here: nothing of the frag goes to the device
    rec->fail = (uint32_t)fail;
    rec->done = 1;
    r->cur_dead = 1;
    return;
  }
  r->pending = 1;
  r->cur_txn = 0;
  rp_ingest(s);
  r->c_entry_unpack_ns += fdm_now_ns() - t1;
}

void rp_pump(fdv_stage* s) {
  fdv_replay* r = s->rp;
  if (r->pending) {
    uint64_t t0 = fdm_now_ns();
    rp_ingest(s);
    r->c_entry_unpack_ns += fdm_now_ns() - t0;
  }
  set_flags(s);
}

}  // namespace

extern "C" {

void* fdv_stage_new(uint64_t shard_idx, uint64_t shard_cnt, uint64_t batch,
                    uint64_t max_msg_len, uint64_t n_slots, void* parse_fn) {
  if (!batch || !n_slots || !max_msg_len || !parse_fn) return nullptr;
  fdv_stage* s = (fdv_stage*)std::calloc(1, sizeof(fdv_stage));
  if (!s) return nullptr;
  s->shard_idx = shard_idx;
  s->shard_cnt = shard_cnt ? shard_cnt : 1;
  s->batch = batch;
  s->mml = max_msg_len;
  s->n_slots = n_slots;
  s->parse = (fdv_parse_fn)parse_fn;
  s->open = -1;
  s->slots = (fdv_slot*)std::calloc(n_slots, sizeof(fdv_slot));
  s->meta = (fdv_slot_meta*)std::calloc(n_slots, sizeof(fdv_slot_meta));
  if (!s->slots || !s->meta) return nullptr;
  for (uint64_t i = 0; i < n_slots; i++) {
    fdv_slot* sl = &s->slots[i];
    sl->rows = (uint8_t*)std::calloc(batch, max_msg_len + ROW_TAIL);
    sl->frames = (uint64_t*)std::calloc(batch, 4 * sizeof(uint64_t));
    sl->ranges = (uint32_t*)std::calloc(batch, 2 * sizeof(uint32_t));
    sl->arena = (uint8_t*)std::malloc(batch * FRAME_CAP);
    if (!sl->rows || !sl->frames || !sl->ranges || !sl->arena)
      return nullptr;
  }
  set_flags(s);  // every slot is free: intake accepts from the start
  return s;
}

void fdv_stage_delete(void* ctx) {
  fdv_stage* s = (fdv_stage*)ctx;
  if (!s) return;
  for (uint64_t i = 0; i < s->n_slots; i++) {
    std::free(s->slots[i].rows);
    std::free(s->slots[i].frames);
    std::free(s->slots[i].ranges);
    std::free(s->slots[i].arena);
  }
  std::free(s->slots);
  std::free(s->meta);
  if (s->rp) {
    for (uint64_t i = 0; i < s->n_slots; i++) std::free(s->rp->rec_of[i]);
    std::free(s->rp->rec_of);
    std::free(s->rp->slot_seq);
    std::free(s->rp->recs);
    std::free(s->rp->arena);
    std::free(s->rp);
  }
  std::free(s);
}

// The fdr_sweep callback: resolved by ADDRESS from Python, called per
// frag inside the sweep crossing.  meta8 = (seq, sig, arena off, sz,
// ctl, tsorig, tspub, in_idx).  Returns -1 (stop the sweep) when the
// frag had to be stashed — the slot ring is full and intake must wait
// for the reap side to free a slot.
int fdv_frag_cb(void* ctx, const uint64_t* meta8, const uint8_t* payload) {
  fdv_stage* s = (fdv_stage*)ctx;
  if (s->shard_cnt > 1 && (meta8[0] % s->shard_cnt) != s->shard_idx) {
    s->c_filtered++;
    return 0;
  }
  return append_one(s, payload, meta8[3], meta8[5]);
}

// Per-frag fallback surface (mixed-lane / lossy-splice topologies): the
// Python after_frag forwards into the SAME state the sweep cb fills.
// The shard filter already ran in before_frag on that path.
int fdv_append(void* ctx, const uint8_t* payload, uint64_t sz,
               uint64_t tsorig) {
  return append_one((fdv_stage*)ctx, payload, sz, tsorig);
}

// The caller's close (deadline passed and the batch can be dispatched,
// or flush): seal the open slot (no-op when nothing accumulated) and
// record why (CLOSE_DEADLINE or CLOSE_WINDOW).
void fdv_seal(void* ctx, uint64_t why) {
  fdv_stage* s = (fdv_stage*)ctx;
  seal_open(s, why);
  set_flags(s);
}

// Retry stashed frags (the reap side calls this after releasing a slot).
void fdv_pump(void* ctx) {
  fdv_stage* s = (fdv_stage*)ctx;
  if (s->rp) rp_pump(s);
  else pump(s);
}

// A dispatched+published slot returns to the ring.
void fdv_slot_release(void* ctx, uint64_t idx) {
  fdv_stage* s = (fdv_stage*)ctx;
  if (idx >= s->n_slots) return;
  s->meta[idx].state = SLOT_FREE;
  fdv_pump(s);
}

// zero-FFI view pointers (called once at construction from Python)
void* fdv_meta_ptr(void* ctx) { return ((fdv_stage*)ctx)->meta; }
void* fdv_counters_ptr(void* ctx) { return &((fdv_stage*)ctx)->flags; }
void* fdv_slot_rows(void* ctx, uint64_t i) {
  return ((fdv_stage*)ctx)->slots[i].rows;
}
void* fdv_slot_frames(void* ctx, uint64_t i) {
  return ((fdv_stage*)ctx)->slots[i].frames;
}
void* fdv_slot_ranges(void* ctx, uint64_t i) {
  return ((fdv_stage*)ctx)->slots[i].ranges;
}
void* fdv_slot_arena(void* ctx, uint64_t i) {
  return ((fdv_stage*)ctx)->slots[i].arena;
}

// -- the replay intake (entry batches in; runtime/replay_verify.py) ---------

// A stage whose frags are entry batches.  `arena_sz` is the ring of
// held frags (>= 4 frags of the largest size), `n_recs` a power of two.
void* fdv_replay_new(uint64_t batch, uint64_t max_msg_len, uint64_t n_slots,
                     void* parse_fn, uint64_t arena_sz, uint64_t n_recs) {
  if (arena_sz < 4 * (RP_FRAG_MAX + RP_VERDICT_SZ) || !n_recs ||
      (n_recs & (n_recs - 1)))
    return nullptr;
  fdv_stage* s =
      (fdv_stage*)fdv_stage_new(0, 1, batch, max_msg_len, n_slots, parse_fn);
  if (!s) return nullptr;
  fdv_replay* r = (fdv_replay*)std::calloc(1, sizeof(fdv_replay));
  if (!r) return nullptr;
  r->cap = arena_sz;
  r->arena = (uint8_t*)std::malloc(arena_sz);
  r->recs = (rp_rec*)std::calloc(n_recs, sizeof(rp_rec));
  r->rec_mask = n_recs - 1;
  r->slot_seq = (uint64_t*)std::calloc(n_slots, sizeof(uint64_t));
  r->rec_of = (uint32_t**)std::calloc(n_slots, sizeof(uint32_t*));
  if (!r->arena || !r->recs || !r->slot_seq || !r->rec_of) return nullptr;
  for (uint64_t i = 0; i < n_slots; i++) {
    r->rec_of[i] = (uint32_t*)std::calloc(batch, sizeof(uint32_t));
    if (!r->rec_of[i]) return nullptr;
  }
  s->rp = r;
  set_flags(s);
  return s;
}

// The fdr_sweep callback of the replay intake: one entry batch a frag.
// -1 (stop the sweep) when the NEXT frag could not be taken: one is
// part-way into the slots, or the ring of held frags is full.
int fdv_replay_cb(void* ctx, const uint64_t* meta8, const uint8_t* payload) {
  fdv_stage* s = (fdv_stage*)ctx;
  rp_frag(s, payload, meta8[3], meta8[5]);
  set_flags(s);
  return (s->flags & 2u) ? 0 : -1;
}

// Per-frag fallback surface: 0 = taken; -1 = no room, dropped and
// counted (only a dead/wedged consumer gets here: the sweep path never
// polls a frag it cannot take).
int fdv_replay_append(void* ctx, const uint8_t* payload, uint64_t sz,
                      uint64_t tsorig) {
  fdv_stage* s = (fdv_stage*)ctx;
  rp_pump(s);
  if (!(s->flags & 2u)) {
    s->c_frags_in++;
    s->c_intake_dropped++;
    return -1;
  }
  rp_frag(s, payload, sz, tsorig);
  set_flags(s);
  return 0;
}

// The reap of device batch slot `idx`: `bad` names the `n_bad`
// transactions (indices into the slot, ascending) a signature of which
// failed.  The first such transaction of a slot not known dead kills
// it at its entry batch; the rest of that slot's are lanes spent.
void fdv_replay_reap(void* ctx, uint64_t idx, const uint32_t* bad,
                     uint64_t n_bad) {
  fdv_stage* s = (fdv_stage*)ctx;
  fdv_replay* r = s->rp;
  if (idx >= s->n_slots) return;
  for (uint64_t k = 0; k < n_bad; k++) {
    uint32_t t = bad[k];
    if (t >= s->meta[idx].n_txn) continue;
    uint64_t ring = r->rec_of[idx][t];
    // the record's ring position, from its ring index
    uint64_t at = r->r_emit + ((ring - r->r_emit) & r->rec_mask);
    if (at >= r->r_head) continue;
    rp_rec* rec = rp_at(r, at);
    if (rec->fail || rp_slot_dead(r, rec->slot, at)) continue;
    rec->fail = RP_SIG;
    r->c_verify_fail++;
    r->c_verify_fail_elems += s->slots[idx].ranges[2 * t + 1] -
                              s->slots[idx].ranges[2 * t];
    if (r->have_slot && rec->slot == r->cur_slot) r->cur_dead = 1;
  }
  r->reaped_seq = r->slot_seq[idx];
  rp_pump(s);
}

// What is due out, in block order, as fdr_publish_burst's frame table
// (rows of arena offset, size, sig, tsorig over fdv_replay_arena): the
// held entry batches whose lanes have all been reaped — left, rejected
// with the slot's verdict frame, or skipped — up to the table's room.
// -> rows written; *n_recs = held records they cover (what
// fdv_replay_out_done frees once the rows are published).
uint64_t fdv_replay_collect(void* ctx, uint64_t* n_recs) {
  fdv_stage* s = (fdv_stage*)ctx;
  fdv_replay* r = s->rp;
  uint64_t rows = 0, recs = 0;
  while (r->r_emit < r->r_head && rows + 2 <= RP_OUT_CAP) {
    rp_rec* rec = rp_at(r, r->r_emit);
    if (!rec->done || rec->last_seq > r->reaped_seq) break;
    uint8_t* v = r->arena + rec->off + rec->sz;  // its verdict's 16 B
    int verdict = -1;
    if (r->emit_dead && rec->slot == r->emit_dead_slot) {
      r->c_dead_slot_txn_skipped += rec->n_txn;
      r->c_dead_slot_lanes_spent += rec->n_lanes;
    } else if (rec->fail) {
      verdict = (int)rec->fail;
      r->emit_dead = 1;
      r->emit_dead_slot = rec->slot;
      r->c_entry_txn_rejected += rec->n_txn;
      if (rec->fail == RP_SIG) r->c_slots_dead_sig++;
      else if (rec->fail == RP_POH) r->c_slots_dead_poh++;
      else r->c_slots_dead_parse++;
    } else {
      uint64_t* row = r->out_tbl + 4 * rows++;
      row[0] = rec->off;
      row[1] = rec->sz;
      row[2] = ((rec->slot & 0x7FFFFFFFull) << 32) | rec->idx;
      row[3] = rec->tsorig;
      r->c_entry_batches_out++;
      r->c_entry_txn_out += rec->n_txn;
      if (rec->flags & RP_F_LAST) {
        verdict = RP_OK;
        r->c_slots_live++;
      }
    }
    if (verdict >= 0) {
      // dead: the first failing entry batch; live: how many it had
      uint32_t vi = verdict ? rec->idx : rec->idx + 1;
      wr64(v, rec->slot);
      wr32(v + 8, vi);
      wr32(v + 12, RP_F_VERDICT | ((uint32_t)verdict << 8));
      uint64_t* row = r->out_tbl + 4 * rows++;
      row[0] = rec->off + rec->sz;
      row[1] = RP_VERDICT_SZ;
      row[2] = RP_SIG_VERDICT | ((rec->slot & 0x7FFFFFFFull) << 32) | vi;
      row[3] = rec->tsorig;
    }
    r->r_emit++;
    recs++;
  }
  *n_recs = recs;
  set_flags(s);
  return rows;
}

// The oldest `n_recs` collected records' rows are out: their bytes and
// records return to the rings.
void fdv_replay_out_done(void* ctx, uint64_t n_recs) {
  fdv_stage* s = (fdv_stage*)ctx;
  fdv_replay* r = s->rp;
  while (n_recs-- && r->r_tail < r->r_emit) {
    r->a_tail += rp_at(r, r->r_tail)->adv;
    r->r_tail++;
  }
  rp_pump(s);
}

// held records not yet collected, and collected not yet freed
uint64_t fdv_replay_held(void* ctx) {
  fdv_replay* r = ((fdv_stage*)ctx)->rp;
  return r->r_head - r->r_tail;
}

void* fdv_replay_arena(void* ctx) { return ((fdv_stage*)ctx)->rp->arena; }
void* fdv_replay_out_tbl(void* ctx) { return ((fdv_stage*)ctx)->rp->out_tbl; }
void* fdv_replay_counters_ptr(void* ctx) {
  return &((fdv_stage*)ctx)->rp->c_entry_batches_in;
}

}  // extern "C"
