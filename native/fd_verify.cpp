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
// Build: g++ -O2 -shared -fPIC -o fd_verify.so fd_verify.cpp

#include <cstdint>
#include <cstdlib>
#include <cstring>

#include "fd_metrics.h"  // fdm_now_ns: the clock of time.monotonic_ns()

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

struct fdv_stage {
  uint64_t shard_idx, shard_cnt, batch, mml, n_slots;
  fdv_parse_fn parse;
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
  s->flags = (s->stash_n ? 1u : 0u) | ((!s->stash_n && room) ? 2u : 0u);
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
void fdv_pump(void* ctx) { pump((fdv_stage*)ctx); }

// A dispatched+published slot returns to the ring.
void fdv_slot_release(void* ctx, uint64_t idx) {
  fdv_stage* s = (fdv_stage*)ctx;
  if (idx >= s->n_slots) return;
  s->meta[idx].state = SLOT_FREE;
  pump(s);
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

}  // extern "C"
