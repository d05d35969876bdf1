// native/frontend.cc — epoll serving front-end for the store server.
//
// Role in the architecture: BucketStoreServer's socket half, in native
// code. The reference's server-side socket machinery is the Redis server
// itself (a C epoll loop parsing RESP and executing Lua scripts); its
// client half is StackExchange.Redis's multiplexed pipelining connection
// (reference TokenBucket/RedisTokenBucketRateLimiter.cs:111-174
// ConnectAsync; SURVEY.md §5.8). This file plays the Redis-process role
// for the TPU store: it owns the listening socket, parses the v4 wire
// protocol (runtime/wire.py is the format authority), hands
// micro-batches of per-request acquires to Python exactly once per
// flush — so the per-REQUEST Python cost of the serving path drops to
// zero and the per-BATCH cost is one store bulk call — and since round
// 8 serves OP_ACQUIRE_MANY natively too: parse, per-row tier-0
// decisions, and the RESP_BULK encode all run here, with only the
// cold-row residue crossing the ABI as one zero-copy batch. The
// per-request asyncio ceiling this replaces was ~13K req/s/core with a
// zero-cost kernel on a builder's CPU host (not measured on the chip's
// host); everything that ceiling charges per request
// (readexactly, task spawn, decode, encode, write lock) runs here in C.
//
// Threading: one IO thread (epoll) owns all sockets. Python's pump
// thread blocks in fe_wait (GIL released — the library loads via
// ctypes.CDLL, unlike the PyDLL directory) and dispatches batches /
// passthrough frames onto the asyncio loop; completions call
// fe_complete / fe_send from the loop thread. One global mutex guards
// shared state — contention is per-flush and per-event-burst, not
// per-request. Byte order: the wire is little-endian and this file
// assumes an LE host (x86-64/aarch64 — everywhere this framework runs).
//
// Batching policy (mirrors runtime/batcher.py MicroBatcher semantics):
//   flush when (a) pending >= max_batch, (b) the oldest pending request
//   has waited deadline_us (timerfd, ns precision — asyncio timers
//   quantize ~1ms), or (c) the pump is idle and nothing is queued
//   (flush-on-idle: batching only pays when a flush is already in
//   flight).

#include <arpa/inet.h>
#include <fcntl.h>
#include <netinet/in.h>
#include <netinet/tcp.h>
#include <pthread.h>
#include <sched.h>
#include <sys/epoll.h>
#include <sys/eventfd.h>
#include <sys/mman.h>
#include <sys/socket.h>
#include <sys/syscall.h>
#include <sys/timerfd.h>
#include <unistd.h>

#if defined(__SANITIZE_THREAD__)
#define DRL_TSAN 1
#elif defined(__has_feature)
#if __has_feature(thread_sanitizer)
#define DRL_TSAN 1
#endif
#endif

#if defined(__SANITIZE_ADDRESS__)
#define DRL_ASAN 1
#elif defined(__has_feature)
#if __has_feature(address_sanitizer)
#define DRL_ASAN 1
#endif
#endif

#include <algorithm>
#include <atomic>
#include <cerrno>
#include <cmath>
#include <condition_variable>
#include <cstdint>
#include <cstdlib>
#include <cstring>
#include <deque>
#include <mutex>
#include <string>
#include <string_view>
#include <chrono>
#include <thread>
#include <unordered_map>
#include <vector>

namespace {

#if defined(DRL_TSAN)
// TSan tracks pthread mutexes by ADDRESS. std::mutex's constexpr
// constructor never calls pthread_mutex_init, so when `new Frontend()`
// lands on a heap block where some earlier allocation (ours or an
// uninstrumented library's) destroyed a mutex, TSan still sees the
// destroyed one: every lock of the new mutex reports "mutex is already
// destroyed" and the lost happens-before cascades into hundreds of
// false races. An explicitly initialized mutex makes the birth visible
// to the pthread interceptors. The paired condition variable wraps a
// pthread_cond_t directly for the same reason (std::condition_variable
// demands std::mutex, and condition_variable_any hides another
// constexpr-constructed internal std::mutex that re-creates the exact
// problem). Production builds keep plain std::mutex/condition_variable.
class TsanVisibleMutex {
 public:
  TsanVisibleMutex() { pthread_mutex_init(&m_, nullptr); }
  ~TsanVisibleMutex() { pthread_mutex_destroy(&m_); }
  TsanVisibleMutex(const TsanVisibleMutex&) = delete;
  TsanVisibleMutex& operator=(const TsanVisibleMutex&) = delete;
  void lock() { pthread_mutex_lock(&m_); }
  void unlock() { pthread_mutex_unlock(&m_); }
  bool try_lock() { return pthread_mutex_trylock(&m_) == 0; }
  pthread_mutex_t* native() { return &m_; }

 private:
  pthread_mutex_t m_;
};

class TsanVisibleCondVar {
 public:
  TsanVisibleCondVar() {
    pthread_condattr_t attr;
    pthread_condattr_init(&attr);
    pthread_condattr_setclock(&attr, CLOCK_MONOTONIC);
    pthread_cond_init(&c_, &attr);
    pthread_condattr_destroy(&attr);
  }
  ~TsanVisibleCondVar() { pthread_cond_destroy(&c_); }
  TsanVisibleCondVar(const TsanVisibleCondVar&) = delete;
  TsanVisibleCondVar& operator=(const TsanVisibleCondVar&) = delete;
  void notify_one() { pthread_cond_signal(&c_); }
  void notify_all() { pthread_cond_broadcast(&c_); }
  template <class Pred>
  bool wait_for(std::unique_lock<TsanVisibleMutex>& lk,
                std::chrono::milliseconds ms, Pred pred) {
    timespec ts;
    clock_gettime(CLOCK_MONOTONIC, &ts);
    ts.tv_sec += time_t(ms.count() / 1000);
    ts.tv_nsec += long((ms.count() % 1000) * 1000000);
    if (ts.tv_nsec >= 1000000000L) {
      ts.tv_sec += 1;
      ts.tv_nsec -= 1000000000L;
    }
    while (!pred()) {
      if (pthread_cond_timedwait(&c_, lk.mutex()->native(), &ts) ==
          ETIMEDOUT) {
        return pred();
      }
    }
    return true;
  }

 private:
  pthread_cond_t c_;
};
using FeMutex = TsanVisibleMutex;
using FeCondVar = TsanVisibleCondVar;
#else
using FeMutex = std::mutex;
using FeCondVar = std::condition_variable;
#endif

constexpr uint8_t kVersion = 4;
constexpr uint32_t kMaxFrame = 1u << 20;
constexpr size_t kBodyOff = 6;  // [u8 ver][u32 seq][u8 op]
constexpr size_t kMaxConnOut = 64u << 20;  // runaway outbox => drop conn

constexpr uint8_t OP_ACQUIRE = 1;
constexpr uint8_t OP_WINDOW = 4;
constexpr uint8_t OP_PING = 5;
constexpr uint8_t OP_SEMA = 8;  // signed count: +acquire / -release / 0 probe
constexpr uint8_t OP_FWINDOW = 9;
constexpr uint8_t OP_HELLO = 10;
// Placement / migration control plane (wire.py, round 6): never hot —
// routed to the Python passthrough lane below. Named (and case-listed)
// so drl-check's wire-conformance diff pins their values against
// wire.py and a future fast-path cannot typo them.
constexpr uint8_t OP_PLACEMENT = 14;
constexpr uint8_t OP_PLACEMENT_ANNOUNCE = 15;
constexpr uint8_t OP_MIGRATE_PULL = 16;
constexpr uint8_t OP_MIGRATE_PUSH = 17;
// Live config mutation (wire.py, round 7): control-plane, never hot —
// passthrough like the placement ops. The CONFIG GATE for the fast
// lanes lives in Python (_serve_batch answers retired-config rows with
// the routable "config moved" error; the tier-0 sync pump re-routes a
// retired config's debits and zeroes its replica headroom).
constexpr uint8_t OP_CONFIG = 18;
// Hierarchical tenant → key acquire (wire.py, the token-denominated
// admission plane): its frame carries a tenant extension this parser
// does not speak, so the op MUST stay on the Python passthrough lane —
// named here (never case-listed in the scalar switch) so drl-check's
// wire-hier rule can pin the fallthrough; a future fast-path for it
// must mirror the full [u16 tlen][tenant][f64 ta][f64 tb][u8 priority]
// tail first.
constexpr uint8_t OP_ACQUIRE_H = 19;
// Estimate-reserve-settle lane (wire.py, runtime/reservations.py):
// JSON control frames (TEXT_OPS) against the server-side reservation
// ledger — control-plane cadence, never hot. Passthrough like the
// placement/config ops: named (and case-listed) so drl-check's
// wire-conformance diff pins their values against wire.py and a
// future fast-path cannot typo them.
constexpr uint8_t OP_RESERVE = 20;
constexpr uint8_t OP_SETTLE = 21;
// Global quota federation lane (wire.py, runtime/federation.py): WAN
// lease control frames (TEXT_OPS JSON) against the home ledger —
// WAN-RTT cadence, never hot. Passthrough like the placement/config/
// reservation ops: named (and case-listed) so drl-check's
// wire-conformance diff pins their values against wire.py and a
// future fast-path cannot typo them.
constexpr uint8_t OP_FED_LEASE = 22;
constexpr uint8_t OP_FED_RENEW = 23;
constexpr uint8_t OP_FED_RECLAIM = 24;
// Conservation audit plane (wire.py, runtime/audit.py): JSON audit
// snapshot / incident-bundle surface (TEXT_OPS) — read-only diagnostic
// cadence, never hot. Passthrough like the other control ops: named
// (and case-listed) so drl-check's wire-conformance diff pins its
// value against wire.py and a future fast-path cannot typo it.
constexpr uint8_t OP_AUDIT = 25;

// Bulk admission lane (round 8): OP_ACQUIRE_MANY parses HERE, tier-0
// decides hot bucket rows per-row, and the RESP_BULK reply encodes in C
// — only the cold/uncertain residue crosses the fe_bulk_* ABI, as one
// zero-copy blob+offsets+counts batch (the wire.KeyBlob lane). wire.py
// stays the layout authority; drl-check diffs every constant below
// against it (kBulkReqHead ↔ _BULK_REQ_HEAD et al).
constexpr uint8_t OP_ACQUIRE_MANY = 11;
constexpr size_t kBulkReqHead = 21;   // [u8 flags][f64 a][f64 b][u32 n]
constexpr size_t kBulkRespHead = 5;   // [u8 flags][u32 n]
constexpr uint8_t kBulkFlagRemaining = 1;  // wire _FLAG_WITH_REMAINING
constexpr uint8_t kBulkFlagChained = 8;    // wire _FLAG_CHAINED
constexpr uint8_t kBulkKindMask = 6;       // wire _KIND_MASK (bits 1-2)
constexpr int kBulkKindShift = 1;          // wire _KIND_SHIFT
constexpr uint8_t BULK_KIND_BUCKET = 0;
constexpr uint8_t BULK_KIND_WINDOW = 1;
constexpr uint8_t BULK_KIND_FWINDOW = 2;
// Hierarchical tenant → key bulk frames (wire.py BULK_KIND_HBUCKET):
// carry a tenant extension after the counts array that this parser
// does not speak — handle_bulk_frame's `kind > BULK_KIND_FWINDOW` gate
// routes them to the Python lane (drl-check wire-hier pins the gate).
constexpr uint8_t BULK_KIND_HBUCKET = 3;
// Flags bit 4: the 25-byte trace tail rides after the counts array
// (old decoders read arrays by explicit counts and never see it).
constexpr uint8_t BULK_FLAG_TRACED = 16;

// Op-byte bit 7 (wire.py TRACE_FLAG): a 25-byte trace tail —
// [u64 trace_hi][u64 trace_lo][u64 parent span][u8 flags] — follows the
// payload. Only sampled requests carry it; parsing it here keeps traced
// hot frames on the batch/tier-0 fast lanes instead of demoting them to
// passthrough.
constexpr uint8_t TRACE_FLAG = 0x80;
constexpr size_t kTraceTail = 25;

constexpr uint8_t RESP_DECISION = 64;
constexpr uint8_t RESP_EMPTY = 67;
constexpr uint8_t RESP_BULK = 69;
constexpr uint8_t RESP_ERROR = 127;

// Serving-latency histogram: identical convention to
// utils/metrics.LatencyHistogram (82 log-1.25 buckets from 1µs; a
// quantile reads its bucket's upper edge) so Python can pour these
// counts straight into that class for p50/p99.
constexpr int kHistBuckets = 82;
const double kInvLogBase = 1.0 / std::log(1.25);

uint64_t now_ns() {
  timespec ts;
  clock_gettime(CLOCK_MONOTONIC, &ts);
  return uint64_t(ts.tv_sec) * 1000000000ull + uint64_t(ts.tv_nsec);
}

inline uint16_t rd_u16(const uint8_t* p) {
  uint16_t v;
  std::memcpy(&v, p, 2);
  return v;
}
inline uint32_t rd_u32(const uint8_t* p) {
  uint32_t v;
  std::memcpy(&v, p, 4);
  return v;
}
inline int32_t rd_i32(const uint8_t* p) {
  int32_t v;
  std::memcpy(&v, p, 4);
  return v;
}
inline double rd_f64(const uint8_t* p) {
  double v;
  std::memcpy(&v, p, 8);
  return v;
}
inline void wr_u32(std::string* s, uint32_t v) {
  s->append(reinterpret_cast<const char*>(&v), 4);
}
inline void wr_f64(std::string* s, double v) {
  s->append(reinterpret_cast<const char*>(&v), 8);
}

std::string encode_decision(uint32_t seq, bool granted, double remaining) {
  std::string s;
  s.reserve(19);
  wr_u32(&s, uint32_t(kBodyOff + 9));
  s.push_back(char(kVersion));
  wr_u32(&s, seq);
  s.push_back(char(RESP_DECISION));
  s.push_back(granted ? 1 : 0);
  wr_f64(&s, remaining);
  return s;
}

std::string encode_empty(uint32_t seq) {
  std::string s;
  s.reserve(10);
  wr_u32(&s, uint32_t(kBodyOff));
  s.push_back(char(kVersion));
  wr_u32(&s, seq);
  s.push_back(char(RESP_EMPTY));
  return s;
}

std::string encode_error(uint32_t seq, const char* msg) {
  uint16_t mlen = uint16_t(std::strlen(msg));
  std::string s;
  wr_u32(&s, uint32_t(kBodyOff + 2 + mlen));
  s.push_back(char(kVersion));
  wr_u32(&s, seq);
  s.push_back(char(RESP_ERROR));
  s.append(reinterpret_cast<const char*>(&mlen), 2);
  s.append(msg, mlen);
  return s;
}

struct Item {
  uint64_t conn_id;
  uint32_t seq;
  uint8_t op;
  int32_t count;
  double a, b;
  std::string key;
  uint64_t t_ns;  // arrival (frame fully parsed) — serving latency start
  // Trace context (all zero when the frame carried no tail). tr_flags
  // bit 0 = traced-present, bit 1 = wire sampled flag — the layout
  // fe_batch_traces hands to Python.
  uint64_t tr_hi = 0, tr_lo = 0, tr_parent = 0;
  uint8_t tr_flags = 0;
};

struct Batch {
  int64_t id;
  std::vector<Item> items;
  uint64_t t_flush_ns = 0;  // batch cut — per-stage decomposition anchor
};

struct Passthrough {
  uint64_t conn_id;
  std::string frame;  // full body: [ver][seq][op][payload]
};

// One OP_ACQUIRE_MANY frame whose residue rows (cold keys, windows,
// probes — everything tier-0 could not decide) are out with Python.
// The reply is one RESP_BULK covering ALL rows: the C-decided verdicts
// wait here until fe_bulk_complete merges the residue verdicts in, so
// nothing is sent early and the frame stays all-or-one-reply. blob/
// offsets/counts are address-stable until the job is erased — the
// zero-copy contract fe_bulk_ptrs hands to Python.
struct BulkJob {
  int64_t id = 0;
  uint64_t conn_id = 0;
  uint32_t seq = 0;
  uint8_t flags = 0;  // the frame's wire flags byte
  uint8_t kind = 0;   // BULK_KIND_*
  bool with_remaining = false;
  double a = 0.0, b = 0.0;
  uint32_t n = 0;
  std::string blob;              // concatenated key bytes
  std::vector<int64_t> offsets;  // n + 1 boundaries into blob
  std::vector<int64_t> counts;   // per-row requested permits
  std::vector<uint8_t> verdict;  // 0 deny, 1 grant, 2 awaiting residue
  std::vector<float> remaining;  // per-row estimate (RESP_BULK is f32)
  std::vector<int32_t> residue;  // row indices Python must decide
  uint64_t t_ns = 0;             // arrival — serving latency start
  uint64_t tr_hi = 0, tr_lo = 0, tr_parent = 0;
  uint8_t tr_flags = 0;
};

// Per-frame hot-key aggregation slot (bulk_hot_feed scratch).
struct HotSlot {
  uint64_t hash = 0;
  uint64_t epoch = 0;
  int64_t row = 0;
  double weight = 0.0;
};

// One traced C-local decision, exported to Python as six u64s:
// hi, lo, parent, start_ns (CLOCK_MONOTONIC — the same epoch Python's
// perf_counter reads), dur_ns, meta (bits 0-7 wire flags, bit 8
// granted, bits 16-23 op).
struct TraceRec {
  uint64_t hi, lo, parent, start_ns, dur_ns, meta;
};

struct Conn {
  int fd = -1;
  uint64_t id = 0;
  bool authed = false;
  bool auth_pending = false;  // HELLO handed to Python, not yet resolved
  bool closing = false;     // close after outbox drains
  std::vector<uint8_t> in;  // accumulated unread bytes
  size_t in_off = 0;        // parse cursor into `in`
  std::vector<std::string> held;  // frames pipelined behind the HELLO
  size_t held_bytes = 0;
  std::string out;          // unwritten reply bytes
  size_t out_off = 0;       // write cursor into `out` (no O(n^2) erase)
  bool want_write = false;  // EPOLLOUT armed
  // Native bulk lane ordering: the last bulk frame's inflight job id
  // (0 once it completed). A chained chunk (wire _FLAG_CHAINED) must
  // decide AFTER its predecessor — the asyncio server's per-connection
  // bulk_tail contract — so chained frames park here until the
  // predecessor's reply is encoded.
  int64_t cur_bulk = 0;
  std::deque<std::string> parked_bulk;  // raw frame bodies, FIFO
  size_t parked_bytes = 0;
  // True when the connection's LAST bulk frame was handed to the
  // Python passthrough lane (malformed shape, or the lane disabled):
  // a chained successor must order behind it THERE (the server's
  // _bulk_tails), not race it natively — the asyncio server answers
  // a malformed chunk's error before its chained successor's reply,
  // and reply-for-reply parity includes that order.
  bool bulk_pt_tail = false;
  // io_uring transport state (round 16). Epoll connections leave these
  // idle. `wbuf` holds the bytes an in-flight SEND sqe points at — the
  // kernel reads them asynchronously, so they must not move while the
  // op is pending (c->out keeps accumulating and swaps in when the
  // current send drains). `uring_ops` counts CQEs still owed to this
  // connection; teardown parks the Conn in Shard::dying until it hits
  // zero — freeing wbuf under an in-flight SEND hands the kernel a
  // dangling iov.
  std::string wbuf;
  size_t wbuf_off = 0;
  uint32_t uring_ops = 0;
  bool recv_armed = false;    // multishot RECV in flight
  bool send_inflight = false;
  bool close_linked = false;  // linked SEND->CLOSE chain in flight
  bool dead = false;          // torn down; parked in Shard::dying
};

// Bound on bytes a connection may pipeline behind an unresolved HELLO.
constexpr size_t kMaxHeld = 256u << 10;

// ---------------------------------------------------------------------
// Tier-0 admission cache: a bounded per-key replica of the store's view,
// serving ACQUIRE permits/denies locally whenever the replica shows
// confident headroom against its last-synced value — the approximate
// local-decision/async-sync split (models/approximate.py) re-hosted
// BELOW the wire, so a hot key's decision never leaves this file. The
// Python sync pump drains each replica's accumulated local grants into
// one bulk saturating-debit launch (store.debit_many — sync_batch's
// decaying-counter semantic mirrored onto the bucket table, where
// score == capacity − tokens), pulls back fresh balances, and acks them
// here; budgets shrink/widen with the observed balance, so
// over-admission is bounded by the documented epsilon
// (2·budget + fill_rate·sync_interval, models/approximate.py
// overadmit_epsilon). Policy formula mirrored from
// models/approximate.py::headroom_budget — keep the two in sync.
// ---------------------------------------------------------------------

struct T0Entry {
  std::string key;
  double cap = 0.0, rate = 0.0;   // config identity ((a, b) of the frames)
  double last_remaining = 0.0;    // last authoritative balance (acked)
  double admitted = 0.0;          // local grants since the last ack
  double pending = 0.0;           // local grants not yet harvested
  double budget = 0.0;            // confident local admission headroom
  uint64_t last_ack_ns = 0;       // staleness anchor
  uint64_t last_touch_ns = 0;     // TTL anchor
  bool live = false;
};

struct T0Config {
  size_t mask = 0;                // per-slice slots - 1 (power of two)
  double split = 1.0;             // shard count: per-shard budget divisor
  double fraction = 0.5;          // budget = floor(balance * fraction)
  double min_budget = 64.0;       // below this, not worth hosting locally
  double max_budget = 1048576.0;
  uint64_t stale_ns = 0;          // max decision age since last ack
  uint64_t ttl_ns = 0;            // idle eviction
};

// Tier-0 partition lock: a TTAS spinlock, not a pthread mutex. The
// partition critical sections are sub-microsecond (a probe plus a few
// arithmetic ops; one aggregate update per key per bulk frame), and
// with N shard threads crossing partitions every frame the futex
// block/wake syscalls of a contended pthread mutex cost more than the
// work they guard (measured ~20% of 4-shard throughput). Spinners
// pause, then yield after a bound — the sync pump can hold a
// partition for tens of microseconds while harvesting, and a
// preempted holder must not burn the shard CPUs. Acquire/release
// atomics keep TSan's happens-before modeling exact.
class T0SpinMutex {
 public:
  void lock() {
    int spins = 0;
    while (flag_.exchange(1, std::memory_order_acquire) != 0) {
      do {
        if (++spins > 2048) {
          sched_yield();
          spins = 0;
        }
#if defined(__x86_64__)
        __builtin_ia32_pause();
#endif
      } while (flag_.load(std::memory_order_relaxed) != 0);
    }
  }
  void unlock() { flag_.store(0, std::memory_order_release); }
  bool try_lock() {
    return flag_.exchange(1, std::memory_order_acquire) == 0;
  }

 private:
  std::atomic<int> flag_{0};
};

// One SHARD's tier-0 replica slice (round 11: the multi-shard
// front-end). Each shard hosts its own replicas of the keys it serves
// and decides them against a budget DIVIDED by the shard count —
// t0_budget_of clamps to max_budget first and divides after, so the
// summed headroom across shards for any key never exceeds the flat
// single-shard budget: Σ_s floor(min(fraction·avail_s, max_budget)/N)
// ≤ min(fraction·avail, max_budget). One envelope, one epsilon — the
// same overadmit_epsilon(budget, fill, sync) bound as single-shard
// (docs/DESIGN.md §16 carries the inequality). The alternative — one
// replica per key in a key-hash-partitioned shared table — was built
// first and REJECTED on measurement: every frame then writes every hot
// key's entry from every shard, and the cross-core line transfers plus
// partition-lock handoffs cost ~25% of 4-shard throughput; per-shard
// slices make the hot path touch exclusively shard-local memory, which
// is where the node-level scaling actually comes from. The slice lock
// is only ever contended by the ONE sync pump's harvest/ack/retire
// (brief, ~100 Hz), never by another shard. Lock order: shard
// connection mutex → slice mutex; the sync pump takes slice mutexes
// only. (drl-verify extracts this order as the c:FeMutex →
// c:T0SpinMutex graph edge — by guard TYPE, so renaming variables
// cannot blind it — and fails on any cycle against it.)
struct T0Part {
  T0SpinMutex mu;
  T0Config cfg;               // per-partition copy, read/written under mu
  std::vector<T0Entry> tab;
  size_t scan = 0;            // harvest resume cursor (fairness)
  int64_t hits = 0;           // local grants
  int64_t local_denies = 0;   // confident local denies
  int64_t misses = 0;         // eligible requests that fell through
  int64_t installs = 0;
  int64_t evictions = 0;
  // Round 18 (conservation audit plane): cumulative TOKENS granted
  // locally by this slice — the ε-consumption the sync pump will later
  // reconcile, witnessed at the grant site itself so the Python-side
  // conservation ledger can hold local admissions to the documented
  // epsilon budget without trusting any Python counter. Monotonic;
  // read via fe_t0_eps.
  double grant_tokens = 0.0;
};

// Linear-probe window and the key-size cap that bounds table memory
// (slots × (entry + key) — ~1.5 MB at the 4096-slot default).
constexpr size_t kT0Probe = 8;
constexpr size_t kT0MaxKey = 256;

// Shard-count ceiling (fe_start_sharded clamps to it): bounds the
// per-frame touched[] scratch below, and a node with more epoll
// shards than this has no cores to feed them anyway.
constexpr int kMaxShards = 128;

uint64_t t0_hash(std::string_view k) {
  uint64_t h = 1469598103934665603ull;  // FNV-1a 64
  for (unsigned char ch : k) {
    h ^= ch;
    h *= 1099511628211ull;
  }
  return h;
}

double t0_budget_of(const T0Config& cfg, double avail) {
  double b = avail * cfg.fraction;
  if (b > cfg.max_budget) b = cfg.max_budget;
  // Multi-shard split AFTER the max_budget clamp: the per-shard shares
  // then sum to ≤ the flat single-shard budget whatever the balance,
  // which is the whole single-envelope invariant (see T0Part). The
  // min_budget gate applies to the POST-split share — a bucket whose
  // per-shard share is not worth hosting stays exact, so tier-0's
  // semantic invisibility to small buckets only widens with shards.
  b /= cfg.split;
  if (b < cfg.min_budget) return 0.0;
  return std::floor(b);
}

struct Frontend;

// Handle tags: every ABI entry takes a void* that is either the whole
// Frontend (aggregate view / shard 0 for per-shard calls — the
// single-shard compatibility posture a stale Python half relies on) or
// one Shard (returned by fe_shard). Both structs lead with a magic so
// the entry points can tell which they were handed.
// ---------------------------------------------------------------------
// io_uring data plane (round 16): the shard IO loop rebuilt on a raw-
// syscall, liburing-free ring — multishot accept, multishot recv over a
// provided-buffer ring, submit-on-reply SEND batching, linked
// SEND->CLOSE teardown, and an optional SQPOLL mode where a hot shard
// submits without any syscall at all. The reply bytes are the spec:
// everything from parse_frames down is shared with the epoll loop, only
// the transport differs, and every shard falls back to io_loop (with a
// recorded reason) when the kernel, seccomp, or an env override refuses.
//
// The UAPI structs and constants are defined here rather than pulled
// from <linux/io_uring.h>: the build host's header may predate the
// 5.19 features this transport needs (multishot recv, PBUF_RING) even
// when the running kernel has them, and the ABI below is frozen by the
// kernel's compatibility contract.
// ---------------------------------------------------------------------

constexpr long kSysUringSetup = 425;
constexpr long kSysUringEnter = 426;
constexpr long kSysUringRegister = 427;

struct DrlSqe {  // struct io_uring_sqe (64 bytes, ABI-frozen)
  uint8_t opcode;
  uint8_t flags;    // IOSQE_* bits
  uint16_t ioprio;  // multishot flags live here for accept/recv
  int32_t fd;
  uint64_t off;
  uint64_t addr;
  uint32_t len;
  uint32_t op_flags;  // msg_flags / accept_flags / cancel_flags union
  uint64_t user_data;
  uint16_t buf_group;  // buf_index/buf_group union
  uint16_t personality;
  int32_t splice_fd_in;
  uint64_t pad2[2];
};
static_assert(sizeof(DrlSqe) == 64, "io_uring_sqe ABI");

struct DrlCqe {  // struct io_uring_cqe (16 bytes)
  uint64_t user_data;
  int32_t res;
  uint32_t flags;
};
static_assert(sizeof(DrlCqe) == 16, "io_uring_cqe ABI");

struct DrlSqOffsets {
  uint32_t head, tail, ring_mask, ring_entries, flags, dropped, array,
      resv1;
  uint64_t resv2;
};
struct DrlCqOffsets {
  uint32_t head, tail, ring_mask, ring_entries, overflow, cqes, flags,
      resv1;
  uint64_t resv2;
};
struct DrlUringParams {  // struct io_uring_params (120 bytes)
  uint32_t sq_entries, cq_entries, flags, sq_thread_cpu, sq_thread_idle,
      features, wq_fd, resv[3];
  DrlSqOffsets sq_off;
  DrlCqOffsets cq_off;
};
static_assert(sizeof(DrlUringParams) == 120, "io_uring_params ABI");

constexpr uint64_t kUringOffSqRing = 0;
constexpr uint64_t kUringOffCqRing = 0x8000000ull;
constexpr uint64_t kUringOffSqes = 0x10000000ull;

constexpr uint32_t kUringFeatSingleMmap = 1u << 0;
constexpr uint32_t kUringSetupSqpoll = 1u << 1;
constexpr uint32_t kUringSetupCqsize = 1u << 3;
constexpr uint32_t kUringSetupClamp = 1u << 4;

constexpr uint32_t kUringSqNeedWakeup = 1u << 0;  // sq ring flags word
constexpr uint32_t kUringEnterGetevents = 1u << 0;
constexpr uint32_t kUringEnterSqWakeup = 1u << 1;

constexpr uint8_t kOpTimeout = 11;      // IORING_OP_TIMEOUT
constexpr uint8_t kOpAccept = 13;       // IORING_OP_ACCEPT
constexpr uint8_t kOpAsyncCancel = 14;  // IORING_OP_ASYNC_CANCEL
constexpr uint8_t kOpClose = 19;        // IORING_OP_CLOSE
constexpr uint8_t kOpRead = 22;         // IORING_OP_READ
constexpr uint8_t kOpSend = 26;         // IORING_OP_SEND
constexpr uint8_t kOpRecv = 27;         // IORING_OP_RECV
// IORING_OP_SOCKET landed in 5.19 alongside multishot recv and
// PBUF_RING, which have no probe bit of their own — its presence in the
// opcode probe is the documented feature-level proxy.
constexpr uint8_t kOpSocketProxy = 45;

constexpr uint16_t kAcceptMultishot = 1u << 0;  // sqe->ioprio, accept
constexpr uint16_t kRecvMultishot = 1u << 1;    // sqe->ioprio, recv
constexpr uint8_t kSqeFixedFile = 1u << 0;
constexpr uint8_t kSqeIoLink = 1u << 2;
constexpr uint8_t kSqeBufferSelect = 1u << 5;

constexpr uint32_t kCqeFBuffer = 1u << 0;  // upper 16 bits carry the bid
constexpr uint32_t kCqeFMore = 1u << 1;    // multishot op still armed
constexpr uint32_t kCqeBufferShift = 16;

constexpr unsigned kRegRegisterFiles = 2;
constexpr unsigned kRegRegisterProbe = 8;
constexpr unsigned kRegRegisterPbufRing = 22;

struct DrlProbeOp {
  uint8_t op;
  uint8_t resv;
  uint16_t flags;  // bit 0 = IO_URING_OP_SUPPORTED
  uint32_t resv2;
};
struct DrlProbe {
  uint8_t last_op;
  uint8_t ops_len;
  uint16_t resv;
  uint32_t resv2[3];
  DrlProbeOp ops[48];
};

struct DrlKTimespec {  // struct __kernel_timespec (TIMEOUT ops)
  int64_t tv_sec;
  long long tv_nsec;
};

struct DrlBufReg {  // struct io_uring_buf_reg
  uint64_t ring_addr;
  uint32_t ring_entries;
  uint16_t bgid;
  uint16_t flags;
  uint64_t resv[3];
};
struct DrlBuf {  // struct io_uring_buf; entry 0's resv overlays the
  uint64_t addr;  // ring tail the producer publishes
  uint32_t len;
  uint16_t bid;
  uint16_t resv;
};

inline int sys_uring_setup(unsigned entries, DrlUringParams* p) {
  return int(syscall(kSysUringSetup, entries, p));
}
inline int sys_uring_enter(int fd, unsigned to_submit, unsigned min_c,
                           unsigned flags) {
  return int(syscall(kSysUringEnter, fd, to_submit, min_c, flags,
                     nullptr, size_t(0)));
}
inline int sys_uring_register(int fd, unsigned opcode, void* arg,
                              unsigned nr) {
  return int(syscall(kSysUringRegister, fd, opcode, arg, nr));
}

// Transport mode knob (fe_start_sharded2's uring_mode; mirrored as
// URING_OFF/URING_ON/URING_SQPOLL in utils/native.py — drl-check's
// transport-flag rule pins the pair, so a drift here is a build break,
// not a silent mode swap).
constexpr int kUringOff = 0;
constexpr int kUringOn = 1;
constexpr int kUringSqpoll = 2;

constexpr unsigned kUringSqEntries = 256;
constexpr unsigned kUringCqEntries = 4096;
constexpr unsigned kUringBufCount = 64;      // provided-buffer slots
constexpr size_t kUringBufSize = 32u << 10;  // 32 KiB per slot
constexpr uint16_t kUringBgid = 7;           // buffer-group id

// user_data = (kind << 56) | conn_id. Tags 0-2 stay reserved for the
// listen/eventfd/timerfd fixed-file slots like the epoll loop's epoll
// tags, so conn ids never collide with control ops.
constexpr uint64_t kUdAccept = 1;
constexpr uint64_t kUdEvRead = 2;
constexpr uint64_t kUdTfRead = 3;
constexpr uint64_t kUdRecv = 4;
constexpr uint64_t kUdSend = 5;
constexpr uint64_t kUdClose = 6;
constexpr uint64_t kUdCancel = 7;

inline uint64_t uring_ud(uint64_t kind, uint64_t id) {
  return (kind << 56) | id;
}

// Per-shard ring state. Conn sockets are deliberately NOT in the
// registered-file table: fixed slots are reused the moment a table
// entry is overwritten, and a slot recycled while a canceled op is
// still in flight attributes the completion to the WRONG connection —
// the registered table holds only the three immortal control fds
// (listen=0, eventfd=1, timerfd=2). docs/DESIGN.md §21.
struct UringRing {
  int fd = -1;
  bool sqpoll = false;
  void* sq_map = nullptr;
  size_t sq_map_len = 0;
  void* cq_map = nullptr;  // == sq_map under FEAT_SINGLE_MMAP
  size_t cq_map_len = 0;
  DrlSqe* sqes = nullptr;
  size_t sqes_len = 0;
  std::atomic<uint32_t>* sq_head = nullptr;  // kernel-consumed cursor
  std::atomic<uint32_t>* sq_tail = nullptr;
  uint32_t sq_mask = 0;
  uint32_t* sq_array = nullptr;
  std::atomic<uint32_t>* sq_flags = nullptr;  // NEED_WAKEUP under SQPOLL
  std::atomic<uint32_t>* cq_head = nullptr;
  std::atomic<uint32_t>* cq_tail = nullptr;
  uint32_t cq_mask = 0;
  DrlCqe* cqes = nullptr;
  // Provided-buffer ring (bgid kUringBgid) feeding multishot recv.
  DrlBuf* buf_ring = nullptr;
  size_t buf_ring_len = 0;
  uint8_t* buf_pool = nullptr;
  size_t buf_pool_len = 0;
  uint16_t buf_tail = 0;
  uint32_t sq_pending = 0;  // SQEs staged since the last submit
  uint64_t ev_buf = 0;      // READ landing pad, eventfd slot
  uint64_t tf_buf = 0;      // READ landing pad, timerfd slot
  // Telemetry (fe_uring_counts): enter calls are made both under the
  // shard mutex (submits) and outside it (the wait leg), so atomics.
  std::atomic<long long> enters{0};
  std::atomic<long long> sqes_submitted{0};
  std::atomic<long long> cqes_seen{0};
};

constexpr uint32_t kFeMagic = 0xFE11D311u;
constexpr uint32_t kShardMagic = 0x5AAD0011u;

// One epoll serving shard (round 11): its own SO_REUSEPORT listener on
// the shared port (kernel-level accept balancing — no dispatch thread),
// its own IO thread, connection table, micro-batch queues, bulk lane,
// stats, and rings, all under its own mutex. A connection lives its
// whole life on one shard, so the per-connection order contract and the
// chained-chunk parking (round 8) carry over shard-locally, unchanged.
// The hot path touches NO cross-shard state: tier-0 decisions draw
// from the shard's own replica slice (see T0Part above), and only the
// sync pump's harvest/ack/retire ever crosses shards.
struct Shard {
  uint32_t magic = kShardMagic;
  Frontend* owner = nullptr;
  int index = 0;
  int listen_fd = -1, epfd = -1, evfd = -1, tfd = -1;
  // Read-only copies of the Frontend-level serving knobs (stamped
  // before the IO thread starts) so the hot path never reaches across.
  size_t max_batch = 4096;
  uint64_t deadline_ns = 300000;
  bool require_auth = false;
  std::thread io;

  // io_uring transport (round 16): non-null ring means this shard's IO
  // thread runs uring_loop; the eventfd/timerfd above double as
  // registered-file slots so arm_deadline/wake_io stay transport-
  // neutral. uring_reason records why a shard that was ASKED for uring
  // fell back to epoll (fe_uring_reason / OPERATIONS.md §17).
  UringRing* ring = nullptr;
  bool uring = false;
  bool uring_sqpoll = false;
  bool uring_sweep = false;  // a conn needs re-arm/reap at burst end
  bool tfd_armed = false;    // skip redundant timerfd disarm syscalls
  std::string uring_reason;
  // Connections torn down but still owed CQEs (in-flight SEND/RECV/
  // CANCEL): reaped when their uring_ops drain to zero.
  std::unordered_map<uint64_t, Conn*> dying;
  // Data-plane syscalls this shard has issued (both transports count
  // every epoll_wait/accept/recv/send/epoll_ctl/timerfd/eventfd/enter
  // call) — the syscalls/frame evidence column is this over
  // requests_served, measured, not modeled.
  std::atomic<long long> io_syscalls{0};

  FeMutex mu;
  FeCondVar cv;
  std::unordered_map<uint64_t, Conn*> conns;
  uint64_t next_conn_id = 16;  // tags 0-2 are listen/eventfd/timerfd
  std::vector<Item> pending;
  uint64_t pending_oldest_ns = 0;
  std::deque<Batch> ready;
  std::deque<Passthrough> pt;
  std::unordered_map<int64_t, Batch> inflight;  // handed to Python
  int64_t next_batch_id = 1;
  bool pump_waiting = false;
  int64_t cur_batch_id = 0;  // last batch returned by fe_wait
  Passthrough cur_pt;

  int64_t requests_served = 0;
  int64_t connections_served = 0;
  int64_t batches_flushed = 0;
  uint64_t hist[kHistBuckets] = {0};
  int64_t hist_total = 0;
  double hist_sum = 0.0;
  // Per-stage decomposition of the serving span (same bucket convention):
  // stage 0 = queue (frame parsed -> batch cut), stage 1 = exec (batch
  // cut -> fe_complete/fe_fail, i.e. Python dispatch + store + kernel).
  // serving ~= queue + exec + reply-write; exported via fe_stage_hist.
  static constexpr int kStages = 2;
  uint64_t stage_hist[kStages][kHistBuckets] = {{0}};
  int64_t stage_total[kStages] = {0};
  double stage_sum[kStages] = {0.0};

  // Completed-span records for traced requests decided entirely in C
  // (tier-0 local grant/deny): Python's sync pump harvests these via
  // fe_trace_harvest and emits them as spans, so locally-granted
  // requests still leave a trace. Bounded; overflow drops oldest.
  std::deque<TraceRec> trace_ring;
  int64_t trace_dropped = 0;

  // Native bulk lane (fe_bulk_configure; round 8). Off by default so a
  // freshly-built .so under an older Python half keeps the round-7
  // passthrough behavior — the pump arms it only when it binds the
  // fe_bulk_* ABI.
  bool bulk_native = false;  // parse + decide OP_ACQUIRE_MANY here
  bool bulk_t0 = true;       // per-row tier-0 decisions on bulk rows
  bool bulk_hot = false;     // per-frame top-K feed for the sketch
  std::deque<int64_t> bulk_ready;
  std::unordered_map<int64_t, BulkJob> bulk_inflight;
  int64_t next_bulk_id = 1;
  int64_t cur_bulk_id = 0;  // last job returned by fe_wait
  int64_t bulk_frames = 0;
  int64_t bulk_frames_local = 0;  // answered without leaving C
  int64_t bulk_rows = 0;
  int64_t bulk_rows_local = 0;    // tier-0 grant/deny rows
  int64_t bulk_rows_residue = 0;  // rows that crossed into Python
  double bulk_permits_local = 0.0;  // locally granted permits — the
                                    // amount the sync pump will debit
  // Bulk parse scratch, reused per frame under mu (no per-frame allocs
  // in the steady state; a residue job copies them out).
  std::vector<int64_t> bulk_offsets_scratch;
  std::vector<int64_t> bulk_counts_scratch;
  std::vector<uint8_t> bulk_verdict_scratch;
  std::vector<float> bulk_rem_scratch;
  std::vector<int32_t> bulk_residue_scratch;
  // Round 11: per-frame key aggregation for the tier-0 decide pass. A
  // hot frame carries thousands of rows over a few dozen keys; the
  // parse pass groups them (open-addressed, epoch-stamped table) so
  // the decide pass takes each touched partition's lock ONCE per frame
  // and makes ONE envelope decision per (key, summed count) — per-row
  // locking across N shard threads cache-bounces the partition mutexes
  // (measured SLOWER at 4 shards than one), and even batched per-row
  // decides keep the lock held for the whole row scan. Keys whose
  // aggregate does not cleanly fit the budget fall back to the exact
  // per-row legacy walk under the same lock (the boundary minority),
  // so observable semantics are unchanged.
  std::vector<int32_t> bulk_aggof_scratch;    // row -> agg index | -1
  std::vector<uint64_t> bulk_aggtab_epoch;    // open table stamp
  std::vector<int32_t> bulk_aggtab_idx;       // open table payload
  uint64_t bulk_agg_epoch = 0;
  std::vector<uint64_t> agg_hash;
  std::vector<int32_t> agg_first;   // first row (key-byte authority)
  std::vector<int32_t> agg_nrows;
  std::vector<int64_t> agg_total;   // summed requested permits
  std::vector<uint8_t> agg_mode;    // see kAgg* in handle_bulk_frame
  std::vector<double> agg_before;   // admitted before a grant-all
  std::vector<double> agg_lastrem;  // last acked balance snapshot
  std::vector<double> agg_run;      // per-row remaining fill cursor
  // Hot-key feed for the heavy-hitter sketch: per-frame open-addressed
  // aggregation scratch + the bounded harvest ring fe_hot_harvest
  // drains (overflow drops oldest — telemetry, not accounting).
  std::vector<HotSlot> hot_scratch;
  uint64_t hot_epoch = 0;
  std::deque<std::pair<std::string, double>> hot_ring;
  int64_t hot_dropped = 0;
};

// The whole front-end: N shards accepting on SO_REUSEPORT listeners
// bound to ONE port, plus the key-hash-partitioned tier-0 replica
// table they all decide against. The Python half runs one pump thread
// per shard (fe_shard hands out the per-shard handles) and ONE sync
// pump that drains every partition's grant ledger through the
// Frontend-level harvest/ack/retire calls — a single reconciliation
// stream into the store, a single epsilon envelope across shards.
struct Frontend {
  uint32_t magic = kFeMagic;
  int port = 0;
  int nshards = 1;
  int uring_mode = kUringOff;  // requested transport (kUring*)
  size_t max_batch = 4096;
  uint64_t deadline_ns = 300000;
  bool require_auth = false;
  std::atomic<bool> stopping{false};
  std::vector<Shard*> shards;
  // Tier-0 partitions, one per shard by key-hash affinity (see T0Part).
  // Empty tables until fe_t0_configure; t0_enabled is the lock-free
  // fast gate the parse loops read before paying a partition lock.
  std::vector<T0Part*> t0parts;
  std::atomic<bool> t0_enabled{false};
  // Harvest fan-out cursor: which partition the Frontend-level harvest
  // resumes from (single sync-pump caller; rotates so an overflowing
  // round cannot starve the high-numbered partitions).
  size_t harvest_part = 0;
  // Same rotation for the shard-level trace/hot harvests.
  size_t trace_shard = 0;
  size_t hot_shard = 0;
};

inline Frontend* as_frontend(void* h) {
  return *static_cast<uint32_t*>(h) == kFeMagic
             ? static_cast<Frontend*>(h)
             : nullptr;
}

// Per-shard entry points accept either handle kind; a Frontend handle
// means shard 0 — exactly the single-shard ABI a stale Python half
// (which never calls fe_shard) keeps using.
inline Shard* shard_of(void* h) {
  Frontend* fe = as_frontend(h);
  return fe != nullptr ? fe->shards[0] : static_cast<Shard*>(h);
}

inline Frontend* owner_of(void* h) {
  Frontend* fe = as_frontend(h);
  return fe != nullptr ? fe : static_cast<Shard*>(h)->owner;
}

// Aggregating entry points: every shard for a Frontend handle, just the
// one for a Shard handle (the per-shard breakdown OP_STATS exposes).
inline std::vector<Shard*> shards_of(void* h) {
  Frontend* fe = as_frontend(h);
  if (fe != nullptr) return fe->shards;
  return {static_cast<Shard*>(h)};
}

// The shard's own tier-0 slice (nullptr before fe_t0_configure).
inline T0Part* t0_slice(Shard* sh);

// Slices a tier-0 call touches: the shard's own for a Shard handle
// (per-shard breakdown / the hot path), all of them for a Frontend
// handle (the sync pump's merge view).
inline std::vector<T0Part*> t0parts_of(void* h) {
  Frontend* fe = as_frontend(h);
  if (fe != nullptr) return fe->t0parts;
  Shard* sh = static_cast<Shard*>(h);
  if (sh->owner->t0parts.empty()) return {};
  return {sh->owner->t0parts[size_t(sh->index)]};
}

inline T0Part* t0_slice(Shard* sh) {
  Frontend* fe = sh->owner;
  return fe->t0parts.empty() ? nullptr
                             : fe->t0parts[size_t(sh->index)];
}

constexpr size_t kTraceRing = 1024;

void trace_ring_push_raw(Shard* sh, uint64_t hi, uint64_t lo,
                         uint64_t parent, uint8_t tr_flags, uint8_t op,
                         bool granted, uint64_t start_ns,
                         uint64_t end_ns) {
  // mu held.
  if (sh->trace_ring.size() >= kTraceRing) {
    sh->trace_ring.pop_front();
    sh->trace_dropped++;
  }
  TraceRec r;
  r.hi = hi;
  r.lo = lo;
  r.parent = parent;
  r.start_ns = start_ns;
  r.dur_ns = end_ns - start_ns;
  r.meta = uint64_t(tr_flags) | (granted ? 0x100u : 0u) |
           (uint64_t(op) << 16);
  sh->trace_ring.push_back(r);
}

void trace_ring_push(Shard* sh, const Item& it, bool granted,
                     uint64_t end_ns) {
  trace_ring_push_raw(sh, it.tr_hi, it.tr_lo, it.tr_parent, it.tr_flags,
                      it.op, granted, it.t_ns, end_ns);
}

T0Entry* t0_find(T0Part* part, std::string_view key, uint64_t h,
                 double cap, double rate) {
  // part->mu held.
  if (part->tab.empty()) return nullptr;
  size_t idx = size_t(h) & part->cfg.mask;
  for (size_t p = 0; p < kT0Probe; p++) {
    T0Entry& e = part->tab[(idx + p) & part->cfg.mask];
    if (e.live && e.cap == cap && e.rate == rate &&
        std::string_view(e.key) == key) {
      return &e;
    }
  }
  return nullptr;
}

void t0_install(T0Part* part, const std::string& key, double cap,
                double rate, double remaining, uint64_t now,
                double cost) {
  // Called with the deciding shard's connection mutex held; takes the
  // shard's OWN slice mutex (lock order: shard mu → slice mu — the one
  // nesting this file allows). Seed/refresh a replica from an
  // authoritative device decision (fe_complete). A refresh keeps
  // `admitted`: the device balance predates our un-drained local
  // grants, so the envelope stays conservative until the next sync
  // acks them away.
  //
  // `cost` is the granting request's token count: a fresh install must
  // have the headroom to decide at least ONE request of the cost that
  // seeded it — min_budget alone is denominated for unit permits, and
  // a replica whose budget cannot cover the workload's typical cost
  // can never grant locally (every request would miss), so installing
  // it only burns probe-window slots the genuinely decidable keys
  // need. Token-denominated install terms, not request-denominated
  // (the count>1 audit, ISSUE 10 satellite).
  if (key.size() > kT0MaxKey || part == nullptr) return;
  if (cost < 1.0) cost = 1.0;  // probe-seeded installs size for 1 token
  uint64_t h = t0_hash(key);
  std::lock_guard<T0SpinMutex> lk(part->mu);
  if (part->tab.empty()) return;
  T0Entry* e = t0_find(part, key, h, cap, rate);
  if (e == nullptr) {
    double budget = t0_budget_of(part->cfg, remaining);
    if (budget <= 0.0 || budget < cost) {
      return;  // headroom too small to host locally
    }
    size_t idx = size_t(h) & part->cfg.mask;
    for (size_t p = 0; p < kT0Probe && e == nullptr; p++) {
      T0Entry& cand = part->tab[(idx + p) & part->cfg.mask];
      if (!cand.live) {
        e = &cand;
      } else if (cand.pending == 0.0 &&
                 now - cand.last_touch_ns > part->cfg.ttl_ns) {
        part->evictions++;  // reuse an idle slot (un-drained grants pin)
        e = &cand;
      }
    }
    if (e == nullptr) return;  // probe window live: bounded table, skip
    e->key = key;
    e->cap = cap;
    e->rate = rate;
    e->admitted = 0.0;
    e->pending = 0.0;
    e->live = true;
    e->last_remaining = remaining;
    e->budget = budget;
    e->last_ack_ns = now;
    e->last_touch_ns = now;
    part->installs++;
    return;
  }
  e->last_remaining = remaining;
  e->budget = t0_budget_of(part->cfg,
                           std::max(remaining - e->admitted, 0.0));
  e->last_ack_ns = now;
  e->last_touch_ns = now;
}

int t0_decide_locked(T0Part* part, std::string_view key, uint64_t h,
                     int64_t count, double cap, double rate,
                     double* rem_out, uint64_t now) {
  // part->mu held. 1 = grant locally, 0 = deny locally, -1 = fall
  // through to the device path. The estimate reported with local
  // replies is the envelope's own conservative view (last acked
  // balance minus local grants — refill since the ack is credit the
  // next sync will restore). `now` comes from the caller: the bulk
  // lane decides up to ~100K rows per frame and must not pay one
  // clock read per row.
  T0Entry* e = t0_find(part, key, h, cap, rate);
  if (e == nullptr) {
    part->misses++;
    return -1;
  }
  if (now - e->last_ack_ns > part->cfg.stale_ns) {
    part->misses++;  // envelope too old: device decides (and re-seeds)
    return -1;
  }
  e->last_touch_ns = now;
  double cnt = double(count);
  if (e->admitted + cnt <= e->budget) {
    e->admitted += cnt;
    e->pending += cnt;
    part->hits++;
    part->grant_tokens += cnt;
    *rem_out = std::max(e->last_remaining - e->admitted, 0.0);
    return 1;
  }
  // Confident deny: even crediting FULL refill since the last ack, the
  // last-synced balance cannot cover this request — uncertainty falls
  // through instead of guessing.
  double elapsed_s = double(now - e->last_ack_ns) * 1e-9;
  double optimistic = e->last_remaining - e->admitted + rate * elapsed_s;
  if (optimistic < cnt) {
    part->local_denies++;
    *rem_out = std::max(e->last_remaining - e->admitted, 0.0);
    return 0;
  }
  part->misses++;
  return -1;
}

int t0_decide(T0Part* part, std::string_view key, int64_t count,
              double cap, double rate, double* rem_out, uint64_t now) {
  // Scalar-lane entry: called with the deciding shard's connection
  // mutex held; takes the shard's OWN slice mutex (the shard's budget
  // share is its to draw down — the split in t0_budget_of keeps the
  // cross-shard sum inside the flat envelope). The bulk lane does NOT
  // come through here: it aggregates a frame by key and locks the
  // slice once (handle_bulk_frame).
  if (part == nullptr) return -1;
  uint64_t h = t0_hash(key);
  std::lock_guard<T0SpinMutex> lk(part->mu);
  return t0_decide_locked(part, key, h, count, cap, rate, rem_out, now);
}

int hist_bucket(double seconds) {
  int idx = 0;
  if (seconds > 1e-6) {
    idx = int(std::log(seconds / 1e-6) * kInvLogBase) + 1;
    if (idx > kHistBuckets - 1) idx = kHistBuckets - 1;
    if (idx < 0) idx = 0;
  }
  return idx;
}

void hist_record(Shard* sh, double seconds) {
  sh->hist[hist_bucket(seconds)]++;
  sh->hist_total++;
  sh->hist_sum += seconds;
}

void stage_record(Shard* sh, int stage, double seconds) {
  sh->stage_hist[stage][hist_bucket(seconds)]++;
  sh->stage_total[stage]++;
  sh->stage_sum[stage] += seconds;
}

void set_nonblock(int fd) {
  int fl = fcntl(fd, F_GETFL, 0);
  fcntl(fd, F_SETFL, fl | O_NONBLOCK);
}

// Flush as much of conn->out as the socket accepts. mu held.
void flush_out(Shard* sh, Conn* c);

// io_uring transport entry points (defined after the epoll loop; the
// shared helpers below branch to them when the shard runs on the ring).
void uring_close_conn(Shard* sh, Conn* c);
void uring_arm_send(Shard* sh, Conn* c);
void uring_submit(Shard* sh);

// Data-plane syscall accounting (see Shard::io_syscalls).
inline void count_sys(Shard* sh, int n = 1) {
  sh->io_syscalls.fetch_add(n, std::memory_order_relaxed);
}

void close_conn(Shard* sh, Conn* c) {
  // mu held. Removes from epoll + conn map and frees.
  if (sh->uring) {
    uring_close_conn(sh, c);
    return;
  }
  count_sys(sh, 2);
  epoll_ctl(sh->epfd, EPOLL_CTL_DEL, c->fd, nullptr);
  ::close(c->fd);
  sh->conns.erase(c->id);
  delete c;
}

void send_to_conn(Shard* sh, Conn* c, const char* data, size_t len) {
  // mu held. Append-or-write: when nothing is queued, try the socket
  // immediately (saves an epoll round trip — the common case); queue
  // the remainder and arm EPOLLOUT on partial writes.
  if (c->closing || c->dead) return;
  if (sh->uring) {
    // uring lane: stage and arm a SEND op; the caller's burst-end
    // submit batches every staged reply into one (or zero, under
    // SQPOLL) enter call — the submit-on-reply contract.
    if (c->out.size() - c->out_off + len > kMaxConnOut) {
      c->closing = true;
      c->out.clear();
      c->out_off = 0;
      uring_arm_send(sh, c);
      return;
    }
    c->out.append(data, len);
    uring_arm_send(sh, c);
    return;
  }
  if (c->out.size() == c->out_off) {
    c->out.clear();
    c->out_off = 0;
    count_sys(sh);
    ssize_t n = ::send(c->fd, data, len, MSG_NOSIGNAL);
    if (n == ssize_t(len)) return;
    if (n < 0) {
      if (errno != EAGAIN && errno != EWOULDBLOCK) {
        c->closing = true;  // broken pipe: IO thread reaps on next event
        return;
      }
      n = 0;
    }
    data += n;
    len -= size_t(n);
  }
  if (c->out.size() - c->out_off + len > kMaxConnOut) {
    c->closing = true;  // unbounded outbox = dead/hostile reader
    c->out.clear();
    c->out_off = 0;
    return;
  }
  c->out.append(data, len);
  if (!c->want_write) {
    c->want_write = true;
    epoll_event ev{};
    ev.events = EPOLLIN | EPOLLOUT;
    ev.data.u64 = c->id;
    count_sys(sh);
    epoll_ctl(sh->epfd, EPOLL_CTL_MOD, c->fd, &ev);
  }
}

void queue_to_conn(Conn* c, const char* data, size_t len) {
  // mu held. Append-only variant of send_to_conn for replies generated
  // inside a parse burst (tier-0 local decisions, PING): the caller
  // flushes ONCE per burst via flush_queued, collapsing per-reply
  // send() syscalls — at tier-0 rates the syscall per reply, not the
  // decision, is the serving ceiling.
  if (c->closing) return;
  if (c->out.size() - c->out_off + len > kMaxConnOut) {
    c->closing = true;  // unbounded outbox = dead/hostile reader
    c->out.clear();
    c->out_off = 0;
    return;
  }
  c->out.append(data, len);
}

void flush_queued(Shard* sh, Conn* c) {
  // mu held. Push burst-queued replies out with one send(); arm
  // EPOLLOUT for any leftover. Never closes/frees the connection (hard
  // errors mark `closing` and the IO loop reaps on the next event), so
  // callers keep their pointer.
  if (sh->uring) {
    uring_arm_send(sh, c);
    return;
  }
  if (c->out_off >= c->out.size() || c->want_write) return;
  count_sys(sh);
  ssize_t n = ::send(c->fd, c->out.data() + c->out_off,
                     c->out.size() - c->out_off, MSG_NOSIGNAL);
  if (n >= 0) {
    c->out_off += size_t(n);
  } else if (errno != EAGAIN && errno != EWOULDBLOCK) {
    c->closing = true;
    c->out.clear();
    c->out_off = 0;
    return;
  }
  if (c->out_off >= c->out.size()) {
    c->out.clear();
    c->out_off = 0;
    return;
  }
  c->want_write = true;
  epoll_event ev{};
  ev.events = EPOLLIN | EPOLLOUT;
  ev.data.u64 = c->id;
  count_sys(sh);
  epoll_ctl(sh->epfd, EPOLL_CTL_MOD, c->fd, &ev);
}

void flush_out(Shard* sh, Conn* c) {
  // mu held. Cursor-based drain: erase-from-front per partial send is
  // O(n^2) memmove on a multi-MB backpressured outbox, all of it under
  // the global mutex — advance out_off instead, compact occasionally.
  if (sh->uring) {
    uring_arm_send(sh, c);
    return;
  }
  while (c->out_off < c->out.size()) {
    count_sys(sh);
    ssize_t n = ::send(c->fd, c->out.data() + c->out_off,
                       c->out.size() - c->out_off, MSG_NOSIGNAL);
    if (n < 0) {
      if (errno == EAGAIN || errno == EWOULDBLOCK) {
        if (c->out_off > (1u << 20)) {
          c->out.erase(0, c->out_off);
          c->out_off = 0;
        }
        return;
      }
      close_conn(sh, c);
      return;
    }
    c->out_off += size_t(n);
  }
  c->out.clear();
  c->out_off = 0;
  if (c->closing) {
    close_conn(sh, c);
    return;
  }
  if (c->want_write) {
    c->want_write = false;
    epoll_event ev{};
    ev.events = EPOLLIN;
    ev.data.u64 = c->id;
    epoll_ctl(sh->epfd, EPOLL_CTL_MOD, c->fd, &ev);
  }
}

// A completed batch reopens the pipeline: hand the accumulated arrivals
// straight to the pump instead of waiting out the deadline timer (the
// adaptive half of flush-on-idle — batch size tracks Python's service
// time under load, and completion immediately restarts service).
void maybe_flush_after_complete(Shard* sh);

void flush_pending(Shard* sh, bool include_tail) {
  // mu held. pending -> ready queue in <= max_batch chunks (max_batch
  // bounds flush SIZE like the asyncio MicroBatcher's, not just the
  // flush trigger). A size-triggered flush (include_tail=false) emits
  // only FULL chunks and keeps the sub-max_batch tail pending to
  // coalesce with the next arrivals — the MicroBatcher's remainder
  // semantics (batcher.py); deadline/idle flushes drain everything
  // (the tail is as overdue as the rest).
  if (sh->pending.empty()) return;
  size_t n = sh->pending.size();
  size_t limit = include_tail ? n : (n / sh->max_batch) * sh->max_batch;
  if (limit == 0) return;
  size_t pos = 0;
  uint64_t t_cut = now_ns();
  while (pos < limit) {
    size_t take = limit - pos;
    if (take > sh->max_batch) take = sh->max_batch;
    Batch b;
    b.id = sh->next_batch_id++;
    b.t_flush_ns = t_cut;
    b.items.assign(std::make_move_iterator(sh->pending.begin() + pos),
                   std::make_move_iterator(sh->pending.begin() + pos +
                                           take));
    pos += take;
    sh->ready.push_back(std::move(b));
    sh->batches_flushed++;
  }
  if (limit == n) {
    sh->pending.clear();
  } else {
    sh->pending.erase(sh->pending.begin(),
                      sh->pending.begin() + static_cast<ptrdiff_t>(limit));
    sh->pending_oldest_ns = sh->pending.front().t_ns;
  }
  sh->cv.notify_one();
}

void maybe_flush_after_complete(Shard* sh) {
  // mu held (called from fe_complete / fe_fail / finish_bulk_job).
  if (!sh->pending.empty() && sh->ready.empty() && sh->pt.empty() &&
      sh->inflight.empty() && sh->bulk_ready.empty() &&
      sh->bulk_inflight.empty()) {
    flush_pending(sh, /*include_tail=*/true);  // pipeline idle: drain
  }
}

void to_passthrough(Shard* sh, Conn* c, const uint8_t* body,
                    size_t len) {
  // mu held. Hand a frame to Python wholesale — the wire module stays
  // the single authority for every non-hot (or malformed) shape.
  Passthrough ptf;
  ptf.conn_id = c->id;
  ptf.frame.assign(reinterpret_cast<const char*>(body), len);
  sh->pt.push_back(std::move(ptf));
  sh->cv.notify_one();
}

// ---------------------------------------------------------------------
// Native bulk lane (round 8). One OP_ACQUIRE_MANY frame = one RESP_BULK
// reply; tier-0 decides hot bucket rows per-row against the SAME
// replica table (and therefore the same epsilon envelope) as the scalar
// ACQUIRE lane — one budget, not two. Rows tier-0 cannot decide cross
// the fe_bulk_* ABI as a zero-copy residue batch.
// ---------------------------------------------------------------------

std::string encode_bulk_reply(uint32_t seq, bool with_rem, uint32_t n,
                              const uint8_t* verdict,
                              const float* remaining) {
  // Byte-identical to wire.encode_bulk_response: [u8 flags][u32 n]
  // [granted bits, LSB-first] [f32 remaining × n iff flags bit 0].
  size_t nbits = (size_t(n) + 7) / 8;
  size_t payload = kBulkRespHead + nbits + (with_rem ? 4ull * n : 0);
  std::string s;
  s.reserve(4 + kBodyOff + payload);
  wr_u32(&s, uint32_t(kBodyOff + payload));
  s.push_back(char(kVersion));
  wr_u32(&s, seq);
  s.push_back(char(RESP_BULK));
  s.push_back(char(with_rem ? kBulkFlagRemaining : 0));
  wr_u32(&s, n);
  for (uint32_t base = 0; base < n; base += 8) {
    uint8_t byte = 0;
    for (uint32_t j = 0; j < 8 && base + j < n; j++) {
      byte |= uint8_t((verdict[base + j] == 1 ? 1u : 0u) << j);
    }
    s.push_back(char(byte));
  }
  if (with_rem) {
    s.append(reinterpret_cast<const char*>(remaining), 4ull * n);
  }
  return s;
}

// Per-frame hot-key aggregation for the heavy-hitter sketch: the bulk
// lane's keys never materialize in Python (KeyBlob end to end — the
// PR-2 exemption), so the C side mirrors the scalar batch lane's
// "top-K per batch" feed: one bounded open-addressed pass over the
// frame, then the frame's heaviest rows land in a ring the pump drains
// into the sketch. Telemetry-grade: scratch overflow and hash-identity
// merging cost tail fidelity, never head weight.
constexpr size_t kHotScratchSlots = 512;  // power of two
constexpr size_t kHotScratchProbe = 4;
constexpr size_t kHotTopPerFrame = 32;
constexpr size_t kHotRingCap = 4096;

void bulk_hot_feed(Shard* sh, const uint8_t* blob,
                   const int64_t* offs, const int64_t* counts,
                   uint64_t n) {
  // mu held.
  if (sh->hot_scratch.empty()) sh->hot_scratch.resize(kHotScratchSlots);
  sh->hot_epoch++;
  uint64_t epoch = sh->hot_epoch;
  size_t used_idx[kHotScratchSlots];
  size_t used = 0;
  for (uint64_t i = 0; i < n; i++) {
    int64_t w = counts[i];
    if (w <= 0) continue;  // probes/releases are not admission demand
    size_t klen = size_t(offs[i + 1] - offs[i]);
    if (klen == 0 || klen > kT0MaxKey) continue;
    std::string_view key(reinterpret_cast<const char*>(blob) + offs[i],
                         klen);
    uint64_t hsh = t0_hash(key);
    size_t idx = size_t(hsh) & (kHotScratchSlots - 1);
    for (size_t pr = 0; pr < kHotScratchProbe; pr++) {
      size_t at = (idx + pr) & (kHotScratchSlots - 1);
      HotSlot& s = sh->hot_scratch[at];
      if (s.epoch != epoch) {
        s.epoch = epoch;
        s.hash = hsh;
        s.row = int64_t(i);
        s.weight = double(w);
        used_idx[used++] = at;
        break;
      }
      if (s.hash == hsh) {  // hash identity suffices for telemetry
        s.weight += double(w);
        break;
      }
    }
  }
  size_t top = used < kHotTopPerFrame ? used : kHotTopPerFrame;
  if (top < used) {
    std::nth_element(used_idx, used_idx + top, used_idx + used,
                     [&](size_t x, size_t y) {
                       return sh->hot_scratch[x].weight >
                              sh->hot_scratch[y].weight;
                     });
  }
  for (size_t j = 0; j < top; j++) {
    const HotSlot& s = sh->hot_scratch[used_idx[j]];
    if (sh->hot_ring.size() >= kHotRingCap) {
      sh->hot_ring.pop_front();
      sh->hot_dropped++;
    }
    sh->hot_ring.emplace_back(
        std::string(
            reinterpret_cast<const char*>(blob) + offs[s.row],
            size_t(offs[s.row + 1] - offs[s.row])),
        s.weight);
  }
}

// Parse + decide one OP_ACQUIRE_MANY frame natively. Returns false when
// the frame does not parse as a well-formed bulk request — the caller
// routes it to the Python passthrough lane, where wire.py (the
// protocol authority) raises the exact routable error the asyncio
// server would, byte for byte. Well-formed frames never leave C unless
// rows need the store.
bool handle_bulk_frame(Shard* sh, Conn* c, const uint8_t* body,
                       size_t len) {
  // mu held (parse burst on the IO thread, or a parked-frame drain /
  // fe_set_authed replay on the loop thread).
  if (len < kBodyOff + kBulkReqHead) return false;
  const uint8_t* p = body + kBodyOff;
  uint8_t flags = p[0];
  double a = rd_f64(p + 1);
  double b = rd_f64(p + 9);
  uint64_t n = rd_u32(p + 17);
  uint8_t kind = uint8_t((flags & kBulkKindMask) >> kBulkKindShift);
  // Kinds past FWINDOW (BULK_KIND_HBUCKET's tenant extension) are
  // Python-lane shapes: wire.py either serves them (hierarchical) or
  // raises the routable error. drl-check wire-hier pins this gate.
  if (kind > BULK_KIND_FWINDOW) return false;
  if (n == 0) return false;  // degenerate frame: Python authority
  bool traced = (flags & BULK_FLAG_TRACED) != 0;
  size_t tail = traced ? kTraceTail : 0;
  if (kBodyOff + kBulkReqHead + 6 * n + tail > len) return false;
  const uint8_t* kl = p + kBulkReqHead;
  uint64_t total = 0;
  for (uint64_t i = 0; i < n; i++) total += rd_u16(kl + 2 * i);
  if (len != kBodyOff + kBulkReqHead + 6 * n + total + tail) return false;
  const uint8_t* blob = kl + 2 * n;
  const uint8_t* cnts = blob + total;
  uint32_t seq = rd_u32(body + 1);
  uint64_t now = now_ns();

  sh->bulk_frames++;
  sh->bulk_rows += int64_t(n);
  std::vector<int64_t>& offs = sh->bulk_offsets_scratch;
  std::vector<int64_t>& cnt64 = sh->bulk_counts_scratch;
  std::vector<uint8_t>& verdict = sh->bulk_verdict_scratch;
  std::vector<float>& remaining = sh->bulk_rem_scratch;
  std::vector<int32_t>& residue = sh->bulk_residue_scratch;
  offs.resize(n + 1);
  cnt64.resize(n);
  verdict.assign(n, 2);
  remaining.assign(n, 0.0f);
  residue.clear();
  bool t0able = sh->bulk_t0 &&
                sh->owner->t0_enabled.load(std::memory_order_relaxed) &&
                kind == BULK_KIND_BUCKET;
  // Agg modes after the decide pass (see the Shard scratch block):
  // grant-all rows fill verdict/remaining lock-free afterward; per-row
  // rows were written exactly by the legacy walk; residue-all rows
  // keep verdict 2 and fall through to Python.
  constexpr uint8_t kAggGrantAll = 0;
  constexpr uint8_t kAggPerRow = 1;
  constexpr uint8_t kAggResidue = 2;
  std::vector<int32_t>& agg_of = sh->bulk_aggof_scratch;
  size_t naggs = 0;
  if (t0able) {
    agg_of.assign(n, -1);
    // Epoch-stamped open table sized for the frame (2n slots, power of
    // two): no per-frame clear, collisions resolved by key bytes — a
    // hash-identity merge would fuse two tenants' budgets.
    size_t want = 2;
    while (want < 2 * n) want <<= 1;
    if (sh->bulk_aggtab_epoch.size() < want) {
      sh->bulk_aggtab_epoch.assign(want, 0);
      sh->bulk_aggtab_idx.assign(want, -1);
    }
    sh->bulk_agg_epoch++;
    sh->agg_hash.clear();
    sh->agg_first.clear();
    sh->agg_nrows.clear();
    sh->agg_total.clear();
    sh->agg_mode.clear();
  }
  size_t aggmask = t0able ? sh->bulk_aggtab_epoch.size() - 1 : 0;
  uint64_t aggepoch = sh->bulk_agg_epoch;
  int64_t off = 0;
  double permits_local = 0.0;
  offs[0] = 0;
  // Pass 1 — parse + aggregate. Tier-0-eligible rows group by key (one
  // agg per distinct key); nothing is decided and no lock is touched
  // while the row loop runs.
  for (uint64_t i = 0; i < n; i++) {
    size_t klen = rd_u16(kl + 2 * i);
    std::string_view key(
        reinterpret_cast<const char*>(blob) + off, klen);
    off += int64_t(klen);
    offs[i + 1] = off;
    int64_t count = int64_t(rd_u32(cnts + 4 * i));
    cnt64[i] = count;
    if (t0able && count > 0 && klen <= kT0MaxKey) {
      uint64_t hsh = t0_hash(key);
      size_t slot = size_t(hsh) & aggmask;
      int32_t agg = -1;
      for (;;) {
        if (sh->bulk_aggtab_epoch[slot] != aggepoch) {
          agg = int32_t(naggs++);
          sh->bulk_aggtab_epoch[slot] = aggepoch;
          sh->bulk_aggtab_idx[slot] = agg;
          sh->agg_hash.push_back(hsh);
          sh->agg_first.push_back(int32_t(i));
          sh->agg_nrows.push_back(1);
          sh->agg_total.push_back(count);
          sh->agg_mode.push_back(kAggResidue);
          break;
        }
        int32_t cand = sh->bulk_aggtab_idx[slot];
        if (sh->agg_hash[size_t(cand)] == hsh) {
          int32_t fr = sh->agg_first[size_t(cand)];
          std::string_view fkey(
              reinterpret_cast<const char*>(blob) + offs[fr],
              size_t(offs[fr + 1] - offs[fr]));
          if (fkey == key) {
            agg = cand;
            sh->agg_nrows[size_t(cand)]++;
            sh->agg_total[size_t(cand)] += count;
            break;
          }
        }
        slot = (slot + 1) & aggmask;
      }
      agg_of[i] = agg;
    }
  }
  // Pass 2 — decide, ONE lock acquisition on the shard's own tier-0
  // slice and one envelope decision per KEY. The grant-all fast path
  // (the hot steady state: the key's summed ask fits this shard's
  // budget share) draws the aggregate down in O(1) under the lock; a
  // key near its envelope edge falls back to the exact per-row legacy
  // walk under the same lock, so boundary semantics — progressive
  // remaining, confident denies, fall-through — stay bit-identical to
  // the scalar lane's. Same replica slice, budgets, and counters as
  // the scalar ACQUIRE lane: a bulk row's local grant draws down the
  // exact envelope a scalar grant would (one epsilon budget, not two).
  if (t0able && naggs > 0) {
    sh->agg_before.assign(naggs, 0.0);
    sh->agg_lastrem.assign(naggs, 0.0);
    T0Part* part = t0_slice(sh);
    if (part != nullptr) {
      bool any_per_row = false;
      std::lock_guard<T0SpinMutex> lk(part->mu);
      for (size_t g = 0; g < naggs; g++) {
        int32_t fr = sh->agg_first[g];
        std::string_view key(
            reinterpret_cast<const char*>(blob) + offs[fr],
            size_t(offs[fr + 1] - offs[fr]));
        T0Entry* e = t0_find(part, key, sh->agg_hash[g], a, b);
        if (e == nullptr ||
            now - e->last_ack_ns > part->cfg.stale_ns) {
          part->misses += sh->agg_nrows[g];
          continue;  // kAggResidue: every row falls through identically
        }
        e->last_touch_ns = now;
        double total = double(sh->agg_total[g]);
        if (e->admitted + total <= e->budget) {
          sh->agg_mode[g] = kAggGrantAll;
          sh->agg_before[g] = e->admitted;
          sh->agg_lastrem[g] = e->last_remaining;
          e->admitted += total;
          e->pending += total;
          part->hits += sh->agg_nrows[g];
          part->grant_tokens += total;
          permits_local += total;
          continue;
        }
        // Envelope edge: mark for the exact legacy walk below. The
        // walk runs as ONE row pass over the frame for ALL boundary
        // keys together — a per-key rescan would be
        // O(boundary keys × rows) under this lock, and the boundary
        // regime (budget shares drawn down between sync rounds) is
        // exactly where frames get slow, not where they may.
        sh->agg_mode[g] = kAggPerRow;
        any_per_row = true;
      }
      if (any_per_row) {
        for (uint64_t i = 0; i < n; i++) {
          int32_t g = agg_of[i];
          if (g < 0 || sh->agg_mode[size_t(g)] != kAggPerRow) continue;
          std::string_view rkey(
              reinterpret_cast<const char*>(blob) + offs[i],
              size_t(offs[i + 1] - offs[i]));
          double rem = 0.0;
          int v = t0_decide_locked(part, rkey, sh->agg_hash[size_t(g)],
                                   cnt64[i], a, b, &rem, now);
          if (v >= 0) {
            verdict[i] = uint8_t(v);
            remaining[i] = float(rem);
            if (v == 1) permits_local += double(cnt64[i]);
          }
        }
      }
    }
    // Lock-free fill for the grant-all keys: row j's remaining is the
    // envelope view after its own grant (last acked balance minus the
    // running admitted) — exactly the per-row walk's estimates.
    sh->agg_run.assign(naggs, 0.0);
    for (uint64_t i = 0; i < n; i++) {
      int32_t g = agg_of[i];
      if (g < 0 || sh->agg_mode[size_t(g)] != kAggGrantAll) continue;
      sh->agg_run[size_t(g)] += double(cnt64[i]);
      verdict[i] = 1;
      remaining[i] = float(std::max(
          sh->agg_lastrem[size_t(g)] -
              (sh->agg_before[size_t(g)] + sh->agg_run[size_t(g)]),
          0.0));
    }
  }
  for (uint64_t i = 0; i < n; i++) {
    if (verdict[i] == 2) residue.push_back(int32_t(i));
  }
  if (sh->bulk_hot) {
    if (t0able && naggs > 0) {
      // The decide pass already aggregated this frame by key — feed
      // the sketch from the aggs instead of re-hashing every row
      // (bulk_hot_feed's own pass exists for frames the tier-0 lane
      // never grouped: windows, disabled tier-0). Same top-K bound.
      size_t top = naggs < kHotTopPerFrame ? naggs : kHotTopPerFrame;
      static_assert(kHotTopPerFrame > 0, "top-K feed");
      std::vector<size_t> order(naggs);
      for (size_t g = 0; g < naggs; g++) order[g] = g;
      if (top < naggs) {
        std::nth_element(order.begin(), order.begin() + top, order.end(),
                         [&](size_t x, size_t y) {
                           return sh->agg_total[x] > sh->agg_total[y];
                         });
      }
      for (size_t j = 0; j < top; j++) {
        size_t g = order[j];
        int32_t fr0 = sh->agg_first[g];
        if (sh->agg_total[g] <= 0 ||
            offs[fr0 + 1] - offs[fr0] == 0) {
          continue;  // empty keys stay out of the sketch, matching
                     // bulk_hot_feed's klen==0 filter
        }
        if (sh->hot_ring.size() >= kHotRingCap) {
          sh->hot_ring.pop_front();
          sh->hot_dropped++;
        }
        int32_t fr = sh->agg_first[g];
        sh->hot_ring.emplace_back(
            std::string(
                reinterpret_cast<const char*>(blob) + offs[fr],
                size_t(offs[fr + 1] - offs[fr])),
            double(sh->agg_total[g]));
      }
    } else {
      bulk_hot_feed(sh, blob, offs.data(), cnt64.data(), n);
    }
  }
  sh->bulk_rows_local += int64_t(n) - int64_t(residue.size());
  sh->bulk_permits_local += permits_local;
  if (residue.empty()) {
    // Whole frame decided locally: encode + queue RESP_BULK without
    // ever leaving this thread — the all-hot fast path.
    std::string resp = encode_bulk_reply(
        seq, (flags & kBulkFlagRemaining) != 0, uint32_t(n),
        verdict.data(), remaining.data());
    queue_to_conn(c, resp.data(), resp.size());
    uint64_t t_end = now_ns();
    if (traced) {
      const uint8_t* tp = body + len - kTraceTail;
      uint64_t hi, lo, parent;
      std::memcpy(&hi, tp, 8);
      std::memcpy(&lo, tp + 8, 8);
      std::memcpy(&parent, tp + 16, 8);
      bool all = true;
      for (uint64_t i = 0; i < n; i++) all = all && verdict[i] == 1;
      trace_ring_push_raw(sh, hi, lo, parent,
                          uint8_t(1 | (tp[24] & 1) << 1),
                          OP_ACQUIRE_MANY, all, now, t_end);
    }
    hist_record(sh, double(t_end - now) * 1e-9);
    sh->requests_served++;
    sh->bulk_frames_local++;
    c->cur_bulk = 0;  // nothing inflight: chained successors may run
    return true;
  }
  BulkJob job;
  job.id = sh->next_bulk_id++;
  job.conn_id = c->id;
  job.seq = seq;
  job.flags = flags;
  job.kind = kind;
  job.with_remaining = (flags & kBulkFlagRemaining) != 0;
  job.a = a;
  job.b = b;
  job.n = uint32_t(n);
  job.blob.assign(reinterpret_cast<const char*>(blob), size_t(total));
  job.offsets = offs;
  job.counts = cnt64;
  job.verdict = verdict;
  job.remaining = remaining;
  job.residue = residue;
  job.t_ns = now;
  if (traced) {
    const uint8_t* tp = body + len - kTraceTail;
    std::memcpy(&job.tr_hi, tp, 8);
    std::memcpy(&job.tr_lo, tp + 8, 8);
    std::memcpy(&job.tr_parent, tp + 16, 8);
    job.tr_flags = uint8_t(1 | (tp[24] & 1) << 1);
  }
  sh->bulk_rows_residue += int64_t(job.residue.size());
  c->cur_bulk = job.id;
  sh->bulk_ready.push_back(job.id);
  sh->bulk_inflight.emplace(job.id, std::move(job));
  sh->cv.notify_one();
  return true;
}

// Decide one un-parked bulk frame: native when well-formed, else the
// Python lane — and once a frame of a chain lands on the Python lane,
// its chained successors follow it there (the server's _bulk_tails
// keeps their order; deciding them natively would race the
// predecessor's reply).
void process_bulk_frame(Shard* sh, Conn* c, const uint8_t* body,
                        size_t len) {
  // mu held.
  bool chained =
      len > kBodyOff && (body[kBodyOff] & kBulkFlagChained) != 0;
  if (chained && c->bulk_pt_tail) {
    to_passthrough(sh, c, body, len);
    return;  // bulk_pt_tail stays set for the rest of the chain
  }
  if (!handle_bulk_frame(sh, c, body, len)) {
    to_passthrough(sh, c, body, len);  // malformed: Python errors
    c->bulk_pt_tail = true;
    return;
  }
  c->bulk_pt_tail = false;
}

void drain_parked(Shard* sh, Conn* c) {
  // mu held. Un-park chained successors once the connection has no
  // inflight bulk job; stops when a drained frame starts a new one (its
  // completion resumes the drain) or the connection goes bad.
  while (!c->parked_bulk.empty() && c->cur_bulk == 0 && !c->closing) {
    std::string f = std::move(c->parked_bulk.front());
    c->parked_bulk.pop_front();
    c->parked_bytes -= f.size();
    process_bulk_frame(sh, c,
                       reinterpret_cast<const uint8_t*>(f.data()),
                       f.size());
  }
  flush_queued(sh, c);
}

void finish_bulk_job(Shard* sh, int64_t job_id) {
  // mu held. Erase a completed/abandoned job and un-park the
  // connection's chained successors (the asyncio server's per-
  // connection bulk_tail contract, kept here by parking raw frames
  // until the predecessor's reply is encoded).
  auto it = sh->bulk_inflight.find(job_id);
  if (it == sh->bulk_inflight.end()) return;
  uint64_t conn_id = it->second.conn_id;
  sh->bulk_inflight.erase(it);
  auto itc = sh->conns.find(conn_id);
  if (itc != sh->conns.end()) {
    Conn* c = itc->second;
    if (c->cur_bulk == job_id) c->cur_bulk = 0;
    drain_parked(sh, c);
  }
  maybe_flush_after_complete(sh);
}

// Handle one complete frame body. Returns false if the connection must
// close (protocol breakage — an error reply is already queued). Called
// from parse_frames (IO thread) and from fe_set_authed's held-frame
// replay (loop thread); mu held either way.
bool handle_frame(Shard* sh, Conn* c, const uint8_t* body, size_t len) {
  if (c->closing) return true;  // replies would be dropped: stop mutating
                                // store state for a dying connection
  uint8_t ver = body[0];
  uint32_t seq = rd_u32(body + 1);
  uint8_t rawop = body[5];
  // The trace flag gates a 25-byte tail after the payload; the base op
  // routes. Non-hot flagged ops fall to the passthrough default with
  // the ORIGINAL body — Python's wire module strips the tail there.
  bool traced = (rawop & TRACE_FLAG) != 0;
  uint8_t op = rawop & uint8_t(~TRACE_FLAG);
  if (ver != kVersion) {
    std::string err = encode_error(seq, "protocol version mismatch");
    send_to_conn(sh, c, err.data(), err.size());
    return false;
  }
  if (!c->authed) {
    if (op == OP_HELLO) {
      c->auth_pending = true;  // Python resolves via fe_set_authed
    } else if (c->auth_pending) {
      // Pipelined behind an unresolved HELLO (legal — the asyncio path
      // reads frames sequentially so ordering makes this work there;
      // here auth resolves asynchronously, so park the frame until it
      // does). Bounded: a flood before auth is protocol abuse.
      if (c->held_bytes + len > kMaxHeld) {
        std::string err = encode_error(seq, "auth pending: too much data");
        send_to_conn(sh, c, err.data(), err.size());
        return false;
      }
      c->held.emplace_back(reinterpret_cast<const char*>(body), len);
      c->held_bytes += len;
      return true;
    } else {
      std::string err =
          encode_error(seq, "authentication required: send HELLO first");
      send_to_conn(sh, c, err.data(), err.size());
      return false;
    }
  }
  switch (op) {
      case OP_ACQUIRE:
      case OP_WINDOW:
      case OP_FWINDOW:
      case OP_SEMA: {
        // [u16 klen][key utf-8][i32 count][f64 a][f64 b] (+ trace tail)
        size_t tail = traced ? kTraceTail : 0;
        if (len < kBodyOff + 2 + 20 + tail) {
          std::string err = encode_error(seq, "truncated request");
          send_to_conn(sh, c, err.data(), err.size());
          return false;
        }
        uint16_t klen = rd_u16(body + kBodyOff);
        if (len != kBodyOff + 2 + size_t(klen) + 20 + tail) {
          std::string err = encode_error(seq, "malformed request");
          send_to_conn(sh, c, err.data(), err.size());
          return false;
        }
        const uint8_t* kp = body + kBodyOff + 2;
        Item it;
        it.conn_id = c->id;
        it.seq = seq;
        it.op = op;
        it.key.assign(reinterpret_cast<const char*>(kp), klen);
        it.count = rd_i32(kp + klen);
        it.a = rd_f64(kp + klen + 4);
        it.b = rd_f64(kp + klen + 12);
        it.t_ns = now_ns();
        if (traced) {
          const uint8_t* tp = body + len - kTraceTail;
          std::memcpy(&it.tr_hi, tp, 8);
          std::memcpy(&it.tr_lo, tp + 8, 8);
          std::memcpy(&it.tr_parent, tp + 16, 8);
          it.tr_flags = uint8_t(1 | (tp[24] & 1) << 1);
        }
        if (op == OP_ACQUIRE && it.count > 0 &&
            sh->owner->t0_enabled.load(std::memory_order_relaxed)) {
          // Tier-0: answer from the local replica when it is confident
          // either way; zero-permit probes and every other op keep the
          // exact device path. A traced local decision leaves a span
          // record for the Python harvest — locally-granted requests
          // still trace.
          double rem = 0.0;
          int verdict = t0_decide(t0_slice(sh), it.key, it.count, it.a,
                                  it.b, &rem, it.t_ns);
          if (verdict >= 0) {
            std::string resp = encode_decision(seq, verdict == 1, rem);
            queue_to_conn(c, resp.data(), resp.size());
            uint64_t t_end = now_ns();
            if (traced) trace_ring_push(sh, it, verdict == 1, t_end);
            hist_record(sh, double(t_end - it.t_ns) * 1e-9);
            sh->requests_served++;
            break;
          }
        }
        if (sh->pending.empty()) sh->pending_oldest_ns = it.t_ns;
        sh->pending.push_back(std::move(it));
        break;
      }
      case OP_PING: {
        std::string resp = encode_empty(seq);
        queue_to_conn(c, resp.data(), resp.size());
        sh->requests_served++;  // the asyncio server counts pings too
        break;
      }
      case OP_ACQUIRE_MANY: {
        if (!sh->bulk_native) {
          // The pump never armed the lane (older Python half, or the
          // operator disabled it): round-7 passthrough behavior.
          to_passthrough(sh, c, body, len);
          break;
        }
        bool chained =
            len > kBodyOff && (body[kBodyOff] & kBulkFlagChained) != 0;
        bool busy = c->cur_bulk != 0 &&
                    sh->bulk_inflight.count(c->cur_bulk) != 0;
        if (!c->parked_bulk.empty() || (chained && busy)) {
          // Chained chunk behind an in-flight predecessor (or any bulk
          // frame queued behind a parked chain — FIFO keeps relative
          // order trivially): park the raw frame; completion drains in
          // order. Bounded like the outbox: a chain backlog past the
          // budget is a dead/hostile pipeliner.
          if (c->parked_bytes + len > kMaxConnOut) {
            std::string err = encode_error(
                seq, "bulk chain backlog exceeds buffer budget");
            send_to_conn(sh, c, err.data(), err.size());
            return false;
          }
          c->parked_bulk.emplace_back(
              reinterpret_cast<const char*>(body), len);
          c->parked_bytes += len;
          break;
        }
        // Malformed / degenerate shapes go to Python inside
        // process_bulk_frame so the error reply stays byte-identical
        // to the asyncio server's — and mark the conn's bulk tail as
        // Python-side so a chained successor follows it there.
        process_bulk_frame(sh, c, body, len);
        break;
      }
      case OP_PLACEMENT:
      case OP_PLACEMENT_ANNOUNCE:
      case OP_MIGRATE_PULL:
      case OP_MIGRATE_PUSH:
      case OP_CONFIG:
      case OP_RESERVE:
      case OP_SETTLE:
      case OP_FED_LEASE:
      case OP_FED_RENEW:
      case OP_FED_RECLAIM:
      case OP_AUDIT:
      default: {
        // Placement/migration/config/reservation/federation control
        // ops, HELLO,
        // PEEK, SYNC, STATS, SAVE, unknown: Python decides (including
        // the unknown-op error) — the wire module stays the single
        // authority for every non-hot shape. ACQUIRE_MANY left this
        // list in round 8: well-formed bulk frames are native above,
        // and only malformed ones fall through so wire.py raises the
        // exact routable error.
        to_passthrough(sh, c, body, len);
        break;
      }
  }
  return true;
}

// Parse every complete frame in c->in. Returns false if the connection
// must close (an error reply is already queued).
bool parse_frames(Shard* sh, Conn* c) {
  // mu held.
  for (;;) {
    if (c->closing) {  // drop pipelined input behind a fatal reply — the
      c->in_off = c->in.size();  // store must not mutate for dead replies
      break;
    }
    size_t avail = c->in.size() - c->in_off;
    if (avail < 4) break;
    const uint8_t* p = c->in.data() + c->in_off;
    uint32_t len = rd_u32(p);
    if (len < kBodyOff || len > kMaxFrame) {
      std::string err = encode_error(0, "bad frame length");
      send_to_conn(sh, c, err.data(), err.size());
      return false;
    }
    if (avail < 4 + size_t(len)) break;
    const uint8_t* body = p + 4;
    c->in_off += 4 + len;
    if (!handle_frame(sh, c, body, len)) return false;
  }
  // Compact the read buffer once the parsed prefix dominates.
  if (c->in_off > 0 && (c->in_off == c->in.size() || c->in_off > 65536)) {
    c->in.erase(c->in.begin(), c->in.begin() + ptrdiff_t(c->in_off));
    c->in_off = 0;
  }
  // One send() for the whole burst's queued replies (tier-0/PING).
  flush_queued(sh, c);
  return true;
}

void arm_deadline(Shard* sh) {
  // mu held. Arm the timerfd for the oldest pending request's flush
  // deadline (ns precision — this is why not epoll_wait's ms timeout).
  bool want = !sh->pending.empty();
  if (!want && !sh->tfd_armed) return;  // already disarmed: skip syscall
  sh->tfd_armed = want;
  itimerspec its{};
  if (!sh->pending.empty()) {
    uint64_t due = sh->pending_oldest_ns + sh->deadline_ns;
    uint64_t now = now_ns();
    uint64_t delta = due > now ? due - now : 1;
    its.it_value.tv_sec = time_t(delta / 1000000000ull);
    its.it_value.tv_nsec = long(delta % 1000000000ull);
  }  // pending empty => zero itimerspec disarms
  count_sys(sh);
  timerfd_settime(sh->tfd, 0, &its, nullptr);
}

void io_loop(Shard* sh) {
  epoll_event events[128];
  for (;;) {
    count_sys(sh);
    int n = epoll_wait(sh->epfd, events, 128, -1);
    if (sh->owner->stopping.load()) break;
    if (n < 0) {
      if (errno == EINTR) continue;
      break;
    }
    std::unique_lock<FeMutex> lk(sh->mu);
    for (int i = 0; i < n; i++) {
      uint64_t tag = events[i].data.u64;
      if (tag == 0) {  // listen socket
        for (;;) {
          count_sys(sh);
          int cfd = accept4(sh->listen_fd, nullptr, nullptr, SOCK_NONBLOCK);
          if (cfd < 0) break;
          int one = 1;
          count_sys(sh, 2);  // setsockopt + epoll_ctl below
          setsockopt(cfd, IPPROTO_TCP, TCP_NODELAY, &one, sizeof one);
          Conn* c = new Conn();
          c->fd = cfd;
          c->id = sh->next_conn_id++;
          c->authed = !sh->require_auth;
          sh->conns[c->id] = c;
          sh->connections_served++;
          epoll_event ev{};
          ev.events = EPOLLIN;
          ev.data.u64 = c->id;
          epoll_ctl(sh->epfd, EPOLL_CTL_ADD, cfd, &ev);
        }
        continue;
      }
      if (tag == 1) {  // eventfd: stop/wake
        uint64_t junk;
        count_sys(sh);
        while (read(sh->evfd, &junk, 8) == 8) {
          count_sys(sh);
        }
        continue;
      }
      if (tag == 2) {  // timerfd: flush deadline
        uint64_t junk;
        count_sys(sh);
        while (read(sh->tfd, &junk, 8) == 8) {
          count_sys(sh);
        }
        sh->tfd_armed = false;  // one-shot timer disarmed itself
        flush_pending(sh, /*include_tail=*/true);  // deadline: all due
        continue;
      }
      auto itc = sh->conns.find(tag);
      if (itc == sh->conns.end()) continue;  // closed earlier this burst
      Conn* c = itc->second;
      uint32_t evs = events[i].events;
      if (evs & (EPOLLHUP | EPOLLERR)) {
        close_conn(sh, c);
        continue;
      }
      if (evs & EPOLLOUT) {
        flush_out(sh, c);
        itc = sh->conns.find(tag);
        if (itc == sh->conns.end()) continue;  // flush closed it
      }
      if (evs & EPOLLIN) {
        bool eof = false, ok = true;
        for (;;) {
          uint8_t buf[65536];
          count_sys(sh);
          ssize_t r = ::recv(c->fd, buf, sizeof buf, 0);
          if (r > 0) {
            c->in.insert(c->in.end(), buf, buf + r);
            if (c->in.size() - c->in_off > 2 * size_t(kMaxFrame) + 4) {
              // Parse eagerly so a pipelining client can't balloon RAM.
              ok = parse_frames(sh, c);
              if (!ok) break;
            }
            continue;
          }
          if (r == 0) {
            eof = true;
            break;
          }
          if (errno == EAGAIN || errno == EWOULDBLOCK) break;
          eof = true;  // ECONNRESET et al.
          break;
        }
        if (ok) ok = parse_frames(sh, c);
        if (!ok || eof) {
          if (!ok && !c->out.empty()) {
            c->closing = true;  // let the error reply drain first
            flush_out(sh, c);
          } else {
            close_conn(sh, c);
          }
          continue;
        }
      }
    }
    // Flush decision once per event burst (so one TCP segment's worth of
    // pipelined frames coalesces into one batch, not N):
    if (!sh->pending.empty()) {
      // "Idle" means nothing is queued for OR being served by Python
      // (ready empty AND inflight empty): batching only pays when a
      // flush is already running — while one is, arrivals accumulate so
      // the batch size adapts to load (same reasoning as MicroBatcher's
      // flush-on-idle).
      bool idle_pump = sh->pump_waiting && sh->ready.empty() &&
                       sh->pt.empty() && sh->inflight.empty() &&
                       sh->bulk_ready.empty() && sh->bulk_inflight.empty();
      bool due = now_ns() >= sh->pending_oldest_ns + sh->deadline_ns;
      if (sh->pending.size() >= sh->max_batch || idle_pump || due) {
        // Size-only trigger holds the sub-max_batch tail to coalesce;
        // idle/deadline triggers drain it (see flush_pending).
        flush_pending(sh, /*include_tail=*/idle_pump || due);
      }
    }
    arm_deadline(sh);
  }
  // Shutdown: fail the pump out of its wait and close every socket.
  std::lock_guard<FeMutex> lk(sh->mu);
  for (auto& [id, c] : sh->conns) {
    ::close(c->fd);
    delete c;
  }
  sh->conns.clear();
  sh->cv.notify_all();
}

void wake_io(Shard* sh) {
  uint64_t one = 1;
  count_sys(sh);
  ssize_t r = write(sh->evfd, &one, 8);
  (void)r;
}

// ---------------------------------------------------------------------
// io_uring transport (round 16). The reply bytes are the spec — every
// frame still flows through the SAME parse_frames / handle_frame /
// handle_bulk_frame / flush_pending machinery as the epoll loop; only
// how bytes cross the kernel boundary changes. Contract notes:
//   * order: at most ONE SEND op in flight per connection, staged from
//     wbuf (the bytes an in-flight op points at) with out as the
//     overflow queue — submission order IS reply order.
//   * teardown: a Conn with owed CQEs parks in Shard::dying until its
//     uring_ops drains to zero (a kernel op holds pointers into the
//     Conn), then frees. close_conn and every helper branch here when
//     sh->uring is set, so callers never see transport-specific state.
//   * graceful close: when the farewell bytes are fully staged, a CLOSE
//     is linked behind the SEND (IOSQE_IO_LINK). The kernel breaks a
//     link on error OR short transfer, so the close runs only when the
//     goodbye actually drained — otherwise the send CQE re-arms.
// ---------------------------------------------------------------------

// Runtime feature probe. Returns 1 when the 5.19+ feature level this
// transport needs is present; 0 with a human-readable reason otherwise.
// Sanitizer builds gate the transport off: the ring's kernel-side
// writes into shared memory are invisible to ASan/TSan instrumentation.
int uring_probe(std::string* reason) {
#if defined(DRL_TSAN) || defined(DRL_ASAN)
  if (reason) *reason = "sanitizer build: uring transport feature-gated off";
  return 0;
#else
  const char* no = std::getenv("DRL_TPU_NO_URING");
  if (no != nullptr && *no != '\0' && std::string(no) != "0") {
    if (reason) *reason = "disabled by DRL_TPU_NO_URING";
    return 0;
  }
  const char* deny = std::getenv("DRL_TPU_URING_FAKE_DENY");
  if (deny != nullptr && *deny != '\0' && std::string(deny) != "0") {
    // Test hook: behave exactly as a seccomp filter returning EPERM.
    if (reason) *reason = "io_uring_setup denied (EPERM, simulated seccomp)";
    return 0;
  }
  DrlUringParams p{};
  int fd = sys_uring_setup(4, &p);
  if (fd < 0) {
    if (reason) {
      if (errno == ENOSYS) {
        *reason = "kernel lacks io_uring (ENOSYS)";
      } else if (errno == EPERM) {
        *reason = "io_uring_setup denied (EPERM — seccomp or "
                  "kernel.io_uring_disabled)";
      } else {
        *reason = std::string("io_uring_setup failed: ") + strerror(errno);
      }
    }
    return 0;
  }
  DrlProbe probe{};
  int rc = sys_uring_register(fd, kRegRegisterProbe, &probe, 48);
  ::close(fd);
  if (rc < 0) {
    if (reason) *reason = "io_uring opcode probe unsupported (pre-5.6)";
    return 0;
  }
  if (probe.last_op < kOpSocketProxy) {
    // Multishot recv and PBUF_RING have no probe bit; IORING_OP_SOCKET
    // shipped in the same release (5.19) and is the documented proxy.
    if (reason) {
      *reason = "kernel predates the 5.19 feature level "
                "(multishot recv + provided-buffer rings)";
    }
    return 0;
  }
  const uint8_t need[] = {kOpAccept, kOpAsyncCancel, kOpClose,
                          kOpRead,   kOpSend,        kOpRecv};
  for (uint8_t op : need) {
    if (op >= probe.ops_len || (probe.ops[op].flags & 1) == 0) {
      if (reason) {
        *reason = "required io_uring opcode " + std::to_string(int(op)) +
                  " not supported";
      }
      return 0;
    }
  }
  if (reason) reason->clear();
  return 1;
#endif
}

void uring_free_ring(UringRing* r) {
  if (r == nullptr) return;
  if (r->buf_ring != nullptr) munmap(r->buf_ring, r->buf_ring_len);
  if (r->buf_pool != nullptr) munmap(r->buf_pool, r->buf_pool_len);
  if (r->sqes != nullptr) munmap(r->sqes, r->sqes_len);
  if (r->cq_map != nullptr && r->cq_map != r->sq_map) {
    munmap(r->cq_map, r->cq_map_len);
  }
  if (r->sq_map != nullptr) munmap(r->sq_map, r->sq_map_len);
  if (r->fd >= 0) ::close(r->fd);
  delete r;
}

// Return one provided buffer to the recv pool. Entry 0's resv overlays
// the ring tail; the release store publishes the refilled slot to the
// kernel (mirrors liburing's io_uring_buf_ring_advance).
void uring_recycle_buf(UringRing* r, uint16_t bid) {
  DrlBuf* e = &r->buf_ring[r->buf_tail & (kUringBufCount - 1)];
  e->addr = uint64_t(reinterpret_cast<uintptr_t>(r->buf_pool)) +
            uint64_t(bid) * kUringBufSize;
  e->len = uint32_t(kUringBufSize);
  e->bid = bid;
  r->buf_tail++;
  reinterpret_cast<std::atomic<uint16_t>*>(&r->buf_ring[0].resv)
      ->store(r->buf_tail, std::memory_order_release);
}

bool uring_setup_shard(Shard* sh, bool sqpoll) {
  std::string reason;
  if (uring_probe(&reason) == 0) {
    sh->uring_reason = reason;
    return false;
  }
  DrlUringParams p{};
  p.flags = kUringSetupCqsize | kUringSetupClamp;
  p.cq_entries = kUringCqEntries;
  if (sqpoll) {
    p.flags |= kUringSetupSqpoll;
    p.sq_thread_idle = 50;  // ms the kernel SQ thread spins before napping
  }
  int fd = sys_uring_setup(kUringSqEntries, &p);
  if (fd < 0 && sqpoll) {
    // SQPOLL needs CAP_SYS_NICE pre-5.11 and can be policy-refused;
    // fall one notch to plain uring rather than all the way to epoll.
    sqpoll = false;
    p = DrlUringParams{};
    p.flags = kUringSetupCqsize | kUringSetupClamp;
    p.cq_entries = kUringCqEntries;
    fd = sys_uring_setup(kUringSqEntries, &p);
    sh->uring_reason = "sqpoll refused by kernel; running uring without it";
  }
  if (fd < 0) {
    sh->uring_reason = std::string("io_uring_setup failed: ") +
                       strerror(errno);
    return false;
  }
  UringRing* r = new UringRing();
  r->fd = fd;
  r->sqpoll = sqpoll;
  size_t sq_len = size_t(p.sq_off.array) + p.sq_entries * sizeof(uint32_t);
  size_t cq_len = size_t(p.cq_off.cqes) + p.cq_entries * sizeof(DrlCqe);
  bool single = (p.features & kUringFeatSingleMmap) != 0;
  if (single) sq_len = cq_len = std::max(sq_len, cq_len);
  void* sq = mmap(nullptr, sq_len, PROT_READ | PROT_WRITE,
                  MAP_SHARED | MAP_POPULATE, fd, long(kUringOffSqRing));
  if (sq == MAP_FAILED) {
    sh->uring_reason = "sq ring mmap failed";
    uring_free_ring(r);
    return false;
  }
  r->sq_map = sq;
  r->sq_map_len = sq_len;
  if (single) {
    r->cq_map = sq;
    r->cq_map_len = sq_len;
  } else {
    void* cq = mmap(nullptr, cq_len, PROT_READ | PROT_WRITE,
                    MAP_SHARED | MAP_POPULATE, fd, long(kUringOffCqRing));
    if (cq == MAP_FAILED) {
      sh->uring_reason = "cq ring mmap failed";
      uring_free_ring(r);
      return false;
    }
    r->cq_map = cq;
    r->cq_map_len = cq_len;
  }
  r->sqes_len = p.sq_entries * sizeof(DrlSqe);
  void* sqes = mmap(nullptr, r->sqes_len, PROT_READ | PROT_WRITE,
                    MAP_SHARED | MAP_POPULATE, fd, long(kUringOffSqes));
  if (sqes == MAP_FAILED) {
    sh->uring_reason = "sqe array mmap failed";
    uring_free_ring(r);
    return false;
  }
  r->sqes = static_cast<DrlSqe*>(sqes);
  auto* sqb = static_cast<uint8_t*>(r->sq_map);
  r->sq_head =
      reinterpret_cast<std::atomic<uint32_t>*>(sqb + p.sq_off.head);
  r->sq_tail =
      reinterpret_cast<std::atomic<uint32_t>*>(sqb + p.sq_off.tail);
  r->sq_mask = *reinterpret_cast<uint32_t*>(sqb + p.sq_off.ring_mask);
  r->sq_array = reinterpret_cast<uint32_t*>(sqb + p.sq_off.array);
  r->sq_flags =
      reinterpret_cast<std::atomic<uint32_t>*>(sqb + p.sq_off.flags);
  auto* cqb = static_cast<uint8_t*>(r->cq_map);
  r->cq_head =
      reinterpret_cast<std::atomic<uint32_t>*>(cqb + p.cq_off.head);
  r->cq_tail =
      reinterpret_cast<std::atomic<uint32_t>*>(cqb + p.cq_off.tail);
  r->cq_mask = *reinterpret_cast<uint32_t*>(cqb + p.cq_off.ring_mask);
  r->cqes = reinterpret_cast<DrlCqe*>(cqb + p.cq_off.cqes);
  // Registered files: the three immortal control fds only (see the
  // UringRing comment for why conn sockets stay out of the table).
  int files[3] = {sh->listen_fd, sh->evfd, sh->tfd};
  if (sys_uring_register(fd, kRegRegisterFiles, files, 3) < 0) {
    sh->uring_reason = "IORING_REGISTER_FILES refused";
    uring_free_ring(r);
    return false;
  }
  // Provided-buffer ring + the registered buffer pool it points into.
  r->buf_ring_len = kUringBufCount * sizeof(DrlBuf);
  if (r->buf_ring_len < 4096) r->buf_ring_len = 4096;  // page-aligned
  void* br = mmap(nullptr, r->buf_ring_len, PROT_READ | PROT_WRITE,
                  MAP_ANONYMOUS | MAP_PRIVATE, -1, 0);
  if (br == MAP_FAILED) {
    r->buf_ring_len = 0;
    sh->uring_reason = "buffer-ring mmap failed";
    uring_free_ring(r);
    return false;
  }
  r->buf_ring = static_cast<DrlBuf*>(br);
  std::memset(r->buf_ring, 0, r->buf_ring_len);
  r->buf_pool_len = size_t(kUringBufCount) * kUringBufSize;
  void* pool = mmap(nullptr, r->buf_pool_len, PROT_READ | PROT_WRITE,
                    MAP_ANONYMOUS | MAP_PRIVATE, -1, 0);
  if (pool == MAP_FAILED) {
    r->buf_pool_len = 0;
    sh->uring_reason = "buffer-pool mmap failed";
    uring_free_ring(r);
    return false;
  }
  r->buf_pool = static_cast<uint8_t*>(pool);
  DrlBufReg reg{};
  reg.ring_addr = uint64_t(reinterpret_cast<uintptr_t>(r->buf_ring));
  reg.ring_entries = kUringBufCount;
  reg.bgid = kUringBgid;
  if (sys_uring_register(fd, kRegRegisterPbufRing, &reg, 1) < 0) {
    sh->uring_reason = "IORING_REGISTER_PBUF_RING refused (pre-5.19)";
    uring_free_ring(r);
    return false;
  }
  for (uint16_t b = 0; b < kUringBufCount; b++) uring_recycle_buf(r, b);
  sh->ring = r;
  sh->uring = true;
  sh->uring_sqpoll = sqpoll;
  return true;
}

// Stage-side submit. mu held (sq_pending and the SQ tail are only ever
// touched under it; the kernel reads the published tail with its own
// acquire). SQPOLL: no enter at all unless the kernel SQ thread napped.
void uring_submit(Shard* sh) {
  UringRing* r = sh->ring;
  if (r == nullptr) return;
  if (r->sqpoll) {
    if (r->sq_pending > 0) {
      r->sqes_submitted.fetch_add(r->sq_pending, std::memory_order_relaxed);
      r->sq_pending = 0;
    }
    if (r->sq_flags->load(std::memory_order_acquire) & kUringSqNeedWakeup) {
      count_sys(sh);
      r->enters.fetch_add(1, std::memory_order_relaxed);
      sys_uring_enter(r->fd, 0, 0, kUringEnterSqWakeup);
    }
    return;
  }
  while (r->sq_pending > 0) {
    count_sys(sh);
    r->enters.fetch_add(1, std::memory_order_relaxed);
    int rc = sys_uring_enter(r->fd, r->sq_pending, 0, 0);
    if (rc > 0) {
      uint32_t done = uint32_t(rc) > r->sq_pending ? r->sq_pending
                                                   : uint32_t(rc);
      r->sqes_submitted.fetch_add(done, std::memory_order_relaxed);
      r->sq_pending -= done;
      continue;
    }
    if (rc == 0) break;
    if (errno == EINTR) continue;
    // EBUSY/EAGAIN: CQ backpressure — keep them staged, retry after
    // the loop reaps completions. Anything else: drop the stage count
    // (the SQEs are still in the ring; a later submit re-offers them).
    break;
  }
}

// Acquire one zeroed SQE slot. mu held. A full ring submits first; the
// post-submit spin matters only under SQPOLL (non-SQPOLL enter consumes
// synchronously). Returns nullptr only when the kernel cannot drain —
// callers treat that as "stage later" and set sh->uring_sweep.
DrlSqe* uring_get_sqe(Shard* sh) {
  UringRing* r = sh->ring;
  uint32_t tail = r->sq_tail->load(std::memory_order_relaxed);
  uint32_t head = r->sq_head->load(std::memory_order_acquire);
  if (tail - head >= r->sq_mask + 1) {
    uring_submit(sh);
    for (int spin = 0; spin < 65536; spin++) {
      head = r->sq_head->load(std::memory_order_acquire);
      if (tail - head < r->sq_mask + 1) break;
    }
    if (tail - head >= r->sq_mask + 1) return nullptr;
  }
  uint32_t idx = tail & r->sq_mask;
  DrlSqe* sqe = &r->sqes[idx];
  std::memset(sqe, 0, sizeof *sqe);
  r->sq_array[idx] = idx;
  r->sq_tail->store(tail + 1, std::memory_order_release);
  r->sq_pending++;
  return sqe;
}

void uring_arm_accept(Shard* sh) {
  DrlSqe* sqe = uring_get_sqe(sh);
  if (sqe == nullptr) {
    sh->uring_sweep = true;
    return;
  }
  sqe->opcode = kOpAccept;
  sqe->flags = kSqeFixedFile;
  sqe->fd = 0;  // registered slot 0: the listen socket
  sqe->ioprio = kAcceptMultishot;
  sqe->op_flags = SOCK_NONBLOCK;
  sqe->user_data = uring_ud(kUdAccept, 0);
}

void uring_arm_ctl_read(Shard* sh, int slot, uint64_t* buf, uint64_t kind) {
  DrlSqe* sqe = uring_get_sqe(sh);
  if (sqe == nullptr) {
    sh->uring_sweep = true;
    return;
  }
  sqe->opcode = kOpRead;
  sqe->flags = kSqeFixedFile;
  sqe->fd = slot;  // registered slot 1 = eventfd, 2 = timerfd
  sqe->addr = uint64_t(reinterpret_cast<uintptr_t>(buf));
  sqe->len = 8;
  sqe->user_data = uring_ud(kind, 0);
}

void uring_arm_recv(Shard* sh, Conn* c) {
  if (c->recv_armed || c->dead || c->fd < 0) return;
  DrlSqe* sqe = uring_get_sqe(sh);
  if (sqe == nullptr) {
    sh->uring_sweep = true;  // loop retries at burst end
    return;
  }
  sqe->opcode = kOpRecv;
  sqe->flags = kSqeBufferSelect;
  sqe->ioprio = kRecvMultishot;
  sqe->fd = c->fd;
  sqe->len = 0;  // the provided buffer's size caps each completion
  sqe->buf_group = kUringBgid;
  sqe->user_data = uring_ud(kUdRecv, c->id);
  c->recv_armed = true;
  c->uring_ops++;
}

// Stage (at most) one SEND for this connection. mu held. NEVER closes
// or frees the Conn (same contract as flush_queued: callers keep their
// pointer) — drained+closing teardown happens in the send CQE handler
// or the loop's sweep.
void uring_arm_send(Shard* sh, Conn* c) {
  if (sh->ring == nullptr || c->dead || c->send_inflight || c->fd < 0) {
    return;
  }
  if (c->wbuf_off >= c->wbuf.size()) {
    c->wbuf.clear();
    c->wbuf_off = 0;
    if (c->out_off >= c->out.size()) {
      c->out.clear();
      c->out_off = 0;
      if (c->closing) {
        // Nothing left to drain and no op to complete into teardown:
        // let the IO loop reap at burst end.
        sh->uring_sweep = true;
        wake_io(sh);
      }
      return;
    }
    if (c->out_off > 0) c->out.erase(0, c->out_off);
    c->out_off = 0;
    c->wbuf.swap(c->out);  // out is now empty; new replies append there
  }
  UringRing* r = sh->ring;
  // Decide the linked-CLOSE up front: acquiring the second SQE must not
  // trigger a submit between the pair (a submit would flush the SEND
  // without its link flag — the kernel only links within one batch).
  bool link_close = false;
  if (c->closing && c->out_off >= c->out.size()) {
    uint32_t tail = r->sq_tail->load(std::memory_order_relaxed);
    uint32_t head = r->sq_head->load(std::memory_order_acquire);
    link_close = (tail - head) + 2 <= r->sq_mask + 1;
  }
  DrlSqe* sqe = uring_get_sqe(sh);
  if (sqe == nullptr) {
    sh->uring_sweep = true;  // bytes stay staged in wbuf; retried later
    return;
  }
  sqe->opcode = kOpSend;
  sqe->fd = c->fd;
  sqe->addr =
      uint64_t(reinterpret_cast<uintptr_t>(c->wbuf.data() + c->wbuf_off));
  sqe->len = uint32_t(c->wbuf.size() - c->wbuf_off);
  sqe->op_flags = MSG_NOSIGNAL;
  sqe->user_data = uring_ud(kUdSend, c->id);
  if (link_close) sqe->flags |= kSqeIoLink;
  c->send_inflight = true;
  c->uring_ops++;
  if (link_close) {
    DrlSqe* cl = uring_get_sqe(sh);
    if (cl != nullptr) {
      cl->opcode = kOpClose;
      cl->fd = c->fd;
      cl->user_data = uring_ud(kUdClose, c->id);
      c->close_linked = true;
      c->uring_ops++;
    } else {
      sqe->flags = uint8_t(sqe->flags & ~kSqeIoLink);
    }
  }
}

// Free the Conn once no kernel op holds pointers into it. mu held.
void uring_reap(Shard* sh, Conn* c) {
  if (c->uring_ops != 0) return;
  if (c->fd >= 0) {
    count_sys(sh);
    ::close(c->fd);
    c->fd = -1;
  }
  sh->dying.erase(c->id);
  delete c;
}

void uring_close_conn(Shard* sh, Conn* c) {
  // mu held. Tear down now if no op is in flight; otherwise park in
  // `dying` (a multishot RECV or SEND still references this Conn) and
  // let the owed CQEs drain it.
  if (c->dead) return;
  c->dead = true;
  c->closing = true;
  sh->conns.erase(c->id);
  sh->dying[c->id] = c;
  if (c->recv_armed) {
    DrlSqe* sqe = uring_get_sqe(sh);
    if (sqe != nullptr) {
      sqe->opcode = kOpAsyncCancel;
      sqe->addr = uring_ud(kUdRecv, c->id);
      sqe->user_data = uring_ud(kUdCancel, c->id);
      c->uring_ops++;
    }
    // SQE unavailable is near-impossible (get_sqe submits+drains); the
    // armed RECV then completes on its own once the peer acts, and
    // shutdown frees `dying` unconditionally.
  }
  if (c->fd >= 0 && !c->close_linked && !c->send_inflight) {
    count_sys(sh);
    ::close(c->fd);  // recv cancel above reaps the multishot op
    c->fd = -1;
  }
  uring_reap(sh, c);
}

void uring_handle_cqe(Shard* sh, const DrlCqe& cqe) {
  // mu held, called from uring_loop only.
  UringRing* r = sh->ring;
  uint64_t kind = cqe.user_data >> 56;
  uint64_t id = cqe.user_data & ((1ull << 56) - 1);
  if (kind == kUdAccept) {
    if ((cqe.flags & kCqeFMore) == 0) uring_arm_accept(sh);
    if (cqe.res < 0) return;
    int cfd = cqe.res;
    int one = 1;
    count_sys(sh);
    setsockopt(cfd, IPPROTO_TCP, TCP_NODELAY, &one, sizeof one);
    Conn* c = new Conn();
    c->fd = cfd;
    c->id = sh->next_conn_id++;
    c->authed = !sh->require_auth;
    sh->conns[c->id] = c;
    sh->connections_served++;
    uring_arm_recv(sh, c);
    return;
  }
  if (kind == kUdEvRead) {  // eventfd: stop/wake — loop rechecks flags
    uring_arm_ctl_read(sh, 1, &r->ev_buf, kUdEvRead);
    return;
  }
  if (kind == kUdTfRead) {  // timerfd: flush deadline
    sh->tfd_armed = false;
    uring_arm_ctl_read(sh, 2, &r->tf_buf, kUdTfRead);
    flush_pending(sh, /*include_tail=*/true);
    return;
  }
  auto ita = sh->conns.find(id);
  Conn* c = ita != sh->conns.end() ? ita->second : nullptr;
  Conn* d = nullptr;
  if (c == nullptr) {
    auto itd = sh->dying.find(id);
    if (itd != sh->dying.end()) d = itd->second;
  }
  Conn* any = c != nullptr ? c : d;
  if (kind == kUdRecv) {
    if ((cqe.flags & kCqeFMore) == 0 && any != nullptr && any->recv_armed) {
      any->recv_armed = false;
      any->uring_ops--;
    }
    if (cqe.flags & kCqeFBuffer) {
      uint16_t bid = uint16_t(cqe.flags >> kCqeBufferShift);
      if (c != nullptr && !c->closing && cqe.res > 0) {
        const uint8_t* p = r->buf_pool + size_t(bid) * kUringBufSize;
        c->in.insert(c->in.end(), p, p + cqe.res);
      }
      uring_recycle_buf(r, bid);  // ALWAYS — even when the conn is gone
    }
    if (c == nullptr) {
      if (d != nullptr) uring_reap(sh, d);
      return;
    }
    if (cqe.res > 0) {
      if (!c->closing) {
        if (!parse_frames(sh, c)) {
          if (c->out_off < c->out.size() ||
              c->wbuf_off < c->wbuf.size()) {
            c->closing = true;  // drain the error reply first
            uring_arm_send(sh, c);
          } else {
            uring_close_conn(sh, c);
          }
          return;
        }
      }
      if (!c->recv_armed && !c->closing) uring_arm_recv(sh, c);
      return;
    }
    if (cqe.res == -ENOBUFS) {
      // Pool exhausted this burst; recycles above refill it — re-arm.
      uring_arm_recv(sh, c);
      return;
    }
    if (cqe.res == -ECANCELED) return;
    uring_close_conn(sh, c);  // EOF (res==0) or hard error: epoll parity
    return;
  }
  if (kind == kUdSend) {
    if (any == nullptr) return;
    any->uring_ops--;
    any->send_inflight = false;
    if (c == nullptr) {
      uring_reap(sh, d);  // teardown already ran; just drain the op
      return;
    }
    if (cqe.res < 0) {
      if (cqe.res == -ECANCELED) return;
      // Broken pipe etc. An armed linked CLOSE got -ECANCELED (its own
      // CQE decrements); the fd is still ours to close.
      c->close_linked = false;
      uring_close_conn(sh, c);
      return;
    }
    c->wbuf_off += size_t(cqe.res);
    bool drained =
        c->wbuf_off >= c->wbuf.size() && c->out_off >= c->out.size();
    if (c->close_linked) {
      if (drained) return;  // the linked CLOSE's CQE finishes teardown
      // Short send broke the link (CLOSE comes back -ECANCELED): the
      // remainder re-arms below and a fresh close links when staged.
      c->close_linked = false;
    }
    if (!drained) {
      uring_arm_send(sh, c);
      return;
    }
    c->wbuf.clear();
    c->wbuf_off = 0;
    c->out.clear();
    c->out_off = 0;
    if (c->closing) uring_close_conn(sh, c);
    return;
  }
  if (kind == kUdClose) {
    if (any == nullptr) return;
    any->uring_ops--;
    any->close_linked = false;
    if (cqe.res >= 0) any->fd = -1;  // the kernel closed it
    // res < 0 (-ECANCELED: the short-send link break): fd still open;
    // close_conn/reap below ::close it.
    if (c != nullptr) {
      uring_close_conn(sh, c);
    } else {
      uring_reap(sh, d);
    }
    return;
  }
  if (kind == kUdCancel) {
    if (any == nullptr) return;
    any->uring_ops--;
    if (any->dead) uring_reap(sh, any);
    return;
  }
}

void uring_loop(Shard* sh) {
  UringRing* r = sh->ring;
  {
    std::lock_guard<FeMutex> lk(sh->mu);
    uring_arm_accept(sh);
    uring_arm_ctl_read(sh, 1, &r->ev_buf, kUdEvRead);
    uring_arm_ctl_read(sh, 2, &r->tf_buf, kUdTfRead);
    uring_submit(sh);
  }
  std::vector<uint64_t> doomed;
  for (;;) {
    // Wait (WITHOUT mu — pump threads stage and submit under it) only
    // when the CQ is empty; completed work never blocks on the wait.
    if (r->cq_head->load(std::memory_order_relaxed) ==
        r->cq_tail->load(std::memory_order_acquire)) {
      count_sys(sh);
      r->enters.fetch_add(1, std::memory_order_relaxed);
      int rc = sys_uring_enter(r->fd, 0, 1, kUringEnterGetevents);
      if (rc < 0 && errno != EINTR && errno != EAGAIN && errno != EBUSY &&
          errno != ETIME) {
        break;  // epoll-loop parity: a hard wait error ends the shard
      }
    }
    if (sh->owner->stopping.load()) break;
    std::unique_lock<FeMutex> lk(sh->mu);
    uint32_t head = r->cq_head->load(std::memory_order_relaxed);
    uint32_t tail = r->cq_tail->load(std::memory_order_acquire);
    while (head != tail) {
      DrlCqe cqe = r->cqes[head & r->cq_mask];
      head++;
      // Publish per-entry so the kernel regains CQ space mid-burst (a
      // 4096-deep CQ can otherwise overflow under multishot recv).
      r->cq_head->store(head, std::memory_order_release);
      r->cqes_seen.fetch_add(1, std::memory_order_relaxed);
      uring_handle_cqe(sh, cqe);
      tail = r->cq_tail->load(std::memory_order_acquire);
    }
    if (sh->uring_sweep) {
      // Rare slow path: an arm hit a full SQ, or a closing conn has no
      // in-flight op to complete into teardown. Walk and repair.
      sh->uring_sweep = false;
      doomed.clear();
      for (auto& [cid, cc] : sh->conns) {
        if (cc->closing && !cc->send_inflight &&
            cc->wbuf_off >= cc->wbuf.size() &&
            cc->out_off >= cc->out.size()) {
          doomed.push_back(cid);
          continue;
        }
        if (!cc->send_inflight && (cc->wbuf_off < cc->wbuf.size() ||
                                   cc->out_off < cc->out.size())) {
          uring_arm_send(sh, cc);
        }
        if (!cc->recv_armed && !cc->closing) uring_arm_recv(sh, cc);
      }
      for (uint64_t cid : doomed) {
        auto it = sh->conns.find(cid);
        if (it != sh->conns.end()) uring_close_conn(sh, it->second);
      }
    }
    // Flush decision once per completion burst — identical policy to
    // the epoll loop (flush-on-idle + deadline + size trigger).
    if (!sh->pending.empty()) {
      bool idle_pump = sh->pump_waiting && sh->ready.empty() &&
                       sh->pt.empty() && sh->inflight.empty() &&
                       sh->bulk_ready.empty() && sh->bulk_inflight.empty();
      bool due = now_ns() >= sh->pending_oldest_ns + sh->deadline_ns;
      if (sh->pending.size() >= sh->max_batch || idle_pump || due) {
        flush_pending(sh, /*include_tail=*/idle_pump || due);
      }
    }
    arm_deadline(sh);
    uring_submit(sh);
  }
  // Shutdown: fail the pump out of its wait and free every connection,
  // parked or live — owed CQEs die with the ring (fe_stop frees it
  // after this thread joins, so no op can complete into freed memory).
  std::lock_guard<FeMutex> lk(sh->mu);
  for (auto& [id, c] : sh->conns) {
    if (c->fd >= 0) ::close(c->fd);
    delete c;
  }
  sh->conns.clear();
  for (auto& [id, c] : sh->dying) {
    if (c->fd >= 0) ::close(c->fd);
    delete c;
  }
  sh->dying.clear();
  sh->cv.notify_all();
}

// Transport-mode resolution: DRL_TPU_NO_URING trumps everything (the
// operator's kill switch), then DRL_TPU_URING ("1"/"on" → uring,
// "sqpoll"/"2" → uring+SQPOLL). Default: epoll (the portable lane).
int uring_mode_from_env(void) {
  const char* m = std::getenv("DRL_TPU_URING");
  if (m == nullptr || *m == '\0') return kUringOff;
  std::string v(m);
  if (v == "0" || v == "off") return kUringOff;
  if (v == "2" || v == "sqpoll") return kUringSqpoll;
  return kUringOn;
}

}  // namespace

extern "C" {

void* fe_start_sharded2(const char* host, int port, int max_batch,
                        int deadline_us, int require_auth, int nshards,
                        int pin_cpus, int uring_mode) {
  if (nshards < 1) nshards = 1;
  if (nshards > kMaxShards) nshards = kMaxShards;
  // The operator kill switch trumps an explicit request from Python.
  bool uring_killed = false;
  {
    const char* no = std::getenv("DRL_TPU_NO_URING");
    if (no != nullptr && *no != '\0' && std::string(no) != "0") {
      uring_killed = uring_mode != kUringOff;
      uring_mode = kUringOff;
    }
  }
  if (uring_mode != kUringOff && uring_mode != kUringOn &&
      uring_mode != kUringSqpoll) {
    uring_mode = kUringOff;
  }
  Frontend* fe = new Frontend();
  fe->uring_mode = uring_mode;
  fe->nshards = nshards;
  fe->max_batch = size_t(max_batch > 0 ? max_batch : 4096);
  fe->deadline_ns = uint64_t(deadline_us > 0 ? deadline_us : 300) * 1000ull;
  fe->require_auth = require_auth != 0;

  sockaddr_in addr{};
  addr.sin_family = AF_INET;
  addr.sin_port = htons(uint16_t(port));
  bool bad_host = inet_pton(AF_INET, host, &addr.sin_addr) != 1;
  bool failed = bad_host;
  for (int i = 0; i < nshards && !failed; i++) {
    Shard* sh = new Shard();
    sh->owner = fe;
    sh->index = i;
    sh->max_batch = fe->max_batch;
    sh->deadline_ns = fe->deadline_ns;
    sh->require_auth = fe->require_auth;
    fe->shards.push_back(sh);
    sh->listen_fd = socket(AF_INET, SOCK_STREAM | SOCK_NONBLOCK, 0);
    if (sh->listen_fd < 0) {
      failed = true;
      break;
    }
    int one = 1;
    setsockopt(sh->listen_fd, SOL_SOCKET, SO_REUSEADDR, &one, sizeof one);
    if (nshards > 1 &&
        setsockopt(sh->listen_fd, SOL_SOCKET, SO_REUSEPORT, &one,
                   sizeof one) < 0) {
      // SO_REUSEPORT must be set on EVERY listener before bind (the
      // first included — later binds can only join a reuseport group
      // the first opted into). The kernel then hashes each incoming
      // connection's 4-tuple across the group: accept balancing with
      // no dispatch thread. Single-shard keeps the round-10 posture
      // (no REUSEPORT), so `fe_start` behavior is bit-identical.
      failed = true;
      break;
    }
    if (bind(sh->listen_fd, reinterpret_cast<sockaddr*>(&addr),
             sizeof addr) < 0 ||
        listen(sh->listen_fd, 512) < 0) {
      failed = true;
      break;
    }
    if (i == 0) {
      // Port 0 resolves on the first bind; `addr` then carries the
      // resolved port so the sibling shards join the same group.
      socklen_t alen = sizeof addr;
      getsockname(sh->listen_fd, reinterpret_cast<sockaddr*>(&addr),
                  &alen);
      fe->port = ntohs(addr.sin_port);
    }
    sh->epfd = epoll_create1(0);
    sh->evfd = eventfd(0, EFD_NONBLOCK);
    sh->tfd = timerfd_create(CLOCK_MONOTONIC, TFD_NONBLOCK);
    epoll_event ev{};
    ev.events = EPOLLIN;
    ev.data.u64 = 0;
    epoll_ctl(sh->epfd, EPOLL_CTL_ADD, sh->listen_fd, &ev);
    ev.data.u64 = 1;
    epoll_ctl(sh->epfd, EPOLL_CTL_ADD, sh->evfd, &ev);
    ev.data.u64 = 2;
    epoll_ctl(sh->epfd, EPOLL_CTL_ADD, sh->tfd, &ev);
  }
  if (failed) {
    for (Shard* sh : fe->shards) {
      if (sh->listen_fd >= 0) ::close(sh->listen_fd);
      if (sh->epfd >= 0) ::close(sh->epfd);
      if (sh->evfd >= 0) ::close(sh->evfd);
      if (sh->tfd >= 0) ::close(sh->tfd);
      delete sh;
    }
    delete fe;
    return nullptr;
  }
  for (int i = 0; i < nshards; i++) fe->t0parts.push_back(new T0Part());
  // Optional affinity: shard i -> the i-th CPU of the set this process
  // is ALLOWED to run on (taskset/numactl/cgroup cpusets shrink it —
  // absolute CPU ids would silently fail pthread_setaffinity_np under
  // exactly the NUMA workflow docs/OPERATIONS.md par.12 recommends).
  std::vector<int> allowed;
  if (pin_cpus != 0) {
    cpu_set_t mask;
    CPU_ZERO(&mask);
    if (sched_getaffinity(0, sizeof mask, &mask) == 0) {
      for (int c = 0; c < CPU_SETSIZE; c++) {
        if (CPU_ISSET(c, &mask)) allowed.push_back(c);
      }
    }
  }
  for (int i = 0; i < nshards; i++) {
    Shard* sh = fe->shards[size_t(i)];
    if (uring_mode != kUringOff) {
      // Per-shard graceful fallback: a shard the kernel (or seccomp)
      // refuses runs the epoll loop, records why in uring_reason, and
      // serves identically — availability over transport.
      uring_setup_shard(sh, uring_mode == kUringSqpoll);
    } else if (uring_killed) {
      sh->uring_reason = "disabled by DRL_TPU_NO_URING";
    }
    sh->io = std::thread(sh->uring ? uring_loop : io_loop, sh);
    if (!allowed.empty()) {
      cpu_set_t cpus;
      CPU_ZERO(&cpus);
      CPU_SET(allowed[size_t(i) % allowed.size()], &cpus);
      pthread_setaffinity_np(sh->io.native_handle(), sizeof cpus, &cpus);
    }
  }
  return fe;
}

void* fe_start_sharded(const char* host, int port, int max_batch,
                       int deadline_us, int require_auth, int nshards,
                       int pin_cpus) {
  // Round-11 compatibility entry: transport comes from the environment
  // (DRL_TPU_URING / DRL_TPU_NO_URING), defaulting to epoll.
  return fe_start_sharded2(host, port, max_batch, deadline_us,
                           require_auth, nshards, pin_cpus,
                           uring_mode_from_env());
}

void* fe_start(const char* host, int port, int max_batch, int deadline_us,
               int require_auth) {
  // Single-shard compatibility entry (an older Python half calls only
  // this): one listener, no SO_REUSEPORT — the round-10 behavior.
  return fe_start_sharded(host, port, max_batch, deadline_us,
                          require_auth, 1, 0);
}

int fe_shard_count(void* h) { return owner_of(h)->nshards; }

// Per-shard sub-handle, valid for every fe_* entry point: fe_wait /
// fe_batch_* / fe_bulk_* / fe_send / fe_complete address per-shard
// state (each Python pump thread drives exactly one shard), and the
// stats/harvest entries give the per-shard breakdown with it where the
// Frontend handle gives the whole-node merge.
void* fe_shard(void* h, int index) {
  Frontend* fe = owner_of(h);
  if (index < 0 || index >= fe->nshards) return nullptr;
  return fe->shards[size_t(index)];
}

int fe_port(void* h) { return owner_of(h)->port; }

// Wait for work: 1 = batch ready (use fe_batch_*), 2 = passthrough frame
// (use fe_pt_*), 3 = bulk residue job (use fe_bulk_*), 0 = timeout,
// -1 = stopping. Per-shard: each pump thread waits on its own shard.
int fe_wait(void* h, int timeout_ms) {
  Shard* sh = shard_of(h);
  std::unique_lock<FeMutex> lk(sh->mu);
  sh->pump_waiting = true;
  bool got = sh->cv.wait_for(lk, std::chrono::milliseconds(timeout_ms), [&] {
    return sh->owner->stopping.load() || !sh->pt.empty() ||
           !sh->ready.empty() || !sh->bulk_ready.empty();
  });
  sh->pump_waiting = false;
  if (sh->owner->stopping.load()) return -1;
  if (!got) return 0;
  // Control ops first so STATS/HELLO can't starve behind a hot-batch
  // stream; all queues drain promptly because the pump never blocks.
  if (!sh->pt.empty()) {
    sh->cur_pt = std::move(sh->pt.front());
    sh->pt.pop_front();
    return 2;
  }
  if (!sh->ready.empty()) {
    Batch b = std::move(sh->ready.front());
    sh->ready.pop_front();
    sh->cur_batch_id = b.id;
    sh->inflight.emplace(b.id, std::move(b));
    return 1;
  }
  sh->cur_bulk_id = sh->bulk_ready.front();
  sh->bulk_ready.pop_front();
  return 3;
}

long long fe_batch_id(void* h) { return shard_of(h)->cur_batch_id; }

int fe_batch_n(void* h) {
  Shard* sh = shard_of(h);
  std::lock_guard<FeMutex> lk(sh->mu);
  auto it = sh->inflight.find(sh->cur_batch_id);
  return it == sh->inflight.end() ? 0 : int(it->second.items.size());
}

long long fe_batch_key_bytes(void* h) {
  Shard* sh = shard_of(h);
  std::lock_guard<FeMutex> lk(sh->mu);
  auto it = sh->inflight.find(sh->cur_batch_id);
  if (it == sh->inflight.end()) return 0;
  long long total = 0;
  for (const Item& item : it->second.items) total += (long long)item.key.size();
  return total;
}

// Copy the current batch out as parallel arrays (key blob is the
// concatenation; klens delimit it). Caller allocates via numpy.
void fe_batch_copy(void* h, char* key_blob, int32_t* klens, int32_t* counts,
                   uint8_t* ops, uint32_t* seqs, uint64_t* conn_ids,
                   double* as, double* bs) {
  Shard* sh = shard_of(h);
  std::lock_guard<FeMutex> lk(sh->mu);
  auto it = sh->inflight.find(sh->cur_batch_id);
  if (it == sh->inflight.end()) return;
  size_t off = 0;
  size_t i = 0;
  for (const Item& item : it->second.items) {
    std::memcpy(key_blob + off, item.key.data(), item.key.size());
    off += item.key.size();
    klens[i] = int32_t(item.key.size());
    counts[i] = item.count;
    ops[i] = item.op;
    seqs[i] = item.seq;
    conn_ids[i] = item.conn_id;
    as[i] = item.a;
    bs[i] = item.b;
    i++;
  }
}

// Count the current batch's traced rows — the one-int gate the pump
// checks before paying fe_batch_traces' array allocations (at 1% head
// sampling ~99% of batches carry none).
int fe_batch_traced_n(void* h) {
  Shard* sh = shard_of(h);
  std::lock_guard<FeMutex> lk(sh->mu);
  auto it = sh->inflight.find(sh->cur_batch_id);
  if (it == sh->inflight.end()) return 0;
  int n = 0;
  for (const Item& item : it->second.items) n += item.tr_flags & 1;
  return n;
}

// The current batch's cut stamp (now_ns: CLOCK_MONOTONIC, the clock
// Python's time.perf_counter reads): where the pump -> loop hand-off
// stage (Python's fe_handoff) starts. Same call window as
// fe_batch_copy; 0 when there is no current batch.
unsigned long long fe_batch_t_cut_ns(void* h) {
  Shard* sh = shard_of(h);
  std::lock_guard<FeMutex> lk(sh->mu);
  auto it = sh->inflight.find(sh->cur_batch_id);
  return it == sh->inflight.end() ? 0ull : it->second.t_flush_ns;
}

// Copy the current batch's trace contexts as parallel arrays (zeros /
// flag bit 0 clear for untraced rows). Same contract as fe_batch_copy:
// call between fe_wait returning 1 and fe_complete/fe_fail.
void fe_batch_traces(void* h, uint64_t* hi, uint64_t* lo, uint64_t* parent,
                     uint8_t* flags) {
  Shard* sh = shard_of(h);
  std::lock_guard<FeMutex> lk(sh->mu);
  auto it = sh->inflight.find(sh->cur_batch_id);
  if (it == sh->inflight.end()) return;
  size_t i = 0;
  for (const Item& item : it->second.items) {
    hi[i] = item.tr_hi;
    lo[i] = item.tr_lo;
    parent[i] = item.tr_parent;
    flags[i] = item.tr_flags;
    i++;
  }
}

// Drain up to `max` traced tier-0 local decisions (6 u64 each: hi, lo,
// parent, start_ns, dur_ns, meta). Returns the record count. A Frontend
// handle drains every shard's ring (rotating so a loud shard cannot
// starve the others); a shard handle drains just that shard.
int fe_trace_harvest(void* h, uint64_t* out, int max) {
  Frontend* fe = owner_of(h);
  std::vector<Shard*> shards = shards_of(h);
  size_t nsh = shards.size();
  size_t start = (as_frontend(h) != nullptr && nsh > 1)
                     ? fe->trace_shard % nsh
                     : 0;
  int n = 0;
  for (size_t si = 0; si < nsh && n < max; si++) {
    Shard* sh = shards[(start + si) % nsh];
    std::lock_guard<FeMutex> lk(sh->mu);
    while (n < max && !sh->trace_ring.empty()) {
      const TraceRec& r = sh->trace_ring.front();
      out[0] = r.hi;
      out[1] = r.lo;
      out[2] = r.parent;
      out[3] = r.start_ns;
      out[4] = r.dur_ns;
      out[5] = r.meta;
      out += 6;
      n++;
      sh->trace_ring.pop_front();
    }
  }
  if (as_frontend(h) != nullptr && nsh > 1) {
    fe->trace_shard = (start + 1) % nsh;
  }
  return n;
}

// Complete a batch: encode one RESP_DECISION per item, write natively,
// record serving latency (arrival -> completion, the same span the
// asyncio server's histogram covers). granted[i] == kRowSkip marks a
// row Python already answered via fe_send (per-row placement error on
// the batch lane — MOVED / handoff deferral); it gets no decision
// reply, no tier-0 install, and no second requests_served count.
constexpr uint8_t kRowSkip = 2;

void fe_complete(void* h, long long batch_id, const uint8_t* granted,
                 const double* remaining) {
  Shard* sh = shard_of(h);
  std::lock_guard<FeMutex> lk(sh->mu);
  auto it = sh->inflight.find(batch_id);
  if (it == sh->inflight.end()) return;
  uint64_t t = now_ns();
  uint64_t t_flush = it->second.t_flush_ns;
  double exec_s = double(t - t_flush) * 1e-9;
  bool t0_on = sh->owner->t0_enabled.load(std::memory_order_relaxed);
  size_t i = 0;
  for (const Item& item : it->second.items) {
    if (granted[i] == kRowSkip) {
      i++;
      continue;
    }
    std::string resp =
        encode_decision(item.seq, granted[i] != 0, remaining[i]);
    auto itc = sh->conns.find(item.conn_id);
    if (itc != sh->conns.end()) {
      send_to_conn(sh, itc->second, resp.data(), resp.size());
    }
    if (t0_on && item.op == OP_ACQUIRE && granted[i] != 0) {
      // Every granted fall-through decision is an authoritative balance
      // observation: seed/refresh the key's tier-0 replica (in its
      // OWNER partition) from it — sized for the grant's token cost
      // (see t0_install).
      t0_install(t0_slice(sh), item.key, item.a, item.b, remaining[i],
                 t, double(item.count));
    }
    hist_record(sh, double(t - item.t_ns) * 1e-9);
    stage_record(sh, 0, double(t_flush - item.t_ns) * 1e-9);  // queue
    stage_record(sh, 1, exec_s);  // Python dispatch + store + kernel
    sh->requests_served++;
    i++;
  }
  sh->inflight.erase(it);
  maybe_flush_after_complete(sh);
  if (sh->uring) uring_submit(sh);  // one enter for the whole batch
}

// Fail a batch (store raised): every item gets a routable error reply.
void fe_fail(void* h, long long batch_id, const char* msg) {
  Shard* sh = shard_of(h);
  std::lock_guard<FeMutex> lk(sh->mu);
  auto it = sh->inflight.find(batch_id);
  if (it == sh->inflight.end()) return;
  uint64_t t = now_ns();
  uint64_t t_flush = it->second.t_flush_ns;
  double exec_s = double(t - t_flush) * 1e-9;
  for (const Item& item : it->second.items) {
    std::string resp = encode_error(item.seq, msg);
    auto itc = sh->conns.find(item.conn_id);
    if (itc != sh->conns.end()) {
      send_to_conn(sh, itc->second, resp.data(), resp.size());
    }
    hist_record(sh, double(t - item.t_ns) * 1e-9);
    stage_record(sh, 0, double(t_flush - item.t_ns) * 1e-9);
    stage_record(sh, 1, exec_s);
    sh->requests_served++;
  }
  sh->inflight.erase(it);
  maybe_flush_after_complete(sh);
  if (sh->uring) uring_submit(sh);
}

long long fe_pt_conn(void* h) {
  return (long long)shard_of(h)->cur_pt.conn_id;
}

int fe_pt_len(void* h) { return int(shard_of(h)->cur_pt.frame.size()); }

void fe_pt_copy(void* h, char* buf) {
  Shard* sh = shard_of(h);
  std::memcpy(buf, sh->cur_pt.frame.data(), sh->cur_pt.frame.size());
}

// Feature probe: this binary's fe_complete honors the kRowSkip
// sentinel. Python falls back to deny-only gating without it (a stale
// .so must never read the sentinel as "granted").
int fe_has_row_skip(void) { return 1; }

// Send a pre-encoded reply frame (passthrough responses).
void fe_send(void* h, uint64_t conn_id, const char* data, int len) {
  Shard* sh = shard_of(h);
  std::lock_guard<FeMutex> lk(sh->mu);
  auto itc = sh->conns.find(conn_id);
  if (itc == sh->conns.end()) return;
  send_to_conn(sh, itc->second, data, size_t(len));
  sh->requests_served++;
  if (sh->uring) uring_submit(sh);
}

void fe_set_authed(void* h, uint64_t conn_id, int authed) {
  Shard* sh = shard_of(h);
  std::lock_guard<FeMutex> lk(sh->mu);
  auto itc = sh->conns.find(conn_id);
  if (itc == sh->conns.end()) return;
  Conn* c = itc->second;
  c->auth_pending = false;
  c->authed = authed != 0;
  if (!c->authed) return;  // failure path: Python sends the error and
                           // closes via fe_close_conn; held frames die
                           // with the connection
  // Replay frames the client pipelined behind its HELLO, in order.
  std::vector<std::string> held = std::move(c->held);
  c->held.clear();
  c->held_bytes = 0;
  bool ok = true;
  for (const std::string& f : held) {
    if (!handle_frame(sh, c,
                      reinterpret_cast<const uint8_t*>(f.data()),
                      f.size())) {
      ok = false;
      break;
    }
  }
  if (!ok) {
    if (!c->out.empty()) {
      c->closing = true;  // drain the error reply first
      flush_out(sh, c);
    } else {
      close_conn(sh, c);
    }
  } else {
    flush_queued(sh, c);  // replayed tier-0/PING replies
  }
  if (sh->uring) uring_submit(sh);
  // Replayed hot items joined `pending` from this (loop) thread: wake
  // the IO thread so its flush/deadline evaluation sees them.
  wake_io(sh);
}

void fe_close_conn(void* h, uint64_t conn_id) {
  Shard* sh = shard_of(h);
  std::lock_guard<FeMutex> lk(sh->mu);
  auto itc = sh->conns.find(conn_id);
  if (itc == sh->conns.end()) return;
  Conn* c = itc->second;
  if (c->out.empty() && (!sh->uring || (c->wbuf_off >= c->wbuf.size() &&
                                        !c->send_inflight))) {
    close_conn(sh, c);
  } else {
    c->closing = true;  // drain the goodbye (e.g. auth-failed error) first
    if (sh->uring) uring_arm_send(sh, c);
  }
  if (sh->uring) uring_submit(sh);
}

// Whole-node counters with a Frontend handle (the sum across shards);
// one shard's slice with a shard handle — the OP_STATS shards=[...]
// breakdown.
void fe_counts(void* h, long long* requests, long long* connections,
               long long* batches) {
  *requests = *connections = *batches = 0;
  for (Shard* sh : shards_of(h)) {
    std::lock_guard<FeMutex> lk(sh->mu);
    *requests += sh->requests_served;
    *connections += sh->connections_served;
    *batches += sh->batches_flushed;
  }
}

long long fe_hist(void* h, uint64_t* counts) {
  std::memset(counts, 0, sizeof(uint64_t) * kHistBuckets);
  long long total = 0;
  for (Shard* sh : shards_of(h)) {
    std::lock_guard<FeMutex> lk(sh->mu);
    for (int b = 0; b < kHistBuckets; b++) counts[b] += sh->hist[b];
    total += sh->hist_total;
  }
  return total;
}

// Per-stage latency histograms (same 82-bucket convention as fe_hist).
// stage: 0 = serving (arrival -> completion, the fe_hist span), 1 =
// queue (arrival -> batch cut), 2 = exec (batch cut -> completion).
// Sums across shards for a Frontend handle (log-bucket histograms are
// closed under addition, so merged quantiles read identically to a
// single shard's). Copies bucket counts into `counts`, writes the
// running sum of seconds into `sum_out`, returns the sample total.
// Unknown stage returns -1.
long long fe_stage_hist(void* h, int stage, uint64_t* counts,
                        double* sum_out) {
  if (stage != 0 && (stage - 1 < 0 || stage - 1 >= Shard::kStages)) {
    return -1;
  }
  std::memset(counts, 0, sizeof(uint64_t) * kHistBuckets);
  *sum_out = 0.0;
  long long total = 0;
  for (Shard* sh : shards_of(h)) {
    std::lock_guard<FeMutex> lk(sh->mu);
    if (stage == 0) {
      for (int b = 0; b < kHistBuckets; b++) counts[b] += sh->hist[b];
      *sum_out += sh->hist_sum;
      total += sh->hist_total;
    } else {
      int s = stage - 1;
      for (int b = 0; b < kHistBuckets; b++) {
        counts[b] += sh->stage_hist[s][b];
      }
      *sum_out += sh->stage_sum[s];
      total += sh->stage_total[s];
    }
  }
  return total;
}

void fe_hist_reset(void* h) {
  for (Shard* sh : shards_of(h)) {
    std::lock_guard<FeMutex> lk(sh->mu);
    std::memset(sh->hist, 0, sizeof sh->hist);
    sh->hist_total = 0;
    sh->hist_sum = 0.0;
    std::memset(sh->stage_hist, 0, sizeof sh->stage_hist);
    std::memset(sh->stage_total, 0, sizeof sh->stage_total);
    for (int s = 0; s < Shard::kStages; s++) sh->stage_sum[s] = 0.0;
  }
}

void fe_stop(void* h) {
  Frontend* fe = owner_of(h);
  fe->stopping.store(true);
  for (Shard* sh : fe->shards) {
    wake_io(sh);
    {
      std::lock_guard<FeMutex> lk(sh->mu);
      sh->cv.notify_all();
    }
    if (sh->io.joinable()) sh->io.join();
    if (sh->ring != nullptr) {
      // After the join no op can complete into shard memory; closing
      // the ring fd also drops the registered-file references.
      uring_free_ring(sh->ring);
      sh->ring = nullptr;
      sh->uring = false;
    }
    ::close(sh->listen_fd);
    ::close(sh->epfd);
    ::close(sh->evfd);
    ::close(sh->tfd);
  }
}

void fe_free(void* h) {
  Frontend* fe = owner_of(h);
  for (Shard* sh : fe->shards) {
    if (sh->ring != nullptr) uring_free_ring(sh->ring);  // stop-less free
    delete sh;
  }
  for (T0Part* part : fe->t0parts) delete part;
  delete fe;
}

// ---------------------------------------------------------------------
// io_uring transport ABI (round 16). Feature detection mirrors the
// shard ABI's: utils/native.py probes these symbols and falls back to
// fe_start_sharded (epoll or env-resolved) when they are absent.
// ---------------------------------------------------------------------

// Process-wide availability: 1 when the kernel offers the 5.19+ feature
// level this transport needs AND no env/sanitizer gate forbids it.
int fe_uring_available(void) {
  std::string r;
  return uring_probe(&r);
}

// Availability plus the human-readable reason (for `--probe` output and
// the loud fallback log line). Returns the same 0/1 as above; writes a
// NUL-terminated reason (empty on success) into buf.
int fe_uring_probe(char* buf, int len) {
  std::string r;
  int ok = uring_probe(&r);
  if (ok != 0 && r.empty()) {
    r = "io_uring available (5.19+ feature level)";
  }
  if (buf != nullptr && len > 0) {
    size_t n = std::min(size_t(len - 1), r.size());
    std::memcpy(buf, r.data(), n);
    buf[n] = '\0';
  }
  return ok;
}

// How many of the node's shards are actually serving on uring (the
// request is per-node; refusal is per-shard).
int fe_uring_shards(void* h) {
  int n = 0;
  for (Shard* sh : owner_of(h)->shards) n += sh->uring ? 1 : 0;
  return n;
}

// Per-shard transport status: returns 1 (uring) / 0 (epoll) / -1 (bad
// index) and writes the shard's fallback reason (empty when it never
// fell back) into buf.
int fe_uring_reason(void* h, int shard, char* buf, int len) {
  Frontend* fe = owner_of(h);
  if (shard < 0 || shard >= fe->nshards) return -1;
  Shard* sh = fe->shards[size_t(shard)];
  if (buf != nullptr && len > 0) {
    size_t n = std::min(size_t(len - 1), sh->uring_reason.size());
    std::memcpy(buf, sh->uring_reason.data(), n);
    buf[n] = '\0';
  }
  return sh->uring ? 1 : 0;
}

// out[8]: shards on uring, shards on SQPOLL, io_uring_enter calls,
// SQEs submitted, CQEs completed, data-plane syscalls (both
// transports — the syscalls/frame numerator), shards that fell back
// after an explicit uring request, reserved. Frontend OR shard handle.
void fe_uring_counts(void* h, long long* out) {
  for (int i = 0; i < 8; i++) out[i] = 0;
  for (Shard* sh : shards_of(h)) {
    if (sh->uring) out[0]++;
    if (sh->uring_sqpoll) out[1]++;
    if (sh->ring != nullptr) {
      out[2] += sh->ring->enters.load(std::memory_order_relaxed);
      out[3] += sh->ring->sqes_submitted.load(std::memory_order_relaxed);
      out[4] += sh->ring->cqes_seen.load(std::memory_order_relaxed);
    }
    out[5] += sh->io_syscalls.load(std::memory_order_relaxed);
    // A fallback is any shard serving epoll WITH a recorded reason —
    // that covers both probe/setup refusals and the DRL_TPU_NO_URING
    // coercion (which rewrites fe->uring_mode, so the mode alone can't
    // tell). uring_reason is written once before the IO threads start.
    if (!sh->uring && !sh->uring_reason.empty()) out[6]++;
  }
}

// ---------------------------------------------------------------------
// Tier-0 admission cache ABI (see the T0Part block above). The table is
// partitioned by key hash across the shards; all calls below take
// partition mutexes only (never a shard's connection mutex), and the
// harvest/ack pair is driven by the ONE Python sync pump
// (runtime/native_frontend.py _t0_sync_loop) regardless of shard count
// — a single reconciliation stream, a single epsilon envelope.
// ---------------------------------------------------------------------

// Enable tier-0 with a bounded replica table. `slots` sizes EACH
// shard's slice (rounded up to a power of two): any shard can see any
// key, so every slice needs full-keyspace capacity — table memory is
// nshards × slots × (entry + key). Budgets are divided by the shard
// count inside t0_budget_of, so the summed per-shard headroom stays
// inside the flat single-shard envelope (see T0Part). Returns the
// total slot count actually allocated (the Python pump sizes harvest
// buffers from it — a harvest can return one row per shard per key).
int fe_t0_configure(void* h, int slots, double fraction, double min_budget,
                    double max_budget, int stale_ms, int ttl_ms) {
  Frontend* fe = owner_of(h);
  size_t want = size_t(slots > 0 ? slots : 4096);
  size_t per = 1;
  while (per < want) per <<= 1;
  T0Config cfg;
  cfg.mask = per - 1;
  cfg.split = double(fe->nshards);
  cfg.fraction = fraction > 0 ? fraction : 0.5;
  cfg.min_budget = min_budget > 0 ? min_budget : 1.0;
  cfg.max_budget = max_budget > 0 ? max_budget : 1048576.0;
  cfg.stale_ns = uint64_t(stale_ms > 0 ? stale_ms : 1000) * 1000000ull;
  cfg.ttl_ns = uint64_t(ttl_ms > 0 ? ttl_ms : 30000) * 1000000ull;
  for (T0Part* part : fe->t0parts) {
    std::lock_guard<T0SpinMutex> lk(part->mu);
    part->cfg = cfg;
    part->tab.assign(per, T0Entry{});
    part->scan = 0;
  }
  fe->t0_enabled.store(true, std::memory_order_release);
  return int(per * size_t(fe->nshards));
}

// Drain accumulated local grants: copies up to max_n (key, amount, cap,
// rate) rows out (key_blob concatenated, klens delimiting) and zeroes
// each entry's pending. Entries that do not fit stay pending for the
// next round — partitions rotate and each partition's scan resumes
// from its own cursor, so an overflowing round cannot starve either a
// partition or the tail of one partition's table. Idle pending-free
// entries are TTL-evicted in the same pass. Returns the row count.
int fe_t0_harvest(void* h, char* key_blob, int blob_cap, int32_t* klens,
                  double* amounts, double* caps, double* rates, int max_n) {
  Frontend* fe = owner_of(h);
  std::vector<T0Part*> parts = t0parts_of(h);
  if (parts.empty()) return 0;
  uint64_t now = now_ns();
  size_t nparts = parts.size();
  bool rotate = as_frontend(h) != nullptr && nparts > 1;
  size_t start = rotate ? fe->harvest_part % nparts : 0;
  int n = 0;
  size_t off = 0;
  bool full = false;
  for (size_t pi = 0; pi < nparts && !full; pi++) {
    T0Part* part = parts[(start + pi) % nparts];
    std::lock_guard<T0SpinMutex> lk(part->mu);
    size_t total = part->tab.size();
    if (total == 0) continue;
    size_t i = part->scan;
    for (size_t scanned = 0; scanned < total; scanned++, i++) {
      T0Entry& e = part->tab[i % total];
      if (!e.live) continue;
      if (e.pending > 0.0) {
        if (n >= max_n || off + e.key.size() > size_t(blob_cap)) {
          full = true;
          break;
        }
        std::memcpy(key_blob + off, e.key.data(), e.key.size());
        off += e.key.size();
        klens[n] = int32_t(e.key.size());
        amounts[n] = e.pending;
        caps[n] = e.cap;
        rates[n] = e.rate;
        e.pending = 0.0;
        n++;
      } else if (now - e.last_touch_ns > part->cfg.ttl_ns) {
        e.live = false;
        part->evictions++;
      }
    }
    part->scan = i % total;
    if (full && rotate) fe->harvest_part = (start + pi) % nparts;
  }
  if (!full && rotate) fe->harvest_part = (start + 1) % nparts;
  return n;
}

// Complete a sync round: install the fresh authoritative balance into
// EVERY shard's replica of each harvested key (the Python pump merges
// per-shard harvest rows by key before the debit, so each key is
// acked once with the one store balance) and recompute the per-shard
// budget shares. Grants made after the harvest (still in `pending`)
// remain outstanding against the new envelope; the drained portion is
// reflected in the balance itself.
void fe_t0_ack(void* h, const char* key_blob, const int32_t* klens,
               const double* caps, const double* rates,
               const double* remainings, int n) {
  std::vector<T0Part*> parts = t0parts_of(h);
  if (parts.empty()) return;
  uint64_t now = now_ns();
  for (T0Part* part : parts) {
    std::lock_guard<T0SpinMutex> lk(part->mu);
    size_t off = 0;
    for (int i = 0; i < n; i++) {
      std::string_view key(key_blob + off, size_t(klens[i]));
      off += size_t(klens[i]);
      T0Entry* e = t0_find(part, key, t0_hash(key), caps[i], rates[i]);
      if (e == nullptr) continue;  // not hosted here / evicted mid-sync
      e->last_remaining = remainings[i];
      e->admitted = e->pending;
      e->budget = t0_budget_of(
          part->cfg, std::max(remainings[i] - e->admitted, 0.0));
      e->last_ack_ns = now;
      e->last_touch_ns = now;
    }
  }
}

// Live config mutation (round 7): kill every replica of one retired
// (cap, rate) config and hand back its un-harvested local grants —
// [key_blob/klens/amounts rows, like fe_t0_harvest] — so the sync pump
// debits them through the REPLACEMENT config. Round 11: the sweep fans
// out to EVERY partition under ONE combined critical section — all
// partition locks are taken up front (index order; this is the only
// multi-partition lock site, so there is no ordering partner to
// deadlock with — and that is now a CHECKED contract, not a comment:
// drl-verify's lock-order analyzer (tools/drl_verify/lockorder.py,
// rule slice-sweep-order) fails `make check` on a reversed sweep, a
// second multi-slice section, or any nested same-class acquisition
// outside this one). A config retired on shard 0 but still live on shard
// 3 would be a double-admit window; with the combined section no grant
// can land on ANY partition between the harvest and the kill. Without
// the kill, stale frames would keep being admitted (or confidently
// denied) against a table nobody serves from anymore; dead entries
// make them fall through to the batch lane's routable "config moved"
// error. Returns the number of rows written (entries with pending >
// 0); every matching entry is dead on return regardless.
int fe_t0_retire(void* h, double cap, double rate, char* key_blob,
                 int blob_cap, int32_t* klens, double* amounts,
                 int max_keys) {
  std::vector<T0Part*> parts = t0parts_of(h);
  std::vector<std::unique_lock<T0SpinMutex>> locks;
  locks.reserve(parts.size());
  for (T0Part* part : parts) locks.emplace_back(part->mu);
  int n = 0;
  int off = 0;
  for (T0Part* part : parts) {
    for (T0Entry& e : part->tab) {
      if (!e.live || e.cap != cap || e.rate != rate) continue;
      if (e.pending > 0.0 && n < max_keys &&
          off + int(e.key.size()) <= blob_cap) {
        std::memcpy(key_blob + off, e.key.data(), e.key.size());
        klens[n] = int32_t(e.key.size());
        amounts[n] = e.pending;
        off += int(e.key.size());
        n++;
      }
      e.live = false;
      e.pending = 0.0;
      part->evictions++;
    }
  }
  return n;
}

// out[6]: hits, local denies, misses, installs, evictions, live
// entries. Frontend handle = summed across partitions (the whole-node
// gauges); shard handle = that shard's own partition.
void fe_t0_counts(void* h, long long* out) {
  for (int i = 0; i < 6; i++) out[i] = 0;
  for (T0Part* part : t0parts_of(h)) {
    std::lock_guard<T0SpinMutex> lk(part->mu);
    long long live = 0;
    for (const T0Entry& e : part->tab) live += e.live ? 1 : 0;
    out[0] += part->hits;
    out[1] += part->local_denies;
    out[2] += part->misses;
    out[3] += part->installs;
    out[4] += part->evictions;
    out[5] += live;
  }
}

// Per-slice ε-consumption counters (round 18, the conservation audit
// plane): out[i] = cumulative tokens granted locally by slice i.
// Frontend handle = every shard's slice in shard order (the whole-node
// per-slice breakdown); shard handle = that shard's own slice only.
// Returns the number of slices written (≤ max_parts). A separate
// export rather than a widened fe_t0_counts: stale Python halves keep
// passing 6-element arrays to fe_t0_counts, and the binding layer
// feature-detects this symbol exactly like fe_t0_retire.
int fe_t0_eps(void* h, double* out, int max_parts) {
  int n = 0;
  for (T0Part* part : t0parts_of(h)) {
    if (n >= max_parts) break;
    std::lock_guard<T0SpinMutex> lk(part->mu);
    out[n++] = part->grant_tokens;
  }
  return n;
}

// ---------------------------------------------------------------------
// Native bulk lane ABI (round 8). fe_bulk_configure arms it (default
// off so a new binary under an older pump keeps the round-7
// passthrough behavior); fe_wait returns 3 when a residue job is
// ready; fe_bulk_meta / fe_bulk_ptrs expose the CURRENT job (same
// call-window contract as fe_batch_*: between fe_wait returning 3 and
// the matching complete/discard/fail); fe_bulk_complete merges
// Python's residue verdicts, encodes RESP_BULK, and answers the
// client. The ptrs stay valid until the job is erased — Python's
// KeyBlob views read them in place (zero copy, zero UTF-8 decode).
// Jobs are per-shard state: the pump thread that pulled the job from
// fe_wait completes it against the same shard handle.
// ---------------------------------------------------------------------

// Arm/disarm the lane on every shard of the handle — one call, all
// shards, so a frame arriving on shard 3 mid-configure can at worst
// see the OLD whole-lane mode, never a half-armed mix on its own
// shard.
int fe_bulk_configure(void* h, int enable, int t0_rows, int hot_feed) {
  for (Shard* sh : shards_of(h)) {
    std::lock_guard<FeMutex> lk(sh->mu);
    sh->bulk_native = enable != 0;
    sh->bulk_t0 = t0_rows != 0;
    sh->bulk_hot = hot_feed != 0;
  }
  return 1;
}

long long fe_bulk_id(void* h) { return shard_of(h)->cur_bulk_id; }

// u[11]: job id, conn id, seq, flags, n, blob bytes, residue rows,
// trace hi/lo/parent, trace flags. f[2]: a, b. Job id 0 = no job.
void fe_bulk_meta(void* h, unsigned long long* u, double* f) {
  Shard* sh = shard_of(h);
  std::lock_guard<FeMutex> lk(sh->mu);
  auto it = sh->bulk_inflight.find(sh->cur_bulk_id);
  if (it == sh->bulk_inflight.end()) {
    u[0] = 0;
    return;
  }
  const BulkJob& j = it->second;
  u[0] = (unsigned long long)j.id;
  u[1] = j.conn_id;
  u[2] = j.seq;
  u[3] = j.flags;
  u[4] = j.n;
  u[5] = j.blob.size();
  u[6] = j.residue.size();
  u[7] = j.tr_hi;
  u[8] = j.tr_lo;
  u[9] = j.tr_parent;
  u[10] = j.tr_flags;
  f[0] = j.a;
  f[1] = j.b;
}

// The current bulk job's arrival stamp (now_ns, as fe_batch_t_cut_ns):
// where its hand-off to the loop starts. Same call window as
// fe_bulk_meta; 0 when there is no current job.
unsigned long long fe_bulk_t_ns(void* h) {
  Shard* sh = shard_of(h);
  std::lock_guard<FeMutex> lk(sh->mu);
  auto it = sh->bulk_inflight.find(sh->cur_bulk_id);
  return it == sh->bulk_inflight.end() ? 0ull : it->second.t_ns;
}

// ptrs[4]: key blob, offsets (i64[n+1]), counts (i64[n]), residue
// (i32[residue_n]) — addresses into the job, stable until it is erased.
void fe_bulk_ptrs(void* h, unsigned long long* ptrs) {
  Shard* sh = shard_of(h);
  std::lock_guard<FeMutex> lk(sh->mu);
  auto it = sh->bulk_inflight.find(sh->cur_bulk_id);
  if (it == sh->bulk_inflight.end()) {
    ptrs[0] = ptrs[1] = ptrs[2] = ptrs[3] = 0;
    return;
  }
  BulkJob& j = it->second;
  ptrs[0] = (unsigned long long)(uintptr_t)j.blob.data();
  ptrs[1] = (unsigned long long)(uintptr_t)j.offsets.data();
  ptrs[2] = (unsigned long long)(uintptr_t)j.counts.data();
  ptrs[3] = (unsigned long long)(uintptr_t)j.residue.data();
}

// Merge Python's residue verdicts (granted/remaining indexed in
// `residue` order), install replicas from granted fall-through rows
// (the bulk lane's mirror of fe_complete's scalar install), encode the
// RESP_BULK reply, and answer the client.
void fe_bulk_complete(void* h, long long job_id, const uint8_t* granted,
                      const double* remaining) {
  Shard* sh = shard_of(h);
  std::lock_guard<FeMutex> lk(sh->mu);
  auto it = sh->bulk_inflight.find(job_id);
  if (it == sh->bulk_inflight.end()) return;
  BulkJob& job = it->second;
  uint64_t t = now_ns();
  bool t0_on = sh->owner->t0_enabled.load(std::memory_order_relaxed);
  for (size_t r = 0; r < job.residue.size(); r++) {
    size_t i = size_t(job.residue[r]);
    job.verdict[i] = granted[r] ? 1 : 0;
    job.remaining[i] = float(remaining[r]);
    if (t0_on && sh->bulk_t0 && job.kind == BULK_KIND_BUCKET &&
        granted[r] && job.with_remaining && job.counts[i] > 0) {
      size_t klen = size_t(job.offsets[i + 1] - job.offsets[i]);
      if (klen <= kT0MaxKey) {
        t0_install(t0_slice(sh),
                   std::string(job.blob.data() + job.offsets[i], klen),
                   job.a, job.b, remaining[r], t,
                   double(job.counts[i]));
      }
    }
  }
  std::string resp = encode_bulk_reply(job.seq, job.with_remaining,
                                       job.n, job.verdict.data(),
                                       job.remaining.data());
  auto itc = sh->conns.find(job.conn_id);
  if (itc != sh->conns.end()) {
    send_to_conn(sh, itc->second, resp.data(), resp.size());
  }
  if (job.tr_flags & 1) {
    bool all = true;
    for (uint32_t i = 0; i < job.n; i++) all = all && job.verdict[i] == 1;
    trace_ring_push_raw(sh, job.tr_hi, job.tr_lo, job.tr_parent,
                        job.tr_flags, OP_ACQUIRE_MANY, all, job.t_ns, t);
  }
  hist_record(sh, double(t - job.t_ns) * 1e-9);
  sh->requests_served++;
  finish_bulk_job(sh, job_id);
  if (sh->uring) uring_submit(sh);
}

// Drop a job whose frame Python already answered wholesale via fe_send
// (frame-level gate errors / drain envelope — the kRowSkip posture,
// whole-frame edition). fe_send counted the request; this only records
// latency and un-parks chained successors.
void fe_bulk_discard(void* h, long long job_id) {
  Shard* sh = shard_of(h);
  std::lock_guard<FeMutex> lk(sh->mu);
  auto it = sh->bulk_inflight.find(job_id);
  if (it == sh->bulk_inflight.end()) return;
  hist_record(sh, double(now_ns() - it->second.t_ns) * 1e-9);
  finish_bulk_job(sh, job_id);
  if (sh->uring) uring_submit(sh);
}

// Fail a job (store raised): the frame gets one routable error reply.
void fe_bulk_fail(void* h, long long job_id, const char* msg) {
  Shard* sh = shard_of(h);
  std::lock_guard<FeMutex> lk(sh->mu);
  auto it = sh->bulk_inflight.find(job_id);
  if (it == sh->bulk_inflight.end()) return;
  BulkJob& job = it->second;
  std::string resp = encode_error(job.seq, msg);
  auto itc = sh->conns.find(job.conn_id);
  if (itc != sh->conns.end()) {
    send_to_conn(sh, itc->second, resp.data(), resp.size());
  }
  hist_record(sh, double(now_ns() - job.t_ns) * 1e-9);
  sh->requests_served++;
  finish_bulk_job(sh, job_id);
  if (sh->uring) uring_submit(sh);
}

// out[7]: frames, frames decided fully in C, rows, rows decided
// locally (tier-0 grant/deny), residue rows, locally granted permits
// (the amount the sync pump debits), hot-ring drops. Frontend handle =
// summed across shards; shard handle = that shard's slice.
void fe_bulk_counts(void* h, long long* out) {
  for (int i = 0; i < 7; i++) out[i] = 0;
  for (Shard* sh : shards_of(h)) {
    std::lock_guard<FeMutex> lk(sh->mu);
    out[0] += sh->bulk_frames;
    out[1] += sh->bulk_frames_local;
    out[2] += sh->bulk_rows;
    out[3] += sh->bulk_rows_local;
    out[4] += sh->bulk_rows_residue;
    out[5] += (long long)sh->bulk_permits_local;
    out[6] += sh->hot_dropped;
  }
}

// Drain up to max_n aggregated (key, weight) hot-key rows from the
// bulk lanes' rings (key_blob concatenated, klens delimiting) — the
// pump offers them to the heavy-hitter sketch. Each shard keeps its
// own ring; the ONE harvest pump drains them all (rotating), so the
// sketch — and therefore split_hot_keys — still sees whole-node ranks.
// Returns the row count.
int fe_hot_harvest(void* h, char* key_blob, int blob_cap, int32_t* klens,
                   double* weights, int max_n) {
  Frontend* fe = owner_of(h);
  std::vector<Shard*> shards = shards_of(h);
  size_t nsh = shards.size();
  bool rotate = as_frontend(h) != nullptr && nsh > 1;
  size_t start = rotate ? fe->hot_shard % nsh : 0;
  int n = 0;
  int off = 0;
  bool full = false;
  for (size_t si = 0; si < nsh && !full; si++) {
    Shard* sh = shards[(start + si) % nsh];
    std::lock_guard<FeMutex> lk(sh->mu);
    while (!sh->hot_ring.empty()) {
      const auto& front = sh->hot_ring.front();
      if (n >= max_n || off + int(front.first.size()) > blob_cap) {
        full = true;
        break;
      }
      std::memcpy(key_blob + off, front.first.data(), front.first.size());
      klens[n] = int32_t(front.first.size());
      weights[n] = front.second;
      off += int(front.first.size());
      n++;
      sh->hot_ring.pop_front();
    }
    if (full && rotate) fe->hot_shard = (start + si) % nsh;
  }
  if (!full && rotate) fe->hot_shard = (start + 1) % nsh;
  return n;
}

// ---------------------------------------------------------------------
// Native closed-loop load generator: the measurement client for the
// front-end (a Python client's own ~14µs/request scheduling floor would
// bound the measurement, not the server). Opens `conns` connections, keeps
// `depth` ACQUIRE requests in flight on each, counts grants. Single
// epoll thread; returns total replies, grants, and elapsed seconds.
// ---------------------------------------------------------------------

namespace {

struct LgConn {
  int fd;
  int sent = 0, recvd = 0;
  bool dead = false;
  std::vector<uint8_t> in;
  size_t in_off = 0;
};

std::string lg_request(uint32_t seq, uint8_t op, const std::string& key,
                       double a, double b) {
  std::string s;
  uint16_t klen = uint16_t(key.size());
  wr_u32(&s, uint32_t(kBodyOff + 2 + klen + 20));
  s.push_back(char(kVersion));
  wr_u32(&s, seq);
  s.push_back(char(op));
  s.append(reinterpret_cast<const char*>(&klen), 2);
  s.append(key);
  int32_t count = 1;
  s.append(reinterpret_cast<const char*>(&count), 4);
  wr_f64(&s, a);
  wr_f64(&s, b);
  return s;
}

}  // namespace

int fe_loadgen(const char* host, int port, int n_conns, int depth,
               int reqs_per_conn, int keyspace, double a, double b,
               int op, double* out_elapsed_s, long long* out_replies,
               long long* out_granted) {
  uint8_t op8 = uint8_t(op > 0 ? op : OP_ACQUIRE);
  std::vector<LgConn> conns{size_t(n_conns)};
  int epfd = epoll_create1(0);
  sockaddr_in addr{};
  addr.sin_family = AF_INET;
  addr.sin_port = htons(uint16_t(port));
  if (inet_pton(AF_INET, host, &addr.sin_addr) != 1) {
    ::close(epfd);
    return -1;
  }
  for (int i = 0; i < n_conns; i++) {
    int fd = socket(AF_INET, SOCK_STREAM, 0);
    if (connect(fd, reinterpret_cast<sockaddr*>(&addr), sizeof addr) < 0) {
      ::close(epfd);
      return -1;
    }
    int one = 1;
    setsockopt(fd, IPPROTO_TCP, TCP_NODELAY, &one, sizeof one);
    set_nonblock(fd);
    conns[size_t(i)].fd = fd;
    epoll_event ev{};
    ev.events = EPOLLIN;
    ev.data.u32 = uint32_t(i);
    epoll_ctl(epfd, EPOLL_CTL_ADD, fd, &ev);
  }
  long long replies = 0, granted = 0;
  int live = n_conns;
  const long long want = (long long)n_conns * reqs_per_conn;
  uint64_t t0 = now_ns();
  // Prime: `depth` pipelined requests per connection.
  for (int i = 0; i < n_conns; i++) {
    std::string burst;
    for (int d = 0; d < depth && d < reqs_per_conn; d++) {
      std::string key =
          "lg" + std::to_string(i) + "-" + std::to_string(d % keyspace);
      burst += lg_request(uint32_t(conns[size_t(i)].sent++), op8, key, a, b);
    }
    ssize_t r = ::send(conns[size_t(i)].fd, burst.data(), burst.size(),
                       MSG_NOSIGNAL);
    (void)r;  // pipelined burst fits the socket buffer at these depths
  }
  epoll_event events[64];
  while (replies < want && live > 0) {
    int n = epoll_wait(epfd, events, 64, 10000);
    if (n <= 0) break;  // stalled server: bail with what we have
    for (int e = 0; e < n; e++) {
      LgConn& c = conns[events[e].data.u32];
      if (c.dead) continue;
      uint8_t buf[65536];
      for (;;) {
        ssize_t r = ::recv(c.fd, buf, sizeof buf, 0);
        if (r > 0) {
          c.in.insert(c.in.end(), buf, buf + r);
          continue;
        }
        if (r == 0 || (errno != EAGAIN && errno != EWOULDBLOCK)) {
          // EOF/reset (e.g. an auth-protected server closing us): a
          // level-triggered dead fd would spin epoll_wait forever —
          // deregister and count the conn out instead.
          epoll_ctl(epfd, EPOLL_CTL_DEL, c.fd, nullptr);
          c.dead = true;
          live--;
        }
        break;
      }
      int completed = 0;
      for (;;) {
        size_t avail = c.in.size() - c.in_off;
        if (avail < 4) break;
        uint32_t len = rd_u32(c.in.data() + c.in_off);
        if (avail < 4 + size_t(len)) break;
        const uint8_t* body = c.in.data() + c.in_off + 4;
        // Length check FIRST: body[5]/body[6] on a short frame (len < 7)
        // would read past the buffered input.
        if (len >= kBodyOff + 1 && body[5] == RESP_DECISION && body[6]) {
          granted++;
        }
        c.in_off += 4 + len;
        replies++;
        c.recvd++;
        completed++;
      }
      if (c.in_off == c.in.size()) {
        c.in.clear();
        c.in_off = 0;
      }
      // Refill the pipeline: one new request per completed reply.
      if (completed > 0 && c.sent < reqs_per_conn) {
        std::string burst;
        for (int d = 0; d < completed && c.sent < reqs_per_conn; d++) {
          std::string key = "lg" + std::to_string(events[e].data.u32) + "-" +
                            std::to_string(c.sent % keyspace);
          burst += lg_request(uint32_t(c.sent++), op8, key, a, b);
        }
        ssize_t r = ::send(c.fd, burst.data(), burst.size(), MSG_NOSIGNAL);
        (void)r;
      }
    }
  }
  *out_elapsed_s = double(now_ns() - t0) * 1e-9;
  *out_replies = replies;
  *out_granted = granted;
  for (auto& c : conns) ::close(c.fd);
  ::close(epfd);
  return 0;
}

// Bulk-lane measurement client (round 11): `conns` connections each
// keeping `depth` OP_ACQUIRE_MANY frames of `rows_per_frame` rows in
// flight. The scalar fe_loadgen exists because a Python client's
// ~14µs/request floor would bound the measurement; at multi-shard bulk
// rates even a Python PER-FRAME client bounds the node (one encode +
// event-loop turn per 4096 rows × N shards), so the shard-sweep rig
// needs frames built and counted in C too. Keys draw from one shared
// `keyspace` pool ("b<i>") — the hot tier-0 shape the sweep measures —
// and the kernel's SO_REUSEPORT hash spreads the connections across
// shards. Frames carry the with-remaining flag: the bulk lane only
// installs tier-0 replicas from with-remaining grants, and the sweep
// exists to measure the replicated-envelope hot path, not the residue
// lane. Returns total frames, rows, and granted rows (bitmap popcount
// — the bitmap precedes the f32 remaining array in RESP_BULK).
int fe_lg_bulk(const char* host, int port, int n_conns, int depth,
               int frames_per_conn, int rows_per_frame, int keyspace,
               double a, double b, double* out_elapsed_s,
               long long* out_frames, long long* out_rows,
               long long* out_granted) {
  if (n_conns <= 0 || rows_per_frame <= 0 || keyspace <= 0) return -1;
  std::vector<LgConn> conns{size_t(n_conns)};
  int epfd = epoll_create1(0);
  sockaddr_in addr{};
  addr.sin_family = AF_INET;
  addr.sin_port = htons(uint16_t(port));
  if (inet_pton(AF_INET, host, &addr.sin_addr) != 1) {
    ::close(epfd);
    return -1;
  }
  for (int i = 0; i < n_conns; i++) {
    int fd = socket(AF_INET, SOCK_STREAM, 0);
    if (connect(fd, reinterpret_cast<sockaddr*>(&addr), sizeof addr) < 0) {
      ::close(epfd);
      return -1;
    }
    int one = 1;
    setsockopt(fd, IPPROTO_TCP, TCP_NODELAY, &one, sizeof one);
    set_nonblock(fd);
    conns[size_t(i)].fd = fd;
    epoll_event ev{};
    ev.events = EPOLLIN;
    ev.data.u32 = uint32_t(i);
    epoll_ctl(epfd, EPOLL_CTL_ADD, fd, &ev);
  }
  // Outbound staging per connection: frames queue in LgConn-local
  // buffers and drain via EPOLLOUT — a burst past the socket buffer
  // must NOT busy-spin on EAGAIN (16 client threads spinning is a
  // measurable bite out of the very CPUs the server under test needs).
  std::vector<std::string> outq(static_cast<size_t>(n_conns));
  std::vector<size_t> outq_off(static_cast<size_t>(n_conns), 0);
  std::vector<uint8_t> want_out(static_cast<size_t>(n_conns), 0);
  // One frame template per sequence slot: the body is identical for
  // every send except the seq, so build it once and patch seq in place.
  uint64_t n = uint64_t(rows_per_frame);
  std::string body;
  body.push_back(char(kVersion));
  wr_u32(&body, 0);  // seq, patched per send at offset 1
  body.push_back(char(OP_ACQUIRE_MANY));
  body.push_back(char(kBulkFlagRemaining));  // kind bucket, remainings on
  wr_f64(&body, a);
  wr_f64(&body, b);
  wr_u32(&body, uint32_t(n));
  std::string blob;
  std::vector<uint16_t> klens(n);
  for (uint64_t i = 0; i < n; i++) {
    std::string key = "b" + std::to_string(i % uint64_t(keyspace));
    klens[i] = uint16_t(key.size());
    blob += key;
  }
  body.append(reinterpret_cast<const char*>(klens.data()), 2 * n);
  body += blob;
  for (uint64_t i = 0; i < n; i++) wr_u32(&body, 1);  // unit counts
  std::string frame;
  wr_u32(&frame, uint32_t(body.size()));
  frame += body;
  constexpr size_t kSeqOff = 5;  // [u32 len][u8 ver] then seq
  auto flush_conn = [&](size_t ci) {
    LgConn& c = conns[ci];
    std::string& out = outq[ci];
    size_t& off = outq_off[ci];
    while (off < out.size()) {
      ssize_t r = ::send(c.fd, out.data() + off, out.size() - off,
                         MSG_NOSIGNAL);
      if (r > 0) {
        off += size_t(r);
        continue;
      }
      if (r < 0 && (errno == EAGAIN || errno == EWOULDBLOCK)) {
        if (!want_out[ci]) {
          want_out[ci] = 1;
          epoll_event ev{};
          ev.events = EPOLLIN | EPOLLOUT;
          ev.data.u32 = uint32_t(ci);
          epoll_ctl(epfd, EPOLL_CTL_MOD, c.fd, &ev);
        }
        return;
      }
      break;  // hard error: reader side will reap the conn
    }
    out.clear();
    off = 0;
    if (want_out[ci]) {
      want_out[ci] = 0;
      epoll_event ev{};
      ev.events = EPOLLIN;
      ev.data.u32 = uint32_t(ci);
      epoll_ctl(epfd, EPOLL_CTL_MOD, c.fd, &ev);
    }
  };
  auto send_frames = [&](size_t ci, int count) {
    LgConn& c = conns[ci];
    for (int d = 0; d < count && c.sent < frames_per_conn; d++) {
      uint32_t seq = uint32_t(c.sent++);
      std::memcpy(&frame[kSeqOff], &seq, 4);
      outq[ci] += frame;
    }
    flush_conn(ci);
  };
  long long frames_done = 0, granted = 0;
  int live = n_conns;
  const long long want = (long long)n_conns * frames_per_conn;
  uint64_t t0 = now_ns();
  for (size_t ci = 0; ci < size_t(n_conns); ci++) {
    send_frames(ci, depth);
  }
  epoll_event events[64];
  while (frames_done < want && live > 0) {
    int nev = epoll_wait(epfd, events, 64, 10000);
    if (nev <= 0) break;  // stalled server: bail with what we have
    for (int e = 0; e < nev; e++) {
      size_t ci = events[e].data.u32;
      LgConn& c = conns[ci];
      if (c.dead) continue;
      if (events[e].events & EPOLLOUT) flush_conn(ci);
      uint8_t buf[65536];
      for (;;) {
        ssize_t r = ::recv(c.fd, buf, sizeof buf, 0);
        if (r > 0) {
          c.in.insert(c.in.end(), buf, buf + r);
          continue;
        }
        if (r == 0 || (errno != EAGAIN && errno != EWOULDBLOCK)) {
          epoll_ctl(epfd, EPOLL_CTL_DEL, c.fd, nullptr);
          c.dead = true;
          live--;
        }
        break;
      }
      int completed = 0;
      for (;;) {
        size_t avail = c.in.size() - c.in_off;
        if (avail < 4) break;
        uint32_t len = rd_u32(c.in.data() + c.in_off);
        if (avail < 4 + size_t(len)) break;
        const uint8_t* rbody = c.in.data() + c.in_off + 4;
        if (len >= kBodyOff + kBulkRespHead &&
            rbody[5] == RESP_BULK) {
          uint32_t rn = rd_u32(rbody + kBodyOff + 1);
          const uint8_t* bits = rbody + kBodyOff + kBulkRespHead;
          size_t nbits = (size_t(rn) + 7) / 8;
          if (len >= kBodyOff + kBulkRespHead + nbits) {
            for (size_t bi = 0; bi < nbits; bi++) {
              granted += __builtin_popcount(bits[bi]);
            }
          }
        }
        c.in_off += 4 + len;
        frames_done++;
        c.recvd++;
        completed++;
      }
      if (c.in_off == c.in.size()) {
        c.in.clear();
        c.in_off = 0;
      }
      if (completed > 0) send_frames(ci, completed);
    }
  }
  *out_elapsed_s = double(now_ns() - t0) * 1e-9;
  *out_frames = frames_done;
  *out_rows = frames_done * (long long)rows_per_frame;
  *out_granted = granted;
  for (auto& c : conns) ::close(c.fd);
  ::close(epfd);
  return 0;
}

// uring twin of fe_lg_bulk (round 16): identical frame template, depth
// pipelining, and accounting — the transport is ONE ring driving every
// connection, so a reply burst costs one enter instead of a recv+send
// pair per connection (in r11 the epoll loadgen's own syscall bill was
// part of the measured ceiling). Per connection at most one SEND and
// one RECV op are in flight; a 10 s TIMEOUT op mirrors the epoll lane's
// stalled-server bail. Returns -2 when the kernel lacks the uring
// feature level (callers fall back to fe_lg_bulk), else 0/-1 with the
// same contract.
int fe_lg_bulk_uring(const char* host, int port, int n_conns, int depth,
                     int frames_per_conn, int rows_per_frame, int keyspace,
                     double a, double b, double* out_elapsed_s,
                     long long* out_frames, long long* out_rows,
                     long long* out_granted) {
  {
    std::string reason;
    if (uring_probe(&reason) == 0) return -2;
  }
  if (n_conns <= 0 || rows_per_frame <= 0 || keyspace <= 0) return -1;
  DrlUringParams p{};
  p.flags = kUringSetupCqsize | kUringSetupClamp;
  unsigned sq_want = 64;
  while (sq_want < unsigned(2 * n_conns + 8) && sq_want < 4096) {
    sq_want <<= 1;
  }
  p.cq_entries = sq_want * 2;
  int rfd = sys_uring_setup(sq_want, &p);
  if (rfd < 0) return -2;
  size_t sq_len = size_t(p.sq_off.array) + p.sq_entries * sizeof(uint32_t);
  size_t cq_len = size_t(p.cq_off.cqes) + p.cq_entries * sizeof(DrlCqe);
  bool single = (p.features & kUringFeatSingleMmap) != 0;
  if (single) sq_len = cq_len = std::max(sq_len, cq_len);
  void* sqm = mmap(nullptr, sq_len, PROT_READ | PROT_WRITE,
                   MAP_SHARED | MAP_POPULATE, rfd, long(kUringOffSqRing));
  void* cqm = single ? sqm
                     : mmap(nullptr, cq_len, PROT_READ | PROT_WRITE,
                            MAP_SHARED | MAP_POPULATE, rfd,
                            long(kUringOffCqRing));
  size_t sqes_len = p.sq_entries * sizeof(DrlSqe);
  void* sqesm = mmap(nullptr, sqes_len, PROT_READ | PROT_WRITE,
                     MAP_SHARED | MAP_POPULATE, rfd, long(kUringOffSqes));
  if (sqm == MAP_FAILED || cqm == MAP_FAILED || sqesm == MAP_FAILED) {
    if (sqm != MAP_FAILED) munmap(sqm, sq_len);
    if (cqm != MAP_FAILED && cqm != sqm) munmap(cqm, cq_len);
    if (sqesm != MAP_FAILED) munmap(sqesm, sqes_len);
    ::close(rfd);
    return -2;
  }
  auto* sqb = static_cast<uint8_t*>(sqm);
  auto* sq_head =
      reinterpret_cast<std::atomic<uint32_t>*>(sqb + p.sq_off.head);
  auto* sq_tail =
      reinterpret_cast<std::atomic<uint32_t>*>(sqb + p.sq_off.tail);
  uint32_t sq_mask = *reinterpret_cast<uint32_t*>(sqb + p.sq_off.ring_mask);
  uint32_t* sq_array = reinterpret_cast<uint32_t*>(sqb + p.sq_off.array);
  DrlSqe* sqes = static_cast<DrlSqe*>(sqesm);
  auto* cqb = static_cast<uint8_t*>(cqm);
  auto* cq_head =
      reinterpret_cast<std::atomic<uint32_t>*>(cqb + p.cq_off.head);
  auto* cq_tail =
      reinterpret_cast<std::atomic<uint32_t>*>(cqb + p.cq_off.tail);
  uint32_t cq_mask = *reinterpret_cast<uint32_t*>(cqb + p.cq_off.ring_mask);
  DrlCqe* cqes = reinterpret_cast<DrlCqe*>(cqb + p.cq_off.cqes);
  uint32_t staged = 0;
  auto get_sqe = [&]() -> DrlSqe* {
    uint32_t tail = sq_tail->load(std::memory_order_relaxed);
    uint32_t head = sq_head->load(std::memory_order_acquire);
    if (tail - head >= sq_mask + 1) return nullptr;
    uint32_t idx = tail & sq_mask;
    DrlSqe* s = &sqes[idx];
    std::memset(s, 0, sizeof *s);
    sq_array[idx] = idx;
    sq_tail->store(tail + 1, std::memory_order_release);
    staged++;
    return s;
  };
  auto cleanup = [&]() {
    if (cqm != sqm) munmap(cqm, cq_len);
    munmap(sqm, sq_len);
    munmap(sqesm, sqes_len);
    ::close(rfd);
  };
  std::vector<LgConn> conns{size_t(n_conns)};
  sockaddr_in addr{};
  addr.sin_family = AF_INET;
  addr.sin_port = htons(uint16_t(port));
  if (inet_pton(AF_INET, host, &addr.sin_addr) != 1) {
    cleanup();
    return -1;
  }
  for (int i = 0; i < n_conns; i++) {
    int fd = socket(AF_INET, SOCK_STREAM, 0);
    if (connect(fd, reinterpret_cast<sockaddr*>(&addr), sizeof addr) < 0) {
      for (int j = 0; j < i; j++) ::close(conns[size_t(j)].fd);
      ::close(fd);
      cleanup();
      return -1;
    }
    int one = 1;
    setsockopt(fd, IPPROTO_TCP, TCP_NODELAY, &one, sizeof one);
    // Sockets stay blocking: a ring op parks in the kernel instead of
    // returning EAGAIN, so no EPOLLOUT staging machinery is needed.
    conns[size_t(i)].fd = fd;
  }
  // Frame template — byte-identical to fe_lg_bulk's (the server replies
  // are compared across loadgens in the parity rig).
  uint64_t n = uint64_t(rows_per_frame);
  std::string body;
  body.push_back(char(kVersion));
  wr_u32(&body, 0);  // seq, patched per send at offset 1
  body.push_back(char(OP_ACQUIRE_MANY));
  body.push_back(char(kBulkFlagRemaining));
  wr_f64(&body, a);
  wr_f64(&body, b);
  wr_u32(&body, uint32_t(n));
  std::string blob;
  std::vector<uint16_t> klens(n);
  for (uint64_t i = 0; i < n; i++) {
    std::string key = "b" + std::to_string(i % uint64_t(keyspace));
    klens[i] = uint16_t(key.size());
    blob += key;
  }
  body.append(reinterpret_cast<const char*>(klens.data()), 2 * n);
  body += blob;
  for (uint64_t i = 0; i < n; i++) wr_u32(&body, 1);  // unit counts
  std::string frame;
  wr_u32(&frame, uint32_t(body.size()));
  frame += body;
  constexpr size_t kSeqOff = 5;
  std::vector<std::string> outq(static_cast<size_t>(n_conns));
  std::vector<size_t> outq_off(static_cast<size_t>(n_conns), 0);
  std::vector<uint8_t> send_busy(static_cast<size_t>(n_conns), 0);
  std::vector<uint8_t> recv_busy(static_cast<size_t>(n_conns), 0);
  std::vector<std::vector<uint8_t>> rbuf(
      static_cast<size_t>(n_conns), std::vector<uint8_t>(65536));
  auto arm_send = [&](size_t ci) {
    LgConn& c = conns[ci];
    if (send_busy[ci] != 0 || c.dead) return;
    if (outq_off[ci] >= outq[ci].size()) {
      outq[ci].clear();
      outq_off[ci] = 0;
      return;
    }
    DrlSqe* s = get_sqe();
    if (s == nullptr) return;  // retried when the op count drops
    s->opcode = kOpSend;
    s->fd = c.fd;
    s->addr = uint64_t(
        reinterpret_cast<uintptr_t>(outq[ci].data() + outq_off[ci]));
    s->len = uint32_t(outq[ci].size() - outq_off[ci]);
    s->op_flags = MSG_NOSIGNAL;
    s->user_data = uring_ud(kUdSend, ci);
    send_busy[ci] = 1;
  };
  auto arm_recv = [&](size_t ci) {
    LgConn& c = conns[ci];
    if (recv_busy[ci] != 0 || c.dead) return;
    DrlSqe* s = get_sqe();
    if (s == nullptr) return;
    s->opcode = kOpRecv;
    s->fd = c.fd;
    s->addr = uint64_t(reinterpret_cast<uintptr_t>(rbuf[ci].data()));
    s->len = uint32_t(rbuf[ci].size());
    s->user_data = uring_ud(kUdRecv, ci);
    recv_busy[ci] = 1;
  };
  auto send_frames = [&](size_t ci, int count) {
    LgConn& c = conns[ci];
    for (int d = 0; d < count && c.sent < frames_per_conn; d++) {
      uint32_t seq = uint32_t(c.sent++);
      std::memcpy(&frame[kSeqOff], &seq, 4);
      outq[ci] += frame;
    }
    arm_send(ci);
  };
  DrlKTimespec bail_ts{10, 0};  // the epoll lane's 10 s stall bail
  auto arm_bail = [&]() {
    DrlSqe* s = get_sqe();
    if (s == nullptr) return;
    s->opcode = kOpTimeout;
    s->addr = uint64_t(reinterpret_cast<uintptr_t>(&bail_ts));
    s->len = 1;
    s->user_data = uring_ud(kUdTfRead, 0);  // kind reuse: the timer slot
  };
  long long frames_done = 0, granted = 0;
  long long bail_mark = -1;
  int live = n_conns;
  const long long want = (long long)n_conns * frames_per_conn;
  uint64_t t0 = now_ns();
  for (size_t ci = 0; ci < size_t(n_conns); ci++) {
    send_frames(ci, depth);
    arm_recv(ci);
  }
  arm_bail();
  bool stalled = false;
  while (frames_done < want && live > 0 && !stalled) {
    int rc = sys_uring_enter(rfd, staged, 1, kUringEnterGetevents);
    if (rc < 0) {
      if (errno == EINTR || errno == EBUSY || errno == EAGAIN) continue;
      break;
    }
    staged -= uint32_t(rc) > staged ? staged : uint32_t(rc);
    uint32_t head = cq_head->load(std::memory_order_relaxed);
    uint32_t tail = cq_tail->load(std::memory_order_acquire);
    while (head != tail) {
      DrlCqe cqe = cqes[head & cq_mask];
      head++;
      cq_head->store(head, std::memory_order_release);
      uint64_t kind = cqe.user_data >> 56;
      size_t ci = size_t(cqe.user_data & ((1ull << 56) - 1));
      if (kind == kUdTfRead) {  // the 10 s stall bail
        if (frames_done == bail_mark) {
          stalled = true;
          break;
        }
        bail_mark = frames_done;
        arm_bail();
        tail = cq_tail->load(std::memory_order_acquire);
        continue;
      }
      LgConn& c = conns[ci];
      if (kind == kUdSend) {
        send_busy[ci] = 0;
        if (cqe.res < 0) {
          if (!c.dead) {
            c.dead = true;
            live--;
          }
        } else if (!c.dead) {
          outq_off[ci] += size_t(cqe.res);
          arm_send(ci);
        }
        tail = cq_tail->load(std::memory_order_acquire);
        continue;
      }
      // kUdRecv
      recv_busy[ci] = 0;
      if (cqe.res <= 0) {
        if (!c.dead) {
          c.dead = true;
          live--;
        }
        tail = cq_tail->load(std::memory_order_acquire);
        continue;
      }
      c.in.insert(c.in.end(), rbuf[ci].data(), rbuf[ci].data() + cqe.res);
      int completed = 0;
      for (;;) {
        size_t avail = c.in.size() - c.in_off;
        if (avail < 4) break;
        uint32_t len = rd_u32(c.in.data() + c.in_off);
        if (avail < 4 + size_t(len)) break;
        const uint8_t* rbody = c.in.data() + c.in_off + 4;
        if (len >= kBodyOff + kBulkRespHead && rbody[5] == RESP_BULK) {
          uint32_t rn = rd_u32(rbody + kBodyOff + 1);
          const uint8_t* bits = rbody + kBodyOff + kBulkRespHead;
          size_t nbits = (size_t(rn) + 7) / 8;
          if (len >= kBodyOff + kBulkRespHead + nbits) {
            for (size_t bi = 0; bi < nbits; bi++) {
              granted += __builtin_popcount(bits[bi]);
            }
          }
        }
        c.in_off += 4 + len;
        frames_done++;
        c.recvd++;
        completed++;
      }
      if (c.in_off == c.in.size()) {
        c.in.clear();
        c.in_off = 0;
      }
      if (completed > 0) send_frames(ci, completed);
      arm_recv(ci);
      tail = cq_tail->load(std::memory_order_acquire);
    }
  }
  *out_elapsed_s = double(now_ns() - t0) * 1e-9;
  *out_frames = frames_done;
  *out_rows = frames_done * (long long)rows_per_frame;
  *out_granted = granted;
  for (auto& c : conns) ::close(c.fd);
  cleanup();
  return 0;
}

}  // extern "C"
