// What a workload of the benchmark's plain reference sees: the engine's
// draw discipline, event and emit rows, the handler context, and the
// registry through which each file under workloads/ adds its workload
// by name. The step loop that drives them is oracle.cpp.
//
// A workload file defines its handler and parameters in an unnamed
// namespace and registers them once, at load, with a static registrar:
//
//   const bool kRegistered = benchref::register_workload(
//       "raft", &set_params, &make);
//
// set_params takes the configuration's reference.params packed by
// workloads/<name>.py and returns false on a count it does not expect;
// make returns the Workload for the parameters last set.
// Integer arithmetic only: uint32 threefry, int64 ns clocks, uint64 trace.

#ifndef BENCHREF_SIM_H_
#define BENCHREF_SIM_H_

#include <cstdint>
#include <vector>

namespace benchref {

// ---- threefry2x32 (engine/rng.py threefry2x32) --------------------------
constexpr uint32_t kParity = 0x1BD11BDA;
constexpr int kRot[8] = {13, 15, 26, 6, 17, 29, 16, 24};

inline uint32_t rotl32(uint32_t x, int r) {
  return (x << r) | (x >> (32 - r));
}

inline void threefry2x32(uint32_t k0, uint32_t k1, uint32_t x0, uint32_t x1,
                         uint32_t* o0, uint32_t* o1) {
  uint32_t ks[3] = {k0, k1, static_cast<uint32_t>(k0 ^ k1 ^ kParity)};
  x0 += ks[0];
  x1 += ks[1];
  for (int chunk = 0; chunk < 5; chunk++) {
    const int* rots = (chunk % 2 == 0) ? kRot : kRot + 4;
    for (int j = 0; j < 4; j++) {
      x0 += x1;
      x1 = rotl32(x1, rots[j]);
      x1 ^= x0;
    }
    x0 += ks[(chunk + 1) % 3];
    x1 += ks[(chunk + 2) % 3] + static_cast<uint32_t>(chunk + 1);
  }
  *o0 = x0;
  *o1 = x1;
}

// ---- draw discipline (engine/rng.py Draw) -------------------------------
constexpr uint32_t kPurposePollCost = 0;
constexpr uint32_t kPurposeClogJitter = 1;
constexpr uint32_t kPurposeLatency = 8;
constexpr uint32_t kPurposeLoss = 64;
constexpr uint32_t kPurposeUser = 128;

struct Draw {
  uint32_t k0, k1, step;
  uint32_t bits(uint32_t purpose) const {
    uint32_t a, b;
    threefry2x32(k0, k1, step, purpose, &a, &b);
    return a;
  }
  // both lanes of one block (engine Draw.bits2): the per-emit latency
  // (lane 0) and loss (lane 1) draws share the PURPOSE_LATENCY+slot
  // counter
  void bits2(uint32_t purpose, uint32_t* a, uint32_t* b) const {
    threefry2x32(k0, k1, step, purpose, a, b);
  }
  // uniform int64 in [lo, hi): modulo reduction, same bias as the spec
  int64_t uniform_int(int64_t lo, int64_t hi, uint32_t purpose) const {
    uint32_t span = static_cast<uint32_t>(hi - lo);
    if (span == 0) span = 1;
    return lo + static_cast<int64_t>(bits(purpose) % span);
  }
  // two uniform int64s from ONE block (engine Draw.uniform_int2):
  // lane 0 -> [lo_a, hi_a), lane 1 -> [lo_b, hi_b)
  void uniform_int2(int64_t lo_a, int64_t hi_a, int64_t lo_b, int64_t hi_b,
                    uint32_t purpose, int64_t* out_a, int64_t* out_b) const {
    uint32_t a, b;
    bits2(purpose, &a, &b);
    uint32_t span_a = static_cast<uint32_t>(hi_a - lo_a);
    if (span_a == 0) span_a = 1;
    uint32_t span_b = static_cast<uint32_t>(hi_b - lo_b);
    if (span_b == 0) span_b = 1;
    *out_a = lo_a + static_cast<int64_t>(a % span_a);
    *out_b = lo_b + static_cast<int64_t>(b % span_b);
  }
  uint32_t user(uint32_t purpose) const { return bits(kPurposeUser + purpose); }
  int64_t user_int(int64_t lo, int64_t hi, uint32_t purpose) const {
    return uniform_int(lo, hi, kPurposeUser + purpose);
  }
};

// ---- event kinds (engine/core.py) ---------------------------------------
constexpr int32_t KIND_KILL = 0;
constexpr int32_t KIND_RESTART = 1;
constexpr int32_t KIND_CLOG = 2;
constexpr int32_t KIND_UNCLOG = 3;
constexpr int32_t KIND_CLOG_NODE = 4;
constexpr int32_t KIND_UNCLOG_NODE = 5;
constexpr int32_t KIND_HALT = 6;
constexpr int32_t KIND_NOP = 7;
constexpr int32_t KIND_PAUSE = 8;
constexpr int32_t KIND_RESUME = 9;
constexpr int32_t FIRST_USER_KIND = 10;

struct Config {  // EngineConfig
  int64_t pool_size;
  int64_t lat_min_ns, lat_max_ns;
  uint64_t loss_u32;  // in [0, 2^32]; 2^32 = always drop (loss_p=1.0)
  int64_t proc_min_ns, proc_max_ns;
  int64_t clog_backoff_min_ns, clog_backoff_max_ns;
  int64_t time_limit_ns;  // 0 = unlimited
};

// payload width cap (Workload.payload_words; engine events carry W
// int32 words — engine/core.py ev_pay). A pooled event's W words are
// held per run, beside the pool (oracle.cpp); ref_run refuses a
// workload that declares more than this.
constexpr int32_t kMaxPay = 64;

struct Event {
  int64_t time;
  bool valid;
  int32_t kind, node, src, epoch, retry;
  int32_t args[4];
};

// one emit row (Emits); words past the workload's payload_words are
// never read
struct Emit {
  bool valid = false;
  bool send = false;
  int32_t kind = 0, dst = 0;
  int64_t delay = 0;
  int32_t args[4] = {0, 0, 0, 0};
  int32_t pay[kMaxPay] = {};
};

// one history record (Emits.record): when, [op, key, arg, ok]
struct Rec {
  bool when;
  int32_t op, key, arg, ok;
};

struct Effects {
  std::vector<Emit> emits;
  std::vector<Rec> recs;
  int32_t kill = -1, restart = -1;
  int32_t clog_a = -1, clog_b = -1, clog_set = -1;
  int32_t pause_node = -1, pause_set = -1;
  bool halt = false;
};

struct Ctx {
  int64_t now;
  int32_t node;
  const int32_t* state;  // (U,)
  const int32_t* args;   // (4,)
  int32_t src;
  Draw draw;
  const int32_t* pay = nullptr;  // (W,) the event's payload words
};

// Workload interface: mirrors engine Workload. new_state is written by
// the handler; the engine applies it only when the event dispatches.
//
// durable_cols are the state columns that survive a restart (engine
// Workload.durable_cols): a restarted node keeps them and takes every
// other column from its initial row. The program's two-phase sync
// discipline (Workload.durable_sync) has no model here: with no injected
// disk fault, a workload that syncs every durable write in the dispatch
// that made it follows the same trajectory as one whose durable columns
// survive verbatim, which is what this engine implements.
struct Workload {
  int32_t n_nodes, state_width, n_handlers, max_emits;
  // handler(h, ctx, new_state_out, effects_out)
  void (*handler)(int32_t h, const Ctx&, int32_t*, Effects*);
  int32_t payload_words = 0;  // engine Workload.payload_words, <= kMaxPay
  std::vector<int32_t> durable_cols = {};
};

inline Emit mk_send(int32_t dst, int32_t kind, int32_t a0 = 0, int32_t a1 = 0,
                    bool when = true) {
  Emit e;
  e.valid = when;
  e.send = true;
  e.kind = kind;
  e.dst = dst;
  e.args[0] = a0;
  e.args[1] = a1;
  return e;
}

inline Emit mk_after(int64_t delay, int32_t kind, int32_t dst, int32_t a0 = 0,
                     bool when = true) {
  Emit e;
  e.valid = when;
  e.send = false;
  e.kind = kind;
  e.dst = dst;
  e.delay = delay;
  e.args[0] = a0;
  return e;
}

// ---- the registry (oracle.cpp) ------------------------------------------
using SetParams = bool (*)(const int64_t* params, int64_t n);
using MakeWorkload = Workload (*)();

// Adds a workload under `name`; returns true (a value for the registrar).
bool register_workload(const char* name, SetParams set_params,
                       MakeWorkload make);

}  // namespace benchref

#endif  // BENCHREF_SIM_H_
