// The benchmark's plain reference: a single-seed C++ simulator of the
// configurations the benchmark runs, kept with the benchmark so that no
// program change can move it. This file is the engine: the step loop,
// the plan channel, the history and the control; each workload is a file
// of its own under workloads/, found by the name it registers (sim.h).
//
// Copied from native/oracle.cpp and extended with what the benchmark's
// deployments need and the program's own oracle lacks:
//   * the fault-plan channel: plan rows pre-seeded into pool slots
//     [n_nodes, n_nodes + P) at init (engine/core.py make_init);
//   * operation-history records (Emits.record): row [op, key, arg,
//     client, ok] stamped with the handling node and the dispatch clock,
//     appended at hist_count, counted in hist_drop once the buffer is full;
//   * the control: `time_quantum` > 1 holds every scheduled event time
//     at that resolution (rounded down), the coarser clock a narrower
//     time representation would tempt. 1 = exact;
//   * payloads as wide as the workload declares, up to kMaxPay words,
//     held per run beside the pool, and the restart-surviving columns
//     the workload declares (Workload.durable_cols).
// Integer arithmetic only: uint32 threefry, int64 ns clocks, uint64 trace.

#include <cstdint>
#include <cstring>
#include <map>
#include <string>
#include <vector>

#include "sim.h"

namespace benchref {
namespace {

constexpr int64_t kInf = int64_t{1} << 62;
constexpr uint64_t kTracePrime = 0x100000001B3ull;
constexpr uint64_t kTraceMix = 0x9E3779B97F4A7C15ull;

// ---- the step loop (engine/core.py make_step) ---------------------------
struct Sim {
  Config cfg;
  Workload wl;
  uint64_t seed;
  int64_t now = 0;
  uint32_t step = 0;
  bool halted = false;
  int64_t halt_time = 0;
  uint64_t trace = 0;
  int32_t overflow = 0;
  int64_t msg_count = 0;
  std::vector<Event> ev;
  std::vector<int32_t> ev_pay;      // (pool, W) each pooled event's payload
  std::vector<uint8_t> alive;
  std::vector<uint8_t> paused;
  std::vector<int32_t> epoch;
  std::vector<int32_t> node_state;  // (N,U)
  std::vector<int32_t> init_state;  // (N,U) Workload.initial_state() rows
  std::vector<uint8_t> durable;     // (U) restart-surviving columns
  std::vector<uint8_t> clog;        // (N,N)
  // fault-plan rows (engine kinds only): slot n_nodes + j
  std::vector<Event> plan;
  // operation history (HistorySpec): rows [op, key, arg, client, ok]
  int64_t hist_cap = 0;
  int32_t hist_count = 0, hist_drop = 0;
  std::vector<int32_t> hist_word;  // (cap, 5)
  std::vector<int64_t> hist_t;     // (cap)
  int64_t time_quantum = 1;        // control: event-time resolution

  void init() {
    ev.assign(cfg.pool_size, Event{0, false, KIND_NOP, 0, -1, 0, 0, {0, 0, 0, 0}});
    for (int32_t n = 0; n < wl.n_nodes; n++) {
      ev[n] = Event{0, true, FIRST_USER_KIND, n, -1, 0, 0, {0, 0, 0, 0}};
    }
    // plan rows ride slots [n, n+p): engine kinds, timer source, epoch 0
    for (size_t j = 0; j < plan.size(); j++) ev[wl.n_nodes + j] = plan[j];
    ev_pay.assign(static_cast<size_t>(cfg.pool_size) * wl.payload_words, 0);
    hist_word.assign(static_cast<size_t>(hist_cap) * 5, 0);
    hist_t.assign(static_cast<size_t>(hist_cap), 0);
    alive.assign(wl.n_nodes, 1);
    paused.assign(wl.n_nodes, 0);
    epoch.assign(wl.n_nodes, 0);
    if (init_state.empty())
      init_state.assign(static_cast<size_t>(wl.n_nodes) * wl.state_width, 0);
    node_state = init_state;
    clog.assign(static_cast<size_t>(wl.n_nodes) * wl.n_nodes, 0);
  }

  void trace_fold(int64_t t, int32_t kind, int32_t node, const int32_t* args,
                  const int32_t* pay) {
    uint64_t h = static_cast<uint64_t>(t) * kTraceMix;
    h ^= static_cast<uint64_t>(static_cast<uint32_t>(kind)) << 32;
    h ^= static_cast<uint64_t>(static_cast<uint32_t>(node)) << 40;
    uint64_t a0 = static_cast<uint32_t>(args[0]);
    uint64_t a1 = static_cast<uint32_t>(args[1]);
    uint64_t a2 = static_cast<uint32_t>(args[2]);
    uint64_t a3 = static_cast<uint32_t>(args[3]);
    h ^= a0 ^ (a1 << 8) ^ (a2 << 16) ^ (a3 << 24);
    if (wl.payload_words > 0) {
      // payload words participate in the trace (engine _trace_fold):
      // h ^= sum_w pay[w] * (MIX ^ w), wrapping uint64
      uint64_t acc = 0;
      for (int32_t wi = 0; wi < wl.payload_words; wi++) {
        acc += static_cast<uint64_t>(static_cast<uint32_t>(pay[wi])) *
               (kTraceMix ^ static_cast<uint64_t>(wi));
      }
      h ^= acc;
    }
    trace = trace * kTracePrime + h;
  }

  void do_step() {
    const int64_t time_limit = cfg.time_limit_ns ? cfg.time_limit_ns : kInf;
    // pop earliest (first-min, matching jnp.argmin)
    int64_t best = kInf;
    int64_t i = 0;
    for (int64_t j = 0; j < cfg.pool_size; j++) {
      int64_t t = ev[j].valid ? ev[j].time : kInf;
      if (t < best) {
        best = t;
        i = j;
      }
    }
    bool has_event = ev[i].valid;
    int64_t ev_t = ev[i].time > now ? ev[i].time : now;
    bool over_limit = ev_t > time_limit;
    bool active = has_event && !halted && !over_limit;

    int32_t kind = ev[i].kind, dst = ev[i].node, src = ev[i].src;
    int32_t args[4];
    std::memcpy(args, ev[i].args, sizeof(args));
    const int32_t W = wl.payload_words;
    int32_t pay[kMaxPay];  // copied now: the slot may be reused below
    if (W > 0)
      std::memcpy(pay, &ev_pay[static_cast<size_t>(i) * W], W * sizeof(int32_t));
    bool is_engine = kind < FIRST_USER_KIND;
    bool is_msg = src >= 0;
    bool live = alive[dst] && epoch[dst] == ev[i].epoch;
    bool clogged =
        is_msg && clog[static_cast<size_t>(src < 0 ? 0 : src) * wl.n_nodes + dst];
    // paused node: user events are stashed and retried (engine `held`)
    bool held = !is_engine && paused[dst];
    bool blocked = clogged || held;
    bool dispatch = active && !blocked && (is_engine || live);

    if (active) now = ev_t;
    Draw draw{static_cast<uint32_t>(seed & 0xFFFFFFFFull),
              static_cast<uint32_t>(seed >> 32), step};
    // poll cost paired with clog jitter in ONE block (engine
    // Draw.uniform_int2 at PURPOSE_POLL_COST: lane 0 = cost, lane 1 =
    // jitter)
    int64_t cost, clog_jit;
    draw.uniform_int2(cfg.proc_min_ns, cfg.proc_max_ns, 0, 1000,
                      kPurposePollCost, &cost, &clog_jit);
    int64_t now_after = dispatch ? now + cost : now;

    // consume / clog-reschedule (engine: resched branch)
    int32_t retries = ev[i].retry;
    int64_t shift = retries < 34 ? retries : 34;
    int64_t backoff = cfg.clog_backoff_min_ns << shift;
    if (backoff > cfg.clog_backoff_max_ns) backoff = cfg.clog_backoff_max_ns;
    backoff += clog_jit;
    bool resched = active && blocked && (is_engine || live);
    ev[i].valid = resched;
    if (resched) {
      ev[i].time = now + backoff;
      ev[i].retry = retries + 1;
    }

    // dispatch through the branch table
    Effects eff;
    std::vector<int32_t> new_state(wl.state_width);
    const int32_t* row = &node_state[static_cast<size_t>(dst) * wl.state_width];
    std::memcpy(new_state.data(), row, wl.state_width * sizeof(int32_t));
    Ctx ctx{now, dst, row, args, src, draw, pay};
    int32_t safe_kind = kind < 0 ? 0 : kind;
    int32_t max_kind = FIRST_USER_KIND + wl.n_handlers - 1;
    if (safe_kind > max_kind) safe_kind = max_kind;
    if (safe_kind >= FIRST_USER_KIND) {
      wl.handler(safe_kind - FIRST_USER_KIND, ctx, new_state.data(), &eff);
    } else {
      switch (safe_kind) {
        case KIND_KILL: eff.kill = args[0]; break;
        case KIND_RESTART: {
          eff.restart = args[0];
          Emit e;  // reborn node re-runs on_init (engine _b_restart)
          e.valid = true;
          e.kind = FIRST_USER_KIND;
          e.dst = args[0];
          eff.emits.push_back(e);
          break;
        }
        case KIND_CLOG: eff.clog_a = args[0]; eff.clog_b = args[1]; eff.clog_set = 1; break;
        case KIND_UNCLOG: eff.clog_a = args[0]; eff.clog_b = args[1]; eff.clog_set = 0; break;
        case KIND_CLOG_NODE: eff.clog_a = args[0]; eff.clog_b = -1; eff.clog_set = 1; break;
        case KIND_UNCLOG_NODE: eff.clog_a = args[0]; eff.clog_b = -1; eff.clog_set = 0; break;
        case KIND_HALT: eff.halt = true; break;
        case KIND_PAUSE: eff.pause_node = args[0]; eff.pause_set = 1; break;
        case KIND_RESUME: eff.pause_node = args[0]; eff.pause_set = 0; break;
        default: break;  // NOP
      }
    }

    // apply node state
    if (dispatch) {
      std::memcpy(&node_state[static_cast<size_t>(dst) * wl.state_width],
                  new_state.data(), wl.state_width * sizeof(int32_t));
    }

    // operation-history append (engine: user_dispatch & rec_valid; the
    // j-th valid record takes slot hist_count + j, a full buffer drops)
    if (dispatch && !is_engine) {
      for (const Rec& r : eff.recs) {
        if (!r.when) continue;
        if (hist_count < hist_cap) {
          int32_t* w = &hist_word[static_cast<size_t>(hist_count) * 5];
          w[0] = r.op;
          w[1] = r.key;
          w[2] = r.arg;
          w[3] = dst;
          w[4] = r.ok;
          hist_t[hist_count] = now;
          hist_count++;
        } else {
          hist_drop++;
        }
      }
    }

    // chaos effects
    int32_t kill_id = dispatch ? eff.kill : -1;
    int32_t restart_id = dispatch ? eff.restart : -1;
    if (kill_id >= 0 && kill_id < wl.n_nodes) {
      alive[kill_id] = 0;
      epoch[kill_id] += 1;
    }
    if (restart_id >= 0 && restart_id < wl.n_nodes) {
      alive[restart_id] = 1;
      epoch[restart_id] += 1;
      // the reborn node restarts from the workload's initial rows, not
      // zeros (engine: node_state reset to init_rows on restart) —
      // EXCEPT durable columns, which survive the crash (the FsSim
      // power-fail analog, Workload.durable_cols)
      for (int32_t u = 0; u < wl.state_width; u++) {
        if (u < static_cast<int32_t>(durable.size()) && durable[u]) continue;
        node_state[static_cast<size_t>(restart_id) * wl.state_width + u] =
            init_state[static_cast<size_t>(restart_id) * wl.state_width + u];
      }
    }
    int32_t pause_id = dispatch ? eff.pause_node : -1;
    if (pause_id >= 0 && pause_id < wl.n_nodes)
      paused[pause_id] = eff.pause_set == 1;
    // kill/restart clears paused (fresh incarnation runs)
    if (kill_id >= 0 && kill_id < wl.n_nodes) paused[kill_id] = 0;
    if (restart_id >= 0 && restart_id < wl.n_nodes) paused[restart_id] = 0;
    int32_t clog_set = dispatch ? eff.clog_set : -1;
    if (clog_set >= 0) {
      for (int32_t a = 0; a < wl.n_nodes; a++) {
        for (int32_t b = 0; b < wl.n_nodes; b++) {
          bool pair_sel = (a == eff.clog_a && b == eff.clog_b) ||
                          (a == eff.clog_b && b == eff.clog_a);
          bool node_sel = eff.clog_b < 0 && (a == eff.clog_a || b == eff.clog_a);
          if (pair_sel || node_sel)
            clog[static_cast<size_t>(a) * wl.n_nodes + b] = clog_set == 1;
        }
      }
    }
    bool was_halted = halted;
    halted = halted || (dispatch && eff.halt) || (has_event && over_limit);
    if (halted && !was_halted)
      halt_time = now < time_limit ? now : time_limit;

    // translate emits (static slot index -> latency/loss purposes)
    int32_t n_sends = 0;
    std::vector<Emit>& em = eff.emits;
    int free_cursor = 0;  // index into the free-slot sequence
    // free slots in pool order (flatnonzero)
    std::vector<int64_t> free;
    for (int64_t j = 0; j < cfg.pool_size && static_cast<int32_t>(free.size()) < wl.max_emits; j++)
      if (!ev[j].valid) free.push_back(j);
    for (size_t slot = 0; slot < em.size(); slot++) {
      const Emit& e = em[slot];
      uint32_t lat_bits, loss_bits;
      draw.bits2(kPurposeLatency + static_cast<uint32_t>(slot), &lat_bits,
                 &loss_bits);
      uint32_t span = static_cast<uint32_t>(cfg.lat_max_ns - cfg.lat_min_ns);
      if (span == 0) span = 1;
      int64_t latency = cfg.lat_min_ns + static_cast<int64_t>(lat_bits % span);
      bool lost = e.send && static_cast<uint64_t>(loss_bits) < cfg.loss_u32;
      bool e_valid = dispatch && e.valid && !lost;
      if (e.send && e_valid && !(e.dst >= 0 && e.dst < wl.n_nodes && alive[e.dst]))
        e_valid = false;
      if (dispatch && e.valid && e.send) n_sends++;
      if (!e_valid) continue;
      if (free_cursor >= static_cast<int>(free.size())) {
        overflow += 1;  // pool full: dropped (engine `dropped`)
        continue;
      }
      int64_t j = free[free_cursor++];
      Event& ne = ev[j];
      ne.valid = true;
      ne.time = now_after + (e.send ? latency : e.delay);
      if (time_quantum > 1) ne.time -= ne.time % time_quantum;
      ne.kind = e.kind;
      ne.node = e.dst;
      ne.src = e.send ? dst : -1;
      ne.epoch = e.kind < FIRST_USER_KIND ? 0
                 : (e.dst >= 0 && e.dst < wl.n_nodes ? epoch[e.dst] : 0);
      ne.retry = 0;
      std::memcpy(ne.args, e.args, sizeof(ne.args));
      if (W > 0)
        std::memcpy(&ev_pay[static_cast<size_t>(j) * W], e.pay,
                    W * sizeof(int32_t));
    }
    msg_count += n_sends;
    if (dispatch) {
      trace_fold(now, kind, dst, args, pay);
    }
    now = now_after;
    step += 1;
  }
};

struct Entry {
  SetParams set_params;
  MakeWorkload make;
};

// function-local, so that it exists before any workload file's registrar
std::map<std::string, Entry>& registry() {
  static std::map<std::string, Entry> r;
  return r;
}

}  // namespace

bool register_workload(const char* name, SetParams set_params,
                       MakeWorkload make) {
  registry()[name] = Entry{set_params, make};
  return true;
}

}  // namespace benchref

extern "C" {

// Run one seed of the workload registered as `workload` for n_steps,
// with its params (workloads/<name>.py pack order). cfg = {pool_size,
// lat_min, lat_max, loss_u32, proc_min, proc_max, backoff_min,
// backoff_max, time_limit}. Plan rows: n_plan engine-kind events (time,
// kind, node, a0, a1, valid). History buffers: hist_word (hist_cap * 5)
// and hist_t (hist_cap), may be null when hist_cap is 0. out = {now,
// halted, halt_time, overflow, msg_count, hist_count, hist_drop}; returns
// 0, or nonzero on a refused input: 1 no workload of that name, 2 more
// plan rows than the pool holds, 3 a plan row of a user kind, 4 params
// the workload does not take, 5 a payload wider than kMaxPay words (out
// = {the workload's width, kMaxPay}), 6 a durable column outside the
// state row (out[0] = that column).
__attribute__((visibility("default")))
int32_t ref_run(const char* workload, const int64_t* params, int64_t n_params,
                uint64_t seed, int64_t n_steps,
                const int64_t* cfg, int64_t time_quantum, int64_t n_plan,
                const int64_t* plan_time, const int32_t* plan_kind,
                const int32_t* plan_node, const int32_t* plan_a0,
                const int32_t* plan_a1, const uint8_t* plan_valid,
                int64_t hist_cap, int32_t* hist_word, int64_t* hist_t,
                uint64_t* out_trace, int64_t* out) {
  using namespace benchref;
  auto found = registry().find(workload);
  if (found == registry().end()) return 1;
  if (!found->second.set_params(params, n_params)) return 4;
  Workload wl = found->second.make();
  if (wl.payload_words < 0 || wl.payload_words > kMaxPay) {
    out[0] = wl.payload_words;
    out[1] = kMaxPay;
    return 5;
  }
  for (int32_t c : wl.durable_cols) {
    if (c < 0 || c >= wl.state_width) {
      out[0] = c;
      return 6;
    }
  }
  if (wl.n_nodes + n_plan > cfg[0]) return 2;
  Sim sim;
  sim.cfg = Config{cfg[0], cfg[1], cfg[2], static_cast<uint64_t>(cfg[3]),
                   cfg[4], cfg[5], cfg[6], cfg[7], cfg[8]};
  sim.wl = wl;
  sim.seed = seed;
  sim.time_quantum = time_quantum;
  for (int64_t j = 0; j < n_plan; j++) {
    if (plan_kind[j] >= FIRST_USER_KIND) return 3;  // engine kinds only
    Event e{plan_time[j], plan_valid[j] != 0, plan_kind[j], plan_node[j],
            -1, 0, 0, {plan_a0[j], plan_a1[j], 0, 0}};
    sim.plan.push_back(e);
  }
  sim.hist_cap = hist_cap;
  sim.durable.assign(wl.state_width, 0);
  for (int32_t c : wl.durable_cols) sim.durable[c] = 1;
  sim.init();
  for (int64_t s = 0; s < n_steps; s++) sim.do_step();
  if (hist_cap > 0) {
    std::memcpy(hist_word, sim.hist_word.data(), hist_cap * 5 * sizeof(int32_t));
    std::memcpy(hist_t, sim.hist_t.data(), hist_cap * sizeof(int64_t));
  }
  *out_trace = sim.trace;
  out[0] = sim.now;
  out[1] = sim.halted ? 1 : 0;
  out[2] = sim.halt_time;
  out[3] = sim.overflow;
  out[4] = sim.msg_count;
  out[5] = sim.hist_count;
  out[6] = sim.hist_drop;
  return 0;
}

}  // extern "C"
