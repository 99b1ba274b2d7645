// raftlog (models/raftlog.py): Raft log replication under one leader
// crash and restart, registered as "raftlog". AppendEntries carries the
// leader's whole log as its payload (payload_words = n_writes). With
// durable=1 the Figure 2 columns (TERM, VOTED, LOGLEN, LOG0..) survive a
// restart; record=1 adds the history: OP_ELECT and OP_COMMIT, and with
// durable=1 OP_SYNCED and OP_RECOVER. Parameters, in the order
// workloads/raftlog.py packs them: n_nodes, n_writes, timeout_min_ns,
// timeout_max_ns, propose_ns, retx_ns, chaos, durable, record.
//
// Emit-row order mirrors the program's EmitBuilder (the slot index keys
// the per-slot latency and loss draws); draw purposes are coordinates, so
// draw call order is free. The program's rows and gates for injected disk
// faults (fsync errors) are left out: with no such fault they are never
// valid, and no row follows them.

#include <vector>

#include "../sim.h"

namespace benchref {
namespace {

struct RaftLogParams {
  int32_t n_nodes, n_writes;
  int64_t timeout_min, timeout_max, propose_ns, retx_ns;
  int32_t chaos, durable, record;
};
RaftLogParams g_rl{5, 4, 150000000, 300000000, 20000000, 60000000, 1, 0, 0};

// check/history.py OP_USER + 0..3
constexpr int32_t OP_ELECT = 16, OP_COMMIT = 17, OP_SYNCED = 18,
                  OP_RECOVER = 19;
constexpr int32_t OK_OK = 1;

constexpr int32_t ROLE = 0, TERM = 1, VOTED = 2, VOTES = 3, TSEQ = 4,
                  LOGLEN = 5, COMMIT = 6, ACKS = 7, LOG0 = 8;

void raftlog_handler(int32_t h, const Ctx& ctx, int32_t* ns, Effects* eff) {
  const int32_t FOLLOWER = 0, CANDIDATE = 1, LEADER = 2;
  const int32_t K_TIMEOUT = FIRST_USER_KIND + 1,
                K_REQVOTE = FIRST_USER_KIND + 2,
                K_GRANT = FIRST_USER_KIND + 3,
                K_APPEND = FIRST_USER_KIND + 4,
                K_ACKAPP = FIRST_USER_KIND + 5,
                K_PROPOSE = FIRST_USER_KIND + 6,
                K_RETX = FIRST_USER_KIND + 7;
  const int32_t P_TIMEOUT = 0, P_VALUE = 1, P_KILL_AT = 2, P_KILL_WHO = 3,
                P_REVIVE = 4;
  const int32_t N = g_rl.n_nodes, W = g_rl.n_writes;
  const int32_t majority = N / 2 + 1;
  const bool record = g_rl.record != 0;
  const bool rec_store = record && g_rl.durable != 0;
  auto rec = [&](int32_t op, int32_t key, int32_t arg, bool when) {
    eff->recs.push_back(Rec{when, op, key, arg, OK_OK});
  };
  // value = low 8 bits, term = the remaining 23
  auto entry_term = [](int32_t e) { return e >> 8; };
  auto lastterm = [&](const int32_t* st) {
    int32_t acc = 0;
    for (int32_t j = 0; j < W; j++)
      if (st[LOGLEN] == j + 1) acc = entry_term(st[LOG0 + j]);
    return acc;
  };
  auto arm = [&](int32_t new_seq, bool when) {
    int64_t d = ctx.draw.user_int(g_rl.timeout_min, g_rl.timeout_max, P_TIMEOUT);
    eff->emits.push_back(mk_after(d, K_TIMEOUT, ctx.node, new_seq, when));
  };
  auto send_appends = [&](const int32_t* st, int32_t term, bool when) {
    int32_t idx = st[LOGLEN] - 1;
    for (int32_t p = 0; p < N; p++) {
      Emit e = mk_send(p, K_APPEND, term, idx, when && p != ctx.node);
      e.args[2] = st[COMMIT];
      e.args[3] = ctx.node;
      for (int32_t j = 0; j < W; j++) e.pay[j] = st[LOG0 + j];
      eff->emits.push_back(e);
    }
  };
  const int32_t* st = ctx.state;
  switch (h) {
    case 0: {  // on_init; a re-init at now > 0 is a restarted node
      arm(1, true);
      if (rec_store) rec(OP_RECOVER, 0, st[LOGLEN], ctx.now > 0);
      if (g_rl.chaos) {
        bool first = ctx.node == 0 && ctx.now == 0;
        int32_t who =
            static_cast<int32_t>(ctx.draw.user_int(0, N, P_KILL_WHO));
        int64_t at = ctx.draw.user_int(200000000, 500000000, P_KILL_AT);
        int64_t revive = ctx.draw.user_int(100000000, 600000000, P_REVIVE);
        eff->emits.push_back(mk_after(at, KIND_KILL, 0, who, first));
        eff->emits.push_back(mk_after(at + revive, KIND_RESTART, 0, who, first));
      }
      ns[TSEQ] = 1;
      break;
    }
    case 1: {  // on_timeout
      bool fire = ctx.args[0] == st[TSEQ] && st[ROLE] != LEADER;
      int32_t term = st[TERM] + 1;
      if (fire) {
        ns[ROLE] = CANDIDATE;
        ns[TERM] = term;
        ns[VOTED] = term;
        ns[VOTES] = 1;
        ns[TSEQ] = st[TSEQ] + 1;
      }
      for (int32_t p = 0; p < N; p++) {
        Emit e = mk_send(p, K_REQVOTE, term, ctx.node, fire && p != ctx.node);
        e.args[2] = st[LOGLEN];
        e.args[3] = lastterm(st);
        eff->emits.push_back(e);
      }
      arm(st[TSEQ] + 1, fire);
      break;
    }
    case 2: {  // on_reqvote
      int32_t term = ctx.args[0], cand = ctx.args[1];
      int32_t c_len = ctx.args[2], c_lt = ctx.args[3];
      std::vector<int32_t> st1(st, st + LOG0 + W);
      bool newer = term > st[TERM];
      if (newer) {
        st1[TERM] = term;
        st1[ROLE] = FOLLOWER;
        st1[VOTES] = 0;
      }
      int32_t my_lt = lastterm(st1.data());
      bool up_to_date =
          c_lt > my_lt || (c_lt == my_lt && c_len >= st1[LOGLEN]);
      bool grant = term == st1[TERM] && st1[VOTED] < term && up_to_date;
      for (int32_t u = 0; u < LOG0 + W; u++) ns[u] = st1[u];
      if (grant) {
        ns[VOTED] = term;
        ns[TSEQ] = st1[TSEQ] + 1;
      }
      eff->emits.push_back(mk_send(cand, K_GRANT, term, 0, grant));
      arm(st1[TSEQ] + 1, grant);
      break;
    }
    case 3: {  // on_grant
      int32_t term = ctx.args[0];
      bool counts = st[ROLE] == CANDIDATE && term == st[TERM];
      int32_t votes = counts ? st[VOTES] + 1 : st[VOTES];
      bool wins = counts && votes >= majority;
      ns[VOTES] = votes;
      if (wins) {
        ns[ROLE] = LEADER;
        // win-time re-stamp of the uncommitted suffix
        for (int32_t j = 0; j < W; j++)
          if (j >= ns[COMMIT] && j < ns[LOGLEN])
            ns[LOG0 + j] = (ns[LOG0 + j] & 0xFF) | (term << 8);
        ns[ACKS] = ns[LOGLEN] > ns[COMMIT] ? (1 << ctx.node) : 0;
      }
      send_appends(ns, term, wins);
      eff->emits.push_back(
          mk_after(g_rl.propose_ns, K_PROPOSE, ctx.node, term, wins));
      eff->emits.push_back(
          mk_after(g_rl.retx_ns, K_RETX, ctx.node, term, wins));
      if (record) rec(OP_ELECT, term, ctx.node, wins);
      break;
    }
    case 4: {  // on_append
      int32_t term = ctx.args[0], idx = ctx.args[1], l_commit = ctx.args[2];
      int32_t leader = ctx.args[3];
      bool ok = term >= st[TERM];
      bool newer_term = term > st[TERM];
      if (ok) {
        ns[TERM] = term;
        ns[ROLE] = FOLLOWER;
        ns[TSEQ] = st[TSEQ] + 1;
      }
      bool adopt = ok && idx >= 0 && (newer_term || idx + 1 >= st[LOGLEN]);
      if (adopt) {
        for (int32_t j = 0; j < W; j++)
          if (j <= idx) ns[LOG0 + j] = ctx.pay[j];
        ns[LOGLEN] = idx + 1;
      }
      if (ok && l_commit > ns[COMMIT]) ns[COMMIT] = l_commit;
      {
        Emit e = mk_send(leader, K_ACKAPP, term, idx, adopt);
        e.args[2] = ctx.node;
        eff->emits.push_back(e);
      }
      arm(st[TSEQ] + 1, ok);
      // a log-length change that a sync committed
      if (rec_store) rec(OP_SYNCED, 0, idx + 1, adopt && idx + 1 != st[LOGLEN]);
      break;
    }
    case 5: {  // on_ackapp
      int32_t term = ctx.args[0], idx = ctx.args[1], frm = ctx.args[2];
      bool counts = st[ROLE] == LEADER && term == st[TERM] &&
                    idx == st[LOGLEN] - 1 && st[COMMIT] < st[LOGLEN];
      int32_t acks = counts ? (st[ACKS] | (1 << frm)) : st[ACKS];
      int32_t n_acks = 0;
      for (int32_t p = 0; p < N; p++) n_acks += (acks >> p) & 1;
      bool commit_now = counts && n_acks >= majority;
      ns[ACKS] = acks;
      if (commit_now) ns[COMMIT] = idx + 1;
      send_appends(ns, term, commit_now);
      // one record per newly committed index: the committed value byte
      if (record)
        for (int32_t j = 0; j < W; j++)
          rec(OP_COMMIT, j, ns[LOG0 + j] & 0xFF,
              commit_now && j >= st[COMMIT] && j <= idx);
      eff->emits.push_back(
          mk_after(0, KIND_HALT, 0, 0, commit_now && ns[COMMIT] == W));
      break;
    }
    case 6: {  // on_propose
      int32_t term = ctx.args[0];
      bool alive_leader = st[ROLE] == LEADER && term == st[TERM];
      bool can = alive_leader && st[COMMIT] == st[LOGLEN] && st[LOGLEN] < W;
      int32_t value = static_cast<int32_t>(ctx.draw.user(P_VALUE) & 0xFF);
      int32_t entry = value | (st[TERM] << 8);
      if (can) {
        for (int32_t j = 0; j < W; j++)
          if (st[LOGLEN] == j) ns[LOG0 + j] = entry;
        ns[LOGLEN] = st[LOGLEN] + 1;
        ns[ACKS] = 1 << ctx.node;
      }
      send_appends(ns, term, can);
      eff->emits.push_back(
          mk_after(g_rl.propose_ns, K_PROPOSE, ctx.node, term, alive_leader));
      if (rec_store) rec(OP_SYNCED, 0, st[LOGLEN] + 1, can);
      break;
    }
    case 7: {  // on_retx
      int32_t term = ctx.args[0];
      bool alive_leader = st[ROLE] == LEADER && term == st[TERM];
      bool send = alive_leader && st[LOGLEN] > 0;
      send_appends(st, term, send);
      eff->emits.push_back(
          mk_after(g_rl.retx_ns, K_RETX, ctx.node, term, alive_leader));
      break;
    }
  }
}

bool set_params(const int64_t* p, int64_t n) {
  if (n != 9) return false;
  g_rl = {static_cast<int32_t>(p[0]), static_cast<int32_t>(p[1]), p[2], p[3],
          p[4], p[5], static_cast<int32_t>(p[6]), static_cast<int32_t>(p[7]),
          static_cast<int32_t>(p[8])};
  return true;
}

// max_emits: on_grant's N appends and two timers
Workload make() {
  const int32_t W = g_rl.n_writes;
  Workload wl{g_rl.n_nodes, LOG0 + W, 8, g_rl.n_nodes + 2, raftlog_handler, W};
  if (g_rl.durable) {
    wl.durable_cols = {TERM, VOTED, LOGLEN};
    for (int32_t j = 0; j < W; j++) wl.durable_cols.push_back(LOG0 + j);
  }
  return wl;
}

const bool kRegistered = register_workload("raftlog", &set_params, &make);

}  // namespace
}  // namespace benchref
