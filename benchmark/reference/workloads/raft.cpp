// raft election (models/raft.py), registered as "raft". Parameters, in
// the order workloads/raft.py packs them: n_nodes, timeout_min_ns,
// timeout_max_ns.

#include <cstring>

#include "../sim.h"

namespace benchref {
namespace {

struct RaftParams {
  int32_t n_nodes;
  int64_t timeout_min, timeout_max;
};
RaftParams g_raft{5, 150000000, 300000000};

void raft_handler(int32_t h, const Ctx& ctx, int32_t* ns, Effects* eff) {
  const int32_t ROLE = 0, TERM = 1, VOTED = 2, VOTES = 3, TSEQ = 4;
  const int32_t FOLLOWER = 0, CANDIDATE = 1, LEADER = 2;
  const int32_t K_TIMEOUT = FIRST_USER_KIND + 1, K_REQVOTE = FIRST_USER_KIND + 2,
                K_GRANT = FIRST_USER_KIND + 3, K_HEARTBEAT = FIRST_USER_KIND + 4;
  const int32_t majority = g_raft.n_nodes / 2 + 1;
  const int32_t N = g_raft.n_nodes;
  auto arm = [&](int32_t new_seq, bool when) {
    int64_t d = ctx.draw.user_int(g_raft.timeout_min, g_raft.timeout_max, 0);
    eff->emits.push_back(mk_after(d, K_TIMEOUT, ctx.node, new_seq, when));
  };
  switch (h) {
    case 0: {  // on_init
      arm(1, true);
      ns[TSEQ] = 1;
      break;
    }
    case 1: {  // on_timeout
      const int32_t* st = ctx.state;
      bool fire = ctx.args[0] == st[TSEQ] && st[ROLE] != LEADER;
      int32_t term = st[TERM] + 1;
      if (fire) {
        ns[ROLE] = CANDIDATE;
        ns[TERM] = term;
        ns[VOTED] = term;
        ns[VOTES] = 1;
        ns[TSEQ] = st[TSEQ] + 1;
      }
      for (int32_t p = 0; p < N; p++)
        eff->emits.push_back(
            mk_send(p, K_REQVOTE, term, ctx.node, fire && p != ctx.node));
      arm(st[TSEQ] + 1, fire);
      break;
    }
    case 2: {  // on_reqvote
      const int32_t* st = ctx.state;
      int32_t term = ctx.args[0], cand = ctx.args[1];
      int32_t st1[8];
      std::memcpy(st1, st, sizeof(int32_t) * 5);
      bool newer = term > st[TERM];
      if (newer) {
        st1[TERM] = term;
        st1[ROLE] = FOLLOWER;
        st1[VOTES] = 0;
      }
      bool grant = term == st1[TERM] && st1[VOTED] < term;
      std::memcpy(ns, st1, sizeof(int32_t) * 5);
      if (grant) {
        ns[VOTED] = term;
        ns[TSEQ] = st1[TSEQ] + 1;
      }
      eff->emits.push_back(mk_send(cand, K_GRANT, term, 0, grant));
      {
        int64_t d = ctx.draw.user_int(g_raft.timeout_min, g_raft.timeout_max, 0);
        eff->emits.push_back(
            mk_after(d, K_TIMEOUT, ctx.node, st1[TSEQ] + 1, grant));
      }
      break;
    }
    case 3: {  // on_grant
      const int32_t* st = ctx.state;
      int32_t term = ctx.args[0];
      bool counts = st[ROLE] == CANDIDATE && term == st[TERM];
      int32_t votes = counts ? st[VOTES] + 1 : st[VOTES];
      bool wins = counts && votes >= majority;
      ns[VOTES] = votes;
      if (wins) ns[ROLE] = LEADER;
      for (int32_t p = 0; p < N; p++)
        eff->emits.push_back(
            mk_send(p, K_HEARTBEAT, term, 0, wins && p != ctx.node));
      eff->emits.push_back(mk_after(0, KIND_HALT, 0, 0, wins));
      break;
    }
    case 4: {  // on_heartbeat
      const int32_t* st = ctx.state;
      int32_t term = ctx.args[0];
      bool accept = term >= st[TERM];
      if (accept) {
        ns[TERM] = term;
        ns[ROLE] = FOLLOWER;
        ns[TSEQ] = st[TSEQ] + 1;
      }
      arm(st[TSEQ] + 1, accept);
      break;
    }
  }
}

bool set_params(const int64_t* p, int64_t n) {
  if (n != 3) return false;
  g_raft = {static_cast<int32_t>(p[0]), p[1], p[2]};
  return true;
}

Workload make() {
  return Workload{g_raft.n_nodes, 6, 5, g_raft.n_nodes + 1, raft_handler};
}

const bool kRegistered = register_workload("raft", &set_params, &make);

}  // namespace
}  // namespace benchref
