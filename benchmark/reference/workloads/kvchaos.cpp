// kvchaos (models/kvchaos.py), registered as "kvchaos": primary-backup
// KV store under a scheduled replica kill/restart; payload mode carries
// two client-drawn value words through WRITE/REPL messages (state_width
// 6, payload_words 2). Record mode (record=1) adds the client's
// write/read history and the READ probe; bug=1 plants the lost-write
// fault (on_join forgets the commit point). Parameters, in the order
// workloads/kvchaos.py packs them: writes, n_replicas, retx_ns,
// client_retx_ns, chaos, record, bug (payload mode stays off).

#include "../sim.h"

namespace benchref {
namespace {

struct KvParams {
  int32_t writes, n_replicas;
  int64_t retx_ns, client_retx_ns;
  int32_t chaos, payload, record, bug;
};
KvParams g_kv{20, 4, 40000000, 100000000, 1, 0, 0, 0};

// check/history.py op and status codes
constexpr int32_t OP_WRITE = 1, OP_READ = 2;
constexpr int32_t OK_PENDING = -1, OK_OK = 1;

void kvchaos_handler(int32_t h, const Ctx& ctx, int32_t* ns, Effects* eff) {
  const int32_t PRIMARY = 0;
  const int32_t K_WRITE = FIRST_USER_KIND + 1, K_REPL = FIRST_USER_KIND + 2,
                K_ACK = FIRST_USER_KIND + 3, K_COMMIT = FIRST_USER_KIND + 4,
                K_RETX = FIRST_USER_KIND + 5, K_CRETX = FIRST_USER_KIND + 6,
                K_FIN = FIRST_USER_KIND + 7, K_JOIN = FIRST_USER_KIND + 8,
                K_JRETX = FIRST_USER_KIND + 9, K_READ = FIRST_USER_KIND + 10,
                K_READRESP = FIRST_USER_KIND + 11;
  const bool record = g_kv.record != 0;
  auto rec = [&](int32_t op, int32_t arg, int32_t ok, bool when) {
    if (record) eff->recs.push_back(Rec{when, op, 0, arg, ok});
  };
  const int32_t P_KILL_AT = 0, P_KILL_WHO = 1, P_REVIVE = 2;
  const int32_t P_VAL0 = 8, P_VAL1 = 9;
  const int32_t R = g_kv.n_replicas;
  const int32_t client = R + 1;
  const int32_t majority = R / 2 + 1;
  const int32_t full_mask = (1 << R) - 1;
  const bool payload = g_kv.payload != 0;
  auto client_value = [&](int32_t* v0, int32_t* v1) {
    *v0 = static_cast<int32_t>(ctx.draw.user(P_VAL0));
    *v1 = static_cast<int32_t>(ctx.draw.user(P_VAL1));
  };
  auto send_pay = [&](Emit e, int32_t p0, int32_t p1) {
    if (payload) {
      e.pay[0] = p0;
      e.pay[1] = p1;
    }
    eff->emits.push_back(e);
  };
  // slots 0..R-1: REPL sends gated per-replica on the ack mask
  auto replicate = [&](int32_t seq, bool when, int32_t mask, int32_t p0,
                       int32_t p1) {
    for (int32_t idx = 0; idx < R; idx++)
      send_pay(mk_send(idx + 1, K_REPL, seq, 0,
                       when && (((mask >> idx) & 1) == 0)),
               p0, p1);
  };
  auto maybe_halt = [&](int32_t committed, int32_t mask, int32_t fin) {
    eff->emits.push_back(mk_after(
        0, KIND_HALT, 0, 0,
        committed >= g_kv.writes && mask == full_mask && fin > 0));
  };
  switch (h) {
    case 0: {  // on_init
      bool is_client = ctx.node == client;
      bool is_replica = ctx.node >= 1 && ctx.node <= R;
      int32_t v0 = 0, v1 = 0;
      if (payload) client_value(&v0, &v1);
      send_pay(mk_send(PRIMARY, K_WRITE, 1, 0, is_client), v0, v1);
      rec(OP_WRITE, 1, OK_PENDING, is_client);
      eff->emits.push_back(
          mk_after(g_kv.client_retx_ns, K_CRETX, client, 0, is_client));
      eff->emits.push_back(
          mk_send(PRIMARY, K_JOIN, ctx.node, 0, is_replica));
      eff->emits.push_back(
          mk_after(g_kv.retx_ns, K_JRETX, ctx.node, 0, is_replica));
      if (g_kv.chaos) {
        int64_t who = ctx.draw.user_int(1, 1 + R, P_KILL_WHO);
        int64_t at = ctx.draw.user_int(20000000, 300000000, P_KILL_AT);
        int64_t revive = ctx.draw.user_int(100000000, 600000000, P_REVIVE);
        eff->emits.push_back(mk_after(
            at, KIND_KILL, 0, static_cast<int32_t>(who), is_client));
        eff->emits.push_back(mk_after(
            at + revive, KIND_RESTART, 0, static_cast<int32_t>(who), is_client));
      }
      break;
    }
    case 1: {  // on_write at primary
      int32_t seq = ctx.args[0];
      const int32_t* st = ctx.state;
      bool fresh = seq > st[0] && seq > st[1];
      if (fresh) {
        ns[1] = seq;
        ns[2] = 0;
        if (payload) {
          // the first WRITE to arrive for a seq fixes its value
          ns[4] = ctx.pay[0];
          ns[5] = ctx.pay[1];
        }
      }
      int32_t p0 = payload ? ns[4] : 0, p1 = payload ? ns[5] : 0;
      replicate(seq, fresh, 0, p0, p1);
      eff->emits.push_back(
          mk_after(g_kv.retx_ns, K_RETX, PRIMARY, seq, fresh));
      break;
    }
    case 2: {  // on_repl at replica
      int32_t seq = ctx.args[0];
      const int32_t* st = ctx.state;
      bool fresh = seq > st[0];
      ns[0] = st[0] > seq ? st[0] : seq;
      ns[1] = st[1] + 1;
      if (payload && fresh) {
        ns[2] = ctx.pay[0];
        ns[3] = ctx.pay[1];
      }
      eff->emits.push_back(mk_send(PRIMARY, K_ACK, seq, ctx.node));
      break;
    }
    case 3: {  // on_ack at primary
      int32_t seq = ctx.args[0], who = ctx.args[1];
      const int32_t* st = ctx.state;
      int32_t bit = int32_t{1} << (who - 1);
      bool current = seq == st[1];
      int32_t mask = current ? (st[2] | bit) : st[2];
      int32_t acks = 0;
      for (int32_t idx = 0; idx < R; idx++) acks += (mask >> idx) & 1;
      bool committed_now = current && seq > st[0] && acks >= majority;
      int32_t committed = committed_now ? seq : st[0];
      ns[0] = committed;
      ns[2] = mask;
      eff->emits.push_back(mk_send(client, K_COMMIT, committed, 0,
                                   current && committed >= seq));
      maybe_halt(committed, mask, st[3]);
      break;
    }
    case 4: {  // on_commit at client
      int32_t seq = ctx.args[0];
      const int32_t* st = ctx.state;
      bool fresh = seq > st[0];
      if (fresh) ns[0] = seq;
      bool done = seq >= g_kv.writes;
      int32_t v0 = 0, v1 = 0;
      if (payload) client_value(&v0, &v1);
      send_pay(mk_send(PRIMARY, K_WRITE, seq + 1, 0, fresh && !done), v0, v1);
      eff->emits.push_back(mk_send(PRIMARY, K_FIN, 0, 0, fresh && done));
      if (record) {
        // close the pending write, then probe it with a READ
        rec(OP_WRITE, seq, OK_OK, fresh);
        rec(OP_READ, 0, OK_PENDING, fresh);
        eff->emits.push_back(mk_send(PRIMARY, K_READ, seq, 0, fresh));
        rec(OP_WRITE, seq + 1, OK_PENDING, fresh && !done);
      }
      break;
    }
    case 5: {  // on_retx at primary
      int32_t seq = ctx.args[0];
      const int32_t* st = ctx.state;
      bool current = seq == st[1];
      bool pending_repl = current && st[2] != full_mask;
      bool pending_commit = current && st[0] >= seq;
      replicate(seq, pending_repl, st[2], payload ? st[4] : 0,
                payload ? st[5] : 0);
      eff->emits.push_back(
          mk_send(client, K_COMMIT, st[0], 0, pending_commit));
      eff->emits.push_back(mk_after(g_kv.retx_ns, K_RETX, PRIMARY, seq,
                                    pending_repl || pending_commit));
      break;
    }
    case 6: {  // on_cretx at client
      const int32_t* st = ctx.state;
      bool waiting = st[0] < g_kv.writes;
      int32_t v0 = 0, v1 = 0;
      if (payload) client_value(&v0, &v1);
      send_pay(mk_send(PRIMARY, K_WRITE, st[0] + 1, 0, waiting), v0, v1);
      eff->emits.push_back(mk_send(PRIMARY, K_FIN, 0, 0, !waiting));
      eff->emits.push_back(
          mk_after(g_kv.client_retx_ns, K_CRETX, client, 0, true));
      break;
    }
    case 7: {  // on_fin at primary
      const int32_t* st = ctx.state;
      ns[3] = 1;
      maybe_halt(st[0], st[2], 1);
      break;
    }
    case 8: {  // on_join at primary
      int32_t who = ctx.args[0];
      const int32_t* st = ctx.state;
      int32_t bit = int32_t{1} << (who - 1);
      ns[2] = st[2] & ~bit;
      if (g_kv.bug) ns[0] = 0;  // planted: re-admission forgets the commit point
      // the retx timer may have died while the mask was full: re-arm
      eff->emits.push_back(
          mk_after(g_kv.retx_ns, K_RETX, PRIMARY, st[1], st[1] > 0));
      break;
    }
    case 9: {  // on_jretx at replica
      const int32_t* st = ctx.state;
      bool behind = st[0] == 0;
      eff->emits.push_back(mk_send(PRIMARY, K_JOIN, ctx.node, 0, behind));
      eff->emits.push_back(
          mk_after(g_kv.retx_ns, K_JRETX, ctx.node, 0, behind));
      break;
    }
    case 10: {  // on_read at primary: answer with the commit point
      eff->emits.push_back(
          mk_send(client, K_READRESP, ctx.args[0], ctx.state[0]));
      break;
    }
    case 11: {  // on_readresp at client: stale-rseq gate
      int32_t rseq = ctx.args[0], committed = ctx.args[1];
      bool fresh_r = rseq > ctx.state[1];
      if (fresh_r) ns[1] = rseq;
      rec(OP_READ, committed, OK_OK, fresh_r);
      break;
    }
  }
}

bool set_params(const int64_t* p, int64_t n) {
  if (n != 7) return false;
  g_kv = {static_cast<int32_t>(p[0]), static_cast<int32_t>(p[1]), p[2], p[3],
          static_cast<int32_t>(p[4]), 0, static_cast<int32_t>(p[5]),
          static_cast<int32_t>(p[6])};
  return true;
}

// max_emits = max(n_replicas + 2, 6)
Workload make() {
  int32_t k = g_kv.n_replicas + 2;
  if (k < 6) k = 6;
  return Workload{g_kv.n_replicas + 2, g_kv.payload ? 6 : 4, 12, k,
                  kvchaos_handler, g_kv.payload ? 2 : 0};
}

const bool kRegistered = register_workload("kvchaos", &set_params, &make);

}  // namespace
}  // namespace benchref
