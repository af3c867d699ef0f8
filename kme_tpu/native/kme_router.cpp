// Native router for the sequential engine (SeqRouter's C++ twin).
//
// The seq engine needs no conflict analysis — routing is pure id
// mapping (dense aid/sid spaces, oid -> lane for cancels, host-reject
// edge semantics). The Python loop
// costs ~2us/message (~0.8s on the 400k soak); this does the same work
// over columnar int64 arrays in ~tens of ns/message. Semantics
// authority: SeqRouter.route (runtime/seqsession.py); equality pinned
// by tests/test_seq_engine.py and tests/test_symbol_lifecycle.py.
//
// The symbol lifecycle is SeqRouter's (its docstring): a lane is bound
// by an ADD_SYMBOL, released by an accepted PAYOUT, the lowest free
// lane goes to the next new id, and a trade, cancel or barrier naming
// an id that holds no lane is host-rejected and takes none.
//
// An order's route (SeqRouter's docstring): written when its trade is
// routed, stamped with that plan's ordinal, gone when the session says
// the order left the book (kme_router_drop_batch) or its symbol is wiped.

#include <algorithm>
#include <chrono>
#include <climits>
#include <cstdint>
#include <functional>
#include <unordered_map>
#include <unordered_set>
#include <vector>

namespace {

// wire opcodes (kme_tpu/opcodes.py)
constexpr int64_t OP_ADD_SYMBOL = 0, OP_REMOVE_SYMBOL = 1, OP_BUY = 2,
                  OP_SELL = 3, OP_CANCEL = 4, OP_CREATE_BALANCE = 100,
                  OP_TRANSFER = 101, OP_PAYOUT = 200;
// seq lane acts (kme_tpu/engine/seq.py)
constexpr int32_t L_BUY = 1, L_SELL = 2, L_CANCEL = 3, L_CREATE = 4,
                  L_TRANSFER = 5, L_ADD_SYMBOL = 6, L_PAYOUT_YES = 7,
                  L_PAYOUT_NO = 8, L_REMOVE_SYMBOL = 9;

constexpr int32_t RT_OK = 0, RT_CAP_ACCOUNTS = 1, RT_CAP_SYMBOLS = 2;

// an order's symbol, and the plan that wrote the route (0: imported)
struct Route {
  int64_t sid, plan;
};

struct Router {
  int64_t S, A;
  std::unordered_map<int64_t, int32_t> aid_idx;
  std::unordered_map<int64_t, int32_t> sid_lane;
  std::unordered_map<int64_t, Route> oid_sid;
  std::unordered_set<int64_t> dead;  // kme_router_drop_batch's scratch
  int64_t routes_dropped = 0;

  // route outputs (valid until the next call)
  std::vector<int64_t> o_msg, o_oid;
  std::vector<int32_t> o_act, o_aidx, o_price, o_size, o_lane;
  std::vector<int64_t> o_rej;
  int64_t err_value = 0;

  int32_t acct(int64_t aid, bool* ok) {
    auto it = aid_idx.find(aid);
    if (it != aid_idx.end()) return it->second;
    if ((int64_t)aid_idx.size() >= A) {
      *ok = false;
      err_value = aid;
      return 0;
    }
    int32_t idx = (int32_t)aid_idx.size();
    aid_idx.emplace(aid, idx);
    return idx;
  }

  // the symbol lifecycle (SeqRouter's twin): bound ids whose book a
  // REMOVE_SYMBOL took away, the released lanes below the high-water
  // mark `hw` as a min-heap, and the cumulative counts in
  // ROUTER_STATS' order (the level after them, the bound lanes, is
  // read off the map)
  std::unordered_set<int64_t> delisted;
  std::vector<int32_t> free_lanes;
  int32_t hw = 0;
  enum {
    LISTED, SETTLED, RELEASED, REUSED, UNLISTED, PURGE_NS, PURGE_N,
    ROUTES_MADE, CANCELS_ROUTED, CANCELS_HOST_REJECTED, PLANS, N_STATS
  };
  int64_t stats[N_STATS] = {};

  // the lane of `sid`, binding the lowest free one to a new id
  int32_t lane(int64_t sid, bool* ok) {
    auto it = sid_lane.find(sid);
    if (it != sid_lane.end()) return it->second;
    if ((int64_t)sid_lane.size() >= S) {
      *ok = false;
      err_value = sid;
      return 0;
    }
    int32_t l;
    if (!free_lanes.empty()) {
      std::pop_heap(free_lanes.begin(), free_lanes.end(),
                    std::greater<int32_t>());
      l = free_lanes.back();
      free_lanes.pop_back();
      stats[REUSED]++;
    } else {
      l = hw++;
    }
    sid_lane.emplace(sid, l);
    return l;
  }

  void release(int64_t sid, int32_t l) {
    sid_lane.erase(sid);
    free_lanes.push_back(l);
    std::push_heap(free_lanes.begin(), free_lanes.end(),
                   std::greater<int32_t>());
    stats[SETTLED]++;
    stats[RELEASED]++;
  }

  // a wholesale import of sid_lane: the pool is rebuilt from the map
  void rebuild_pool() {
    delisted.clear();
    hw = 0;
    for (auto& kv : sid_lane) hw = std::max(hw, kv.second + 1);
    std::vector<bool> bound(hw, false);
    for (auto& kv : sid_lane) bound[kv.second] = true;
    free_lanes.clear();
    for (int32_t l = 0; l < hw; l++)
      if (!bound[l]) free_lanes.push_back(l);  // ascending: a min-heap
  }

  // resting-oid routes die with the wipe
  void purge(int64_t s) {
    auto t0 = std::chrono::steady_clock::now();
    for (auto it = oid_sid.begin(); it != oid_sid.end();) {
      if (it->second.sid == s)
        it = oid_sid.erase(it);
      else
        ++it;
    }
    stats[PURGE_NS] += std::chrono::duration_cast<std::chrono::nanoseconds>(
                           std::chrono::steady_clock::now() - t0)
                           .count();
    stats[PURGE_N]++;
  }
};

// splitmix64 finalizer — the shared 64-bit mixer of the group
// assignment below and its Python twin (bridge/front.py _mix64). The
// two MUST stay bit-identical: the front's split decision is part of
// the durable stream (each group replays its own MatchIn), so an
// assignment drift would re-home symbols across a version bump.
inline uint64_t mix64(uint64_t z) {
  z += 0x9E3779B97F4A7C15ull;
  z = (z ^ (z >> 30)) * 0xBF58476D1CE4E5B9ull;
  z = (z ^ (z >> 27)) * 0x94D049BB133111EBull;
  return z ^ (z >> 31);
}

// rendezvous (highest-random-weight) choice: every (key, group) pair
// gets an independent score; the max wins. Adding a group moves only
// the keys the new group wins — the consistent-hash property the
// front door needs when N changes.
inline int32_t group_of(uint64_t key, int32_t ngroups, uint64_t salt) {
  int32_t best = 0;
  uint64_t best_score = 0;
  for (int32_t g = 0; g < ngroups; g++) {
    uint64_t score = mix64(key ^ mix64(salt + (uint64_t)g));
    if (g == 0 || score > best_score) {
      best = g;
      best_score = score;
    }
  }
  return best;
}

}  // namespace

extern "C" {

// Columnar group assignment: out[i] = rendezvous group of key[i] among
// ngroups, under `salt` (distinct salts keep the symbol->group and
// account->group spaces independently balanced). Stateless and pure —
// tens of ns/key, same cost profile as kme_router_route.
void kme_group_assign(int64_t n, const int64_t* key, int32_t ngroups,
                      int64_t salt, int32_t* out) {
  if (ngroups <= 1) {
    for (int64_t i = 0; i < n; i++) out[i] = 0;
    return;
  }
  for (int64_t i = 0; i < n; i++)
    out[i] = group_of((uint64_t)key[i], ngroups, (uint64_t)salt);
}

void* kme_router_new(int64_t lanes, int64_t accounts) {
  auto* r = new Router();
  r->S = lanes;
  r->A = accounts;
  return r;
}

void kme_router_free(void* p) { delete static_cast<Router*>(p); }

// Route n messages. Fields arrive as raw int64 values (anything beyond
// int64 never reaches this path: the Python wrapper's array build
// raises OverflowError first and that call falls back to the Python
// router).
// Returns RT_OK or a capacity code (err_value holds the offending id).
int32_t kme_router_route(void* p, int64_t n, const int64_t* action,
                         const int64_t* oid, const int64_t* aid,
                         const int64_t* sid, const int64_t* price,
                         const int64_t* size) {
  Router& r = *static_cast<Router*>(p);
  r.o_msg.clear();
  r.o_oid.clear();
  r.o_act.clear();
  r.o_aidx.clear();
  r.o_price.clear();
  r.o_size.clear();
  r.o_lane.clear();
  r.o_rej.clear();
  r.o_msg.reserve(n);
  bool ok = true;
  const int64_t plan = ++r.stats[Router::PLANS];
  auto emit = [&](int64_t i, int32_t act, int32_t aidx, int32_t ln) {
    r.o_msg.push_back(i);
    r.o_act.push_back(act);
    r.o_aidx.push_back(aidx);
    r.o_price.push_back((int32_t)price[i]);
    r.o_size.push_back((int32_t)size[i]);
    r.o_lane.push_back(ln);
    r.o_oid.push_back(oid[i]);
  };
  auto unlisted = [&](int64_t i) {
    r.o_rej.push_back(i);
    r.stats[Router::UNLISTED]++;
  };
  for (int64_t i = 0; i < n; i++) {
    int64_t a = action[i];
    if (a == OP_BUY || a == OP_SELL) {
      // mutation ORDER matches the Python authority (oid_sid, then
      // acct) so partial map state after a CapacityError is identical
      // either way (ADVICE r4)
      auto sl = r.sid_lane.find(sid[i]);
      if (sl == r.sid_lane.end()) {
        unlisted(i);
        continue;
      }
      r.oid_sid[oid[i]] = Route{sid[i], plan};
      r.stats[Router::ROUTES_MADE]++;
      int32_t ai = r.acct(aid[i], &ok);
      if (!ok) return RT_CAP_ACCOUNTS;
      emit(i, a == OP_BUY ? L_BUY : L_SELL, ai, sl->second);
    } else if (a == OP_CANCEL) {
      auto it = r.oid_sid.find(oid[i]);
      if (it == r.oid_sid.end()) {
        r.o_rej.push_back(i);
        r.stats[Router::CANCELS_HOST_REJECTED]++;
        continue;
      }
      r.stats[Router::CANCELS_ROUTED]++;
      auto sl = r.sid_lane.find(it->second.sid);
      if (sl == r.sid_lane.end()) {  // only an imported map can say so
        unlisted(i);
        continue;
      }
      int32_t ai = r.acct(aid[i], &ok);
      if (!ok) return RT_CAP_ACCOUNTS;
      emit(i, L_CANCEL, ai, sl->second);
    } else if (a == OP_CREATE_BALANCE) {
      int32_t ai = r.acct(aid[i], &ok);
      if (!ok) return RT_CAP_ACCOUNTS;
      emit(i, L_CREATE, ai, 0);
    } else if (a == OP_TRANSFER) {
      int32_t ai = r.acct(aid[i], &ok);
      if (!ok) return RT_CAP_ACCOUNTS;
      emit(i, L_TRANSFER, ai, 0);
    } else if (a == OP_ADD_SYMBOL) {
      if (sid[i] < 0) {
        r.o_rej.push_back(i);
        continue;
      }
      bool fresh = r.sid_lane.find(sid[i]) == r.sid_lane.end();
      int32_t ln = r.lane(sid[i], &ok);
      if (!ok) return RT_CAP_SYMBOLS;
      // the device accepts it where the book does not exist
      if (fresh || r.delisted.erase(sid[i])) r.stats[Router::LISTED]++;
      emit(i, L_ADD_SYMBOL, 0, ln);
    } else if (a == OP_REMOVE_SYMBOL || a == OP_PAYOUT) {
      // abs(INT64_MIN) = 2^63 can never be a (wrapped) Java-long map
      // key, so the Python authority host-rejects it — and negating it
      // here would be signed-overflow UB (same guard as kme_host.cpp)
      int64_t s = 0;
      auto it = r.sid_lane.end();
      if (sid[i] != INT64_MIN) {
        s = sid[i] < 0 ? -sid[i] : sid[i];
        it = r.sid_lane.find(s);
      }
      if (it == r.sid_lane.end()) {
        unlisted(i);
        continue;
      }
      int32_t ln = it->second;
      int32_t act = a == OP_REMOVE_SYMBOL
                        ? L_REMOVE_SYMBOL
                        : (sid[i] >= 0 ? L_PAYOUT_YES : L_PAYOUT_NO);
      emit(i, act, 0, ln);
      r.purge(s);
      if (r.delisted.count(s)) continue;  // no book: the device rejects
      if (a == OP_REMOVE_SYMBOL)
        r.delisted.insert(s);  // its positions stay, so the lane does
      else
        r.release(s, ln);  // books wiped, positions zeroed
    } else {
      r.o_rej.push_back(i);
    }
  }
  return RT_OK;
}

int64_t kme_router_n_routed(void* p) {
  return (int64_t)static_cast<Router*>(p)->o_msg.size();
}
int64_t kme_router_n_rejects(void* p) {
  return (int64_t)static_cast<Router*>(p)->o_rej.size();
}
int64_t kme_router_err_value(void* p) {
  return static_cast<Router*>(p)->err_value;
}
// ROUTER_STATS (runtime/seqsession.py), cumulative; `add` (N_STATS
// values or null) is folded in first: what a call routed by the Python
// twin counted
void kme_router_stats(void* p, const int64_t* add, int64_t* out) {
  Router& r = *static_cast<Router*>(p);
  for (int k = 0; k < Router::N_STATS; k++) {
    if (add) r.stats[k] += add[k];
    out[k] = r.stats[k];
  }
  out[Router::N_STATS] = (int64_t)r.sid_lane.size();
}
// SeqRouter.drop_batch: what the collect of plan `plan` fetched, walked
// in message order over its nr routed rows (route_events' rule): a
// sweep's makers leave before their taker's own event, every maker but
// the last emptied and the last by the kernel's word; a trade rests
// where it was accepted with a residual; an accepted cancel takes its
// order off. The last event of an oid decides, and an oid that ends
// dead loses its route unless a plan after `plan` wrote it. out =
// {routes dropped so far, routes held}; returns 1 where nfill runs
// past the fills given (nothing is dropped then), else 0
int32_t kme_router_drop_batch(void* p, int64_t nr, const int32_t* act,
                              const int64_t* oid, const uint8_t* ok,
                              const int32_t* resid, const int32_t* nfill,
                              const uint8_t* last_emptied, int64_t nfills,
                              const int64_t* f_oid, int64_t plan,
                              int64_t* out) {
  Router& r = *static_cast<Router*>(p);
  r.dead.clear();
  int64_t o0 = 0;
  for (int64_t k = 0; k < nr; k++) {
    int64_t nf = nfill[k];
    if (nf < 0 || o0 + nf > nfills) return 1;
    for (int64_t e = 0; e < nf; e++)
      if (e + 1 < nf || last_emptied[k]) r.dead.insert(f_oid[o0 + e]);
    o0 += nf;
    if (act[k] == L_BUY || act[k] == L_SELL) {
      if (ok[k] && resid[k] > 0)
        r.dead.erase(oid[k]);
      else
        r.dead.insert(oid[k]);
    } else if (act[k] == L_CANCEL && ok[k]) {
      r.dead.insert(oid[k]);
    }
  }
  for (int64_t o : r.dead) {
    auto it = r.oid_sid.find(o);
    if (it != r.oid_sid.end() && it->second.plan <= plan) {
      r.oid_sid.erase(it);
      r.routes_dropped++;
    }
  }
  out[0] = r.routes_dropped;
  out[1] = (int64_t)r.oid_sid.size();
  return 0;
}
const int64_t* kme_router_o_msg(void* p) {
  return static_cast<Router*>(p)->o_msg.data();
}
const int64_t* kme_router_o_oid(void* p) {
  return static_cast<Router*>(p)->o_oid.data();
}
const int32_t* kme_router_o_act(void* p) {
  return static_cast<Router*>(p)->o_act.data();
}
const int32_t* kme_router_o_aidx(void* p) {
  return static_cast<Router*>(p)->o_aidx.data();
}
const int32_t* kme_router_o_price(void* p) {
  return static_cast<Router*>(p)->o_price.data();
}
const int32_t* kme_router_o_size(void* p) {
  return static_cast<Router*>(p)->o_size.data();
}
const int32_t* kme_router_o_lane(void* p) {
  return static_cast<Router*>(p)->o_lane.data();
}
const int64_t* kme_router_o_rej(void* p) {
  return static_cast<Router*>(p)->o_rej.data();
}

// map export/import (checkpoint contract)
int64_t kme_router_n_accounts(void* p) {
  return (int64_t)static_cast<Router*>(p)->aid_idx.size();
}
int64_t kme_router_n_symbols(void* p) {
  return (int64_t)static_cast<Router*>(p)->sid_lane.size();
}
int64_t kme_router_n_routes(void* p) {
  return (int64_t)static_cast<Router*>(p)->oid_sid.size();
}
void kme_router_export_accounts(void* p, int64_t* keys, int32_t* vals) {
  int64_t i = 0;
  for (auto& kv : static_cast<Router*>(p)->aid_idx) {
    keys[i] = kv.first;
    vals[i] = kv.second;
    i++;
  }
}
void kme_router_export_symbols(void* p, int64_t* keys, int32_t* vals) {
  int64_t i = 0;
  for (auto& kv : static_cast<Router*>(p)->sid_lane) {
    keys[i] = kv.first;
    vals[i] = kv.second;
    i++;
  }
}
void kme_router_export_routes(void* p, int64_t* keys, int64_t* vals) {
  int64_t i = 0;
  for (auto& kv : static_cast<Router*>(p)->oid_sid) {
    keys[i] = kv.first;
    vals[i] = kv.second.sid;
    i++;
  }
}
void kme_router_import_accounts(void* p, int64_t n, const int64_t* keys,
                                const int32_t* vals) {
  auto& m = static_cast<Router*>(p)->aid_idx;
  m.clear();
  for (int64_t i = 0; i < n; i++) m.emplace(keys[i], vals[i]);
}
void kme_router_import_symbols(void* p, int64_t n, const int64_t* keys,
                               const int32_t* vals) {
  Router& r = *static_cast<Router*>(p);
  r.sid_lane.clear();
  for (int64_t i = 0; i < n; i++) r.sid_lane.emplace(keys[i], vals[i]);
  r.rebuild_pool();
}
// the bound ids whose book is gone (after an import of the symbols)
int64_t kme_router_n_delisted(void* p) {
  return (int64_t)static_cast<Router*>(p)->delisted.size();
}
void kme_router_export_delisted(void* p, int64_t* keys) {
  int64_t i = 0;
  for (int64_t s : static_cast<Router*>(p)->delisted) keys[i++] = s;
}
void kme_router_import_delisted(void* p, int64_t n, const int64_t* keys) {
  auto& d = static_cast<Router*>(p)->delisted;
  d.clear();
  d.insert(keys, keys + n);
}
void kme_router_import_routes(void* p, int64_t n, const int64_t* keys,
                              const int64_t* vals) {
  auto& m = static_cast<Router*>(p)->oid_sid;
  m.clear();
  for (int64_t i = 0; i < n; i++) m.emplace(keys[i], Route{vals[i], 0});
}

}  // extern "C"
