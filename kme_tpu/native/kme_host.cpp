// Native host-runtime core: the plan half of the served host path.
//
// The reference's runtime substrate is native third-party code behind the
// JVM (RocksDB JNI, Kafka clients — SURVEY.md §2.4); here the host
// runtime's hot loop has a C++ implementation bound over a C ABI with
// ctypes (kme_tpu/native/sched.py).
//
// Batch plan: route + H2D staging pack in one call. Calls the seq router
// (kme_router.cpp) through its own C ABI (same shared object) and packs
// the routed columns straight into the stacked (K, B) int32 scan-input
// planes, replacing SeqSession._plan's numpy zero-pad + int64 split.
// Plane order matches the scan's input dict:
//   [act, aid, price, size, lane, oid_lo, oid_hi], plane-major, K*B each.
//
// Build: g++ -O3 -shared -fPIC kme_host.cpp -o kme_host.so
// (driven by kme_tpu/native/__init__.py, cached by source hash).

#include <climits>
#include <cstdint>
#include <cstring>

extern "C" {
int32_t kme_router_route(void*, int64_t, const int64_t*, const int64_t*,
                         const int64_t*, const int64_t*, const int64_t*,
                         const int64_t*);
int64_t kme_router_n_routed(void*);
const int32_t* kme_router_o_act(void*);
const int32_t* kme_router_o_aidx(void*);
const int32_t* kme_router_o_price(void*);
const int32_t* kme_router_o_size(void*);
const int32_t* kme_router_o_lane(void*);
const int64_t* kme_router_o_oid(void*);
}

namespace {

// Rotating plane buffers: the Python side hands the planes to the jit
// dispatch zero-copy, and double-buffered serving keeps up to two packed
// batches in flight — four buffers give a 2x safety margin before a
// plane is overwritten.
struct Pack {
  static constexpr int NBUF = 4;
  int32_t* buf[NBUF] = {nullptr, nullptr, nullptr, nullptr};
  int64_t cap[NBUF] = {0, 0, 0, 0};
  int cur = NBUF - 1;
  int64_t err_index = -1;
  ~Pack() {
    for (int i = 0; i < NBUF; ++i) delete[] buf[i];
  }
};

}  // namespace

extern "C" {

void* kme_pack_new() { return new Pack(); }
void kme_pack_free(void* p) { delete static_cast<Pack*>(p); }

// Envelope-check + route + pack one batch. Returns K (the power-of-two
// chunk count, >= 1) on success, or:
//   -1 account-capacity exhausted   (router err_value holds the id)
//   -2 symbol-capacity exhausted
//   -3 price/size outside int32     (kme_pack_err_index holds the index;
//                                    id maps untouched, like the Python
//                                    wrapper's pre-route envelope check)
int64_t kme_plan_batch(void* pack, void* router, int64_t n,
                       const int64_t* action, const int64_t* oid,
                       const int64_t* aid, const int64_t* sid,
                       const int64_t* price, const int64_t* size,
                       int32_t B) {
  Pack& pk = *static_cast<Pack*>(pack);
  pk.err_index = -1;
  for (int64_t i = 0; i < n; ++i) {
    if (price[i] < INT32_MIN || price[i] > INT32_MAX ||
        size[i] < INT32_MIN || size[i] > INT32_MAX) {
      pk.err_index = i;
      return -3;
    }
  }
  int32_t rc = kme_router_route(router, n, action, oid, aid, sid, price,
                                size);
  if (rc != 0) return -(int64_t)rc;
  const int64_t nr = kme_router_n_routed(router);
  int64_t nk = nr > 0 ? (nr + B - 1) / B : 1;
  int64_t K = 1;
  while (K < nk) K <<= 1;
  const int64_t total = K * (int64_t)B;
  pk.cur = (pk.cur + 1) % Pack::NBUF;
  int32_t*& b = pk.buf[pk.cur];
  if (pk.cap[pk.cur] < 7 * total) {
    delete[] b;
    b = new int32_t[7 * total];
    pk.cap[pk.cur] = 7 * total;
  }
  std::memset(b, 0, sizeof(int32_t) * 7 * total);
  std::memcpy(b + 0 * total, kme_router_o_act(router), nr * 4);
  std::memcpy(b + 1 * total, kme_router_o_aidx(router), nr * 4);
  std::memcpy(b + 2 * total, kme_router_o_price(router), nr * 4);
  std::memcpy(b + 3 * total, kme_router_o_size(router), nr * 4);
  std::memcpy(b + 4 * total, kme_router_o_lane(router), nr * 4);
  const int64_t* roid = kme_router_o_oid(router);
  int32_t* lo = b + 5 * total;
  int32_t* hi = b + 6 * total;
  for (int64_t i = 0; i < nr; ++i) {
    // numpy split64 semantics: low 32 bits reinterpreted as int32,
    // high 32 via arithmetic shift then truncating cast
    lo[i] = (int32_t)(uint32_t)(uint64_t)roid[i];
    hi[i] = (int32_t)(roid[i] >> 32);
  }
  return K;
}

const int32_t* kme_pack_planes(void* p) {
  Pack& pk = *static_cast<Pack*>(p);
  return pk.buf[pk.cur];
}
int64_t kme_pack_err_index(void* p) {
  return static_cast<Pack*>(p)->err_index;
}

// Per-shard submission-queue slice (seqmesh async dispatch): gather
// one shard's rows for `n` windows out of a stacked (K, shards*bw)
// int32 plane into a dense zero-padded (kpad, bw) segment plane. One
// memcpy per window row; out-of-range window indices are skipped (the
// Python wrapper never produces them — defensive only).
void kme_shard_slice(const int32_t* src, int64_t K, int64_t shards,
                     int64_t bw, int64_t shard, const int64_t* win_idx,
                     int64_t n, int64_t kpad, int32_t* dst) {
  if (kpad > 0)
    std::memset(dst, 0, sizeof(int32_t) * (size_t)(kpad * bw));
  for (int64_t i = 0; i < n && i < kpad; ++i) {
    const int64_t w = win_idx[i];
    if (w < 0 || w >= K) continue;
    std::memcpy(dst + i * bw, src + (w * shards + shard) * bw,
                sizeof(int32_t) * (size_t)bw);
  }
}

}  // extern "C"
