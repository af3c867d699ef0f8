// Native wire-stream reconstruction for the sequential engine.
//
// The engine returns compact per-message arrays + a packed fill log;
// turning those into the byte-exact `IN {...}` / `OUT {...}` record
// stream (consumer.js:19 format; Jackson template wire.order_json) was
// a per-fill Python loop costing ~1s per 100k messages — the host-side
// cap SURVEY.md §7 H5 warns about. This is the same reconstruction in
// C++ behind a C ABI: one call emits every line into a single buffer
// with per-line offsets; Python slices lazily or streams the buffer.
// Semantics authority: SeqSession.process_wire (runtime/seqsession.py);
// equivalence is pinned by tests/test_seq_engine.py.
//
// Built together with kme_host.cpp / kme_oracle.cpp by
// kme_tpu/native/__init__.py.

#include <charconv>
#include <cstdint>
#include <cstring>

namespace {

constexpr int32_t L_BUY = 1, L_SELL = 2;
constexpr int64_t OP_BOUGHT = 5, OP_SOLD = 6, OP_REJECT = 7;

struct Recon {
  // output storage (valid until the next call / free)
  char* buf = nullptr;
  int64_t cap = 0, len = 0;
  int64_t* line_off = nullptr;   // start offset of each line
  int64_t n_lines = 0, lines_cap = 0;
  int32_t* msg_lines = nullptr;  // lines per message
  int64_t nmsg_cap = 0;
  ~Recon() {
    delete[] buf;
    delete[] line_off;
    delete[] msg_lines;
  }
};

inline void put_raw(Recon& r, const char* s, int64_t n) {
  std::memcpy(r.buf + r.len, s, n);
  r.len += n;
}

inline void put_i64(Recon& r, int64_t v) {
  auto res = std::to_chars(r.buf + r.len, r.buf + r.cap, v);
  r.len = res.ptr - r.buf;
}

// order_json (wire.py): compact Jackson template, declaration order.
inline void put_order(Recon& r, int64_t action, int64_t oid, int64_t aid,
                      int64_t sid, int64_t price, int64_t size,
                      bool has_next, int64_t next, bool has_prev,
                      int64_t prev) {
  put_raw(r, "{\"action\":", 10);
  put_i64(r, action);
  put_raw(r, ",\"oid\":", 7);
  put_i64(r, oid);
  put_raw(r, ",\"aid\":", 7);
  put_i64(r, aid);
  put_raw(r, ",\"sid\":", 7);
  put_i64(r, sid);
  put_raw(r, ",\"price\":", 9);
  put_i64(r, price);
  put_raw(r, ",\"size\":", 8);
  put_i64(r, size);
  put_raw(r, ",\"next\":", 8);
  if (has_next) put_i64(r, next); else put_raw(r, "null", 4);
  put_raw(r, ",\"prev\":", 8);
  if (has_prev) put_i64(r, prev); else put_raw(r, "null", 4);
  put_raw(r, "}", 1);
}

inline void start_line(Recon& r, const char* key, int64_t klen) {
  r.line_off[r.n_lines++] = r.len;
  put_raw(r, key, klen);
}

}  // namespace

extern "C" {

void* kme_recon_new() { return new Recon(); }
void kme_recon_free(void* p) { delete static_cast<Recon*>(p); }

const char* kme_recon_buf(void* p) { return static_cast<Recon*>(p)->buf; }
int64_t kme_recon_len(void* p) { return static_cast<Recon*>(p)->len; }
int64_t kme_recon_n_lines(void* p) {
  return static_cast<Recon*>(p)->n_lines;
}
const int64_t* kme_recon_line_off(void* p) {
  return static_cast<Recon*>(p)->line_off;
}
const int32_t* kme_recon_msg_lines(void* p) {
  return static_cast<Recon*>(p)->msg_lines;
}

// One-pass reconstruction straight from the engine's routed/host arrays
// (the D2H half of the native host path): routed rows arrive in
// ascending msg-index order (the router emits at most one row per
// message, in order), so a single merge walk
// recovers isdev/act/ok/fill-window per message, translates a fill's
// account-index -> aid through the LUT (its symbol id is the taker
// message's own: a lane may name another id by the time the batch is
// collected), and emits through the same line builders. Fill windows
// are the running sum of h_nfill over ALL routed rows (failed rows
// carry nfill 0), matching the numpy cumsum. Returns 0 on success, 1 on
// an out-of-range account index / fill offset (the Python caller
// raises; numpy would IndexError on the same input).
int32_t kme_recon_batch(
    int64_t nmsg, const int64_t* m_action, const int64_t* m_oid,
    const int64_t* m_aid, const int64_t* m_sid, const int64_t* m_price,
    const int64_t* m_size, const int64_t* m_next, const uint8_t* m_has_next,
    const int64_t* m_prev, const uint8_t* m_has_prev,
    int64_t nr, const int64_t* r_msg, const int32_t* r_act,
    const uint8_t* h_ok, const int64_t* h_nfill, const int64_t* h_resid,
    const int64_t* h_prev, const uint8_t* h_append,
    int64_t nacct, const int64_t* idx2aid,
    int64_t nfills, const int64_t* f_oid, const int64_t* f_aidx,
    const int64_t* f_price, const int64_t* f_size, void* handle) {
  Recon& r = *static_cast<Recon*>(handle);
  // worst-case line budget: IN + OUT per message + 2 lines per fill.
  // Longest line: "OUT " (4) + 65 bytes of JSON scaffolding + 8 fields
  // of up to 20 chars (int64 min) = 229; 240 leaves slack.
  int64_t lines = 2 * nmsg + 2 * nfills;
  int64_t need = 240 * lines + 64;
  if (r.cap < need) {
    delete[] r.buf;
    r.buf = new char[need];
    r.cap = need;
  }
  if (r.lines_cap < lines) {
    delete[] r.line_off;
    r.line_off = new int64_t[lines];
    r.lines_cap = lines;
  }
  if (r.nmsg_cap < nmsg) {
    delete[] r.msg_lines;
    r.msg_lines = new int32_t[nmsg];
    r.nmsg_cap = nmsg;
  }
  r.len = 0;
  r.n_lines = 0;

  int64_t k = 0;   // routed-row cursor
  int64_t o0 = 0;  // running fill offset
  for (int64_t i = 0; i < nmsg; i++) {
    int64_t lines0 = r.n_lines;
    start_line(r, "IN ", 3);
    put_order(r, m_action[i], m_oid[i], m_aid[i], m_sid[i], m_price[i],
              m_size[i], m_has_next[i], m_next[i], m_has_prev[i],
              m_prev[i]);
    bool isdev = k < nr && r_msg[k] == i;
    bool ok = isdev && h_ok[k] != 0;
    if (!ok) {
      start_line(r, "OUT ", 4);
      put_order(r, OP_REJECT, m_oid[i], m_aid[i], m_sid[i], m_price[i],
                m_size[i], m_has_next[i], m_next[i], m_has_prev[i],
                m_prev[i]);
    } else {
      int32_t act = r_act[k];
      if (act == L_BUY || act == L_SELL) {
        // a fill is in the taker's book: the id its message was
        // routed under, whatever a lane -> id table says by now
        int64_t sid = m_sid[i];
        int64_t mk = act == L_BUY ? OP_SOLD : OP_BOUGHT;
        int64_t tk = act == L_BUY ? OP_BOUGHT : OP_SOLD;
        for (int64_t e = 0; e < h_nfill[k]; e++) {
          if (o0 + e >= nfills) return 1;
          int64_t ai = f_aidx[o0 + e];
          if (ai < 0 || ai >= nacct) return 1;
          start_line(r, "OUT ", 4);
          put_order(r, mk, f_oid[o0 + e], idx2aid[ai], sid, 0,
                    f_size[o0 + e], false, 0, false, 0);
          start_line(r, "OUT ", 4);
          put_order(r, tk, m_oid[i], m_aid[i], sid,
                    m_price[i] - f_price[o0 + e], f_size[o0 + e],
                    false, 0, false, 0);
        }
        start_line(r, "OUT ", 4);
        bool app = h_append[k] != 0;
        put_order(r, m_action[i], m_oid[i], m_aid[i], m_sid[i],
                  m_price[i], h_resid[k], m_has_next[i], m_next[i],
                  app || m_has_prev[i], app ? h_prev[k] : m_prev[i]);
      } else {
        start_line(r, "OUT ", 4);
        put_order(r, m_action[i], m_oid[i], m_aid[i], m_sid[i],
                  m_price[i], m_size[i], m_has_next[i], m_next[i],
                  m_has_prev[i], m_prev[i]);
      }
    }
    if (isdev) {
      o0 += h_nfill[k];
      k++;
    }
    r.msg_lines[i] = static_cast<int32_t>(r.n_lines - lines0);
  }
  return 0;
}

}  // extern "C"

// ---------------------------------------------------------------------------
// kme_parse: newline-separated JSON order messages -> columnar arrays.
//
// The input half of the wire boundary (the reference consumes JSON
// bytes from Kafka and Jackson-binds them onto the Order POJO,
// KProcessor.java:96, 448-475). Semantics authority: wire.parse_order —
// creator-bound value fields default to 0 when absent/null, next/prev
// bind by name (null/absent -> has=0), unknown keys are ignored, fields
// may appear in any order, last occurrence wins. This parser handles
// the integer/null/object subset exactly; ANY construct outside it
// (floats, strings, nested values, syntax errors, ints beyond int64)
// returns -(line+1) and the caller re-parses the whole buffer through
// the Python authority so error behavior and coercions stay identical
// (wire.WireBatch.parse_buffer).

namespace {

struct Parse {
  int64_t* cols[8] = {};  // action oid aid sid price size next prev
  uint8_t* hnext = nullptr;
  uint8_t* hprev = nullptr;
  int64_t* tidcol = nullptr;  // transport-advisory trace word (FLAG_TID)
  uint8_t* htid = nullptr;
  int64_t cap = 0, n = 0;
  int64_t err_off = 0;       // byte offset of the frame that failed
  Recon emit;                // canonical-JSON emission scratch
  int64_t* emit_off = nullptr;  // n+1 line offsets into emit.buf
  int64_t emit_off_cap = 0;
  ~Parse() {
    for (auto* c : cols) delete[] c;
    delete[] hnext;
    delete[] hprev;
    delete[] tidcol;
    delete[] htid;
    delete[] emit_off;
  }
};

inline void parse_reserve(Parse& P, int64_t n) {
  if (P.cap >= n) return;
  for (auto*& c : P.cols) {
    delete[] c;
    c = new int64_t[n];
  }
  delete[] P.hnext;
  delete[] P.hprev;
  delete[] P.tidcol;
  delete[] P.htid;
  P.hnext = new uint8_t[n];
  P.hprev = new uint8_t[n];
  P.tidcol = new int64_t[n];
  P.htid = new uint8_t[n];
  P.cap = n;
}

inline void skip_ws(const char*& p, const char* end) {
  while (p < end && (*p == ' ' || *p == '\t' || *p == '\r')) p++;
}

// parse an int64 with JSON number syntax restricted to integers:
// -?(0|[1-9][0-9]*). Returns false on anything else (incl. overflow).
inline bool parse_int(const char*& p, const char* end, int64_t* out) {
  bool neg = false;
  if (p < end && *p == '-') {
    neg = true;
    p++;
  }
  if (p >= end || *p < '0' || *p > '9') return false;
  if (*p == '0' && p + 1 < end && p[1] >= '0' && p[1] <= '9')
    return false;  // leading zero: invalid JSON
  uint64_t v = 0;
  const uint64_t lim = neg ? (uint64_t)1 << 63 : ((uint64_t)1 << 63) - 1;
  while (p < end && *p >= '0' && *p <= '9') {
    uint64_t d = (uint64_t)(*p - '0');
    if (v > (lim - d) / 10) return false;  // beyond int64
    v = v * 10 + d;
    p++;
  }
  if (p < end && (*p == '.' || *p == 'e' || *p == 'E')) return false;
  *out = neg ? (int64_t)(0 - v) : (int64_t)v;
  return true;
}

// Template fast path: the overwhelmingly common case is the exact
// Jackson template order_json emits (compact, declaration field order,
// next/prev always present). One memcmp per literal + digit runs; any
// deviation falls through to the general object walk above.
inline bool fast_line(const char* p, const char* end, int64_t* v,
                      uint8_t* has) {
  static const struct { const char* lit; int n; } L[8] = {
      {"{\"action\":", 10}, {",\"oid\":", 7}, {",\"aid\":", 7},
      {",\"sid\":", 7},     {",\"price\":", 9}, {",\"size\":", 8},
      {",\"next\":", 8},    {",\"prev\":", 8}};
  for (int f = 0; f < 8; f++) {
    if (end - p < L[f].n || std::memcmp(p, L[f].lit, L[f].n))
      return false;
    p += L[f].n;
    if (f >= 6 && end - p >= 4 && !std::memcmp(p, "null", 4)) {
      p += 4;
      v[f] = 0;
      has[f] = 0;
    } else {
      if (!parse_int(p, end, &v[f])) return false;
      has[f] = 1;
    }
  }
  return p < end && *p == '}' && p + 1 == end;
}

}  // namespace

extern "C" {

void* kme_parse_new() { return new Parse(); }
void kme_parse_free(void* p) { delete static_cast<Parse*>(p); }

const int64_t* kme_parse_col(void* p, int32_t i) {
  return static_cast<Parse*>(p)->cols[i];
}
const uint8_t* kme_parse_hnext(void* p) {
  return static_cast<Parse*>(p)->hnext;
}
const uint8_t* kme_parse_hprev(void* p) {
  return static_cast<Parse*>(p)->hprev;
}
const int64_t* kme_parse_tid(void* p) {
  return static_cast<Parse*>(p)->tidcol;
}
const uint8_t* kme_parse_htid(void* p) {
  return static_cast<Parse*>(p)->htid;
}

// Parse `len` bytes of newline-separated order JSON. Returns the line
// count on success, -(line+1) on the first line outside the fast
// subset (caller falls back to the Python authority).
int64_t kme_parse_lines(void* handle, const char* buf, int64_t len) {
  Parse& P = *static_cast<Parse*>(handle);
  // count lines (a trailing newline does not open an empty last line)
  int64_t nlines = 0;
  for (int64_t i = 0; i < len; i++)
    if (buf[i] == '\n') nlines++;
  if (len > 0 && buf[len - 1] != '\n') nlines++;
  parse_reserve(P, nlines);
  P.n = 0;
  const char* p = buf;
  const char* bend = buf + len;
  for (int64_t li = 0; li < nlines; li++) {
    const char* end = static_cast<const char*>(
        std::memchr(p, '\n', bend - p));
    if (!end) end = bend;
    int64_t v[8] = {0, 0, 0, 0, 0, 0, 0, 0};
    uint8_t has[8] = {0, 0, 0, 0, 0, 0, 0, 0};
    if (fast_line(p, end, v, has)) {
      for (int f = 0; f < 8; f++) P.cols[f][li] = v[f];
      P.hnext[li] = has[6];
      P.hprev[li] = has[7];
      P.tidcol[li] = 0;
      P.htid[li] = 0;
      P.n++;
      p = end < bend ? end + 1 : end;
      continue;
    }
    for (int f = 0; f < 8; f++) {
      v[f] = 0;
      has[f] = 0;
    }
    skip_ws(p, end);
    if (p >= end || *p != '{') return -(li + 1);
    p++;
    skip_ws(p, end);
    bool first = true;
    while (true) {
      if (p < end && *p == '}') {
        p++;
        break;
      }
      if (!first) {
        if (p >= end || *p != ',') return -(li + 1);
        p++;
        skip_ws(p, end);
      }
      first = false;
      if (p >= end || *p != '"') return -(li + 1);
      p++;
      const char* k0 = p;
      while (p < end && *p != '"') {
        if (*p == '\\') return -(li + 1);  // escaped keys: fall back
        p++;
      }
      if (p >= end) return -(li + 1);
      int64_t klen = p - k0;
      p++;
      skip_ws(p, end);
      if (p >= end || *p != ':') return -(li + 1);
      p++;
      skip_ws(p, end);
      int fi = -1;
      switch (klen) {
        case 3:
          if (!std::memcmp(k0, "oid", 3)) fi = 1;
          else if (!std::memcmp(k0, "aid", 3)) fi = 2;
          else if (!std::memcmp(k0, "sid", 3)) fi = 3;
          break;
        case 4:
          if (!std::memcmp(k0, "size", 4)) fi = 5;
          else if (!std::memcmp(k0, "next", 4)) fi = 6;
          else if (!std::memcmp(k0, "prev", 4)) fi = 7;
          break;
        case 5:
          if (!std::memcmp(k0, "price", 5)) fi = 4;
          break;
        case 6:
          if (!std::memcmp(k0, "action", 6)) fi = 0;
          break;
      }
      if (p < end && *p == 'n') {
        if (end - p < 4 || std::memcmp(p, "null", 4)) return -(li + 1);
        p += 4;
        // null: value fields -> 0 (Jackson primitive default),
        // next/prev -> unset; LAST occurrence wins either way
        if (fi >= 0) {
          v[fi] = 0;
          has[fi] = 0;
        }
      } else {
        int64_t x;
        if (!parse_int(p, end, &x)) return -(li + 1);
        if (fi >= 0) {
          v[fi] = x;
          has[fi] = 1;
        }
      }
      skip_ws(p, end);
    }
    skip_ws(p, end);
    if (p != end) return -(li + 1);  // trailing garbage
    if (p < bend) p++;               // consume '\n'
    for (int f = 0; f < 8; f++) P.cols[f][li] = v[f];
    P.hnext[li] = has[6];
    P.hprev[li] = has[7];
    P.tidcol[li] = 0;
    P.htid[li] = 0;
    P.n++;
  }
  return P.n;
}

// ---------------------------------------------------------------------------
// Binary order frames (wire.py layout authority): 72 bytes little-
// endian — magic 0xB1, version, kind, flags, u32 length prefix, then
// action/oid/aid/sid/price/size/next/prev as int64. Values are
// memcpy'd (alignment-safe); the build targets little-endian hosts
// only, same assumption the journal's binary framing already makes.

int64_t kme_parse_err_off(void* p) {
  return static_cast<Parse*>(p)->err_off;
}

// Parse `len` bytes of concatenated binary order frames into the same
// columns kme_parse_lines fills. Returns the frame count, or a
// negative validation code for the FIRST bad frame (offset readable
// via kme_parse_err_off): -1 truncated, -2 bad magic, -3 version
// skew, -4 bad kind, -5 bad length. Check order matches
// wire._check_frame_header exactly — the Python caller re-raises
// through the Python authority so the surfaced error is identical.
int64_t kme_parse_frames(void* handle, const uint8_t* buf, int64_t len) {
  // Flags bit 2 (FLAG_TID) extends the frame by a trailing int64 trace
  // word: 80 bytes instead of 72. The word is transport-advisory — it
  // never reaches the canonical JSON emission (kme_parse_emit).
  constexpr int64_t FRAME_SIZE = 72, FRAME_HDR = 8;
  constexpr int64_t FRAME_SIZE_TRACED = 80;
  Parse& P = *static_cast<Parse*>(handle);
  parse_reserve(P, len / FRAME_SIZE + 1);
  P.n = 0;
  P.err_off = 0;
  int64_t off = 0, i = 0;
  while (off < len) {
    P.err_off = off;
    const uint8_t* b = buf + off;
    int64_t rem = len - off;
    if (rem < FRAME_HDR) return -1;
    if (b[0] != 0xB1) return -2;
    if (b[1] != 1) return -3;
    if (b[2] != 0) return -4;
    const bool traced = (b[3] & 4) != 0;
    const int64_t expected = traced ? FRAME_SIZE_TRACED : FRAME_SIZE;
    uint32_t length;
    std::memcpy(&length, b + 4, 4);
    if (length != expected) return -5;
    if (rem < expected) return -1;
    int64_t v[8];
    std::memcpy(v, b + 8, 64);
    for (int f = 0; f < 8; f++) P.cols[f][i] = v[f];
    P.hnext[i] = b[3] & 1;
    P.hprev[i] = (b[3] >> 1) & 1;
    if (traced) {
      std::memcpy(&P.tidcol[i], b + FRAME_SIZE, 8);
      P.htid[i] = 1;
    } else {
      P.tidcol[i] = 0;
      P.htid[i] = 0;
    }
    off += expected;
    i++;
  }
  P.n = i;
  return i;
}

// Emit the canonical Jackson JSON line for every parsed row (the value
// the broker stores — binary is transport-only, the durable log and
// the oracle replay see order_json bytes regardless of encoding).
// Lines are concatenated with NO separators; kme_parse_emit_off gives
// n+1 offsets. Goes through put_order, the same emitter the byte-
// pinned reconstruction uses, so encode parity is inherited.
int64_t kme_parse_emit(void* handle) {
  Parse& P = *static_cast<Parse*>(handle);
  Recon& r = P.emit;
  // worst case per line: 65 bytes of scaffolding + 8 fields of up to
  // 20 chars (int64 min) = 225; 240 leaves slack
  int64_t need = 240 * (P.n > 0 ? P.n : 1);
  if (r.cap < need) {
    delete[] r.buf;
    r.buf = new char[need];
    r.cap = need;
  }
  if (P.emit_off_cap < P.n + 1) {
    delete[] P.emit_off;
    P.emit_off = new int64_t[P.n + 1];
    P.emit_off_cap = P.n + 1;
  }
  r.len = 0;
  for (int64_t i = 0; i < P.n; i++) {
    P.emit_off[i] = r.len;
    put_order(r, P.cols[0][i], P.cols[1][i], P.cols[2][i], P.cols[3][i],
              P.cols[4][i], P.cols[5][i], P.hnext[i] != 0, P.cols[6][i],
              P.hprev[i] != 0, P.cols[7][i]);
  }
  P.emit_off[P.n] = r.len;
  return r.len;
}

const char* kme_parse_emit_buf(void* p) {
  return static_cast<Parse*>(p)->emit.buf;
}
const int64_t* kme_parse_emit_off(void* p) {
  return static_cast<Parse*>(p)->emit_off;
}

}  // extern "C"

// ---------------------------------------------------------------------------
// kme_run: a stamped run of output records as ONE buffer (the egress
// twin of kme_parse above). The reconstruction buffer already is the
// records — "KEY value" lines with n+1 offsets — and both places it is
// going want bytes: the broker's durable log (one JSON row a record)
// and a consumer's fetch_bin reply (one fixed-width row a record).
// Line i is buf[off[i], off[i+1]); its key is the first klen[i] bytes
// (klen < 0: the key is null and the whole line is the value), its
// value starts one separator byte after the key (or is empty where the
// line ends with the key). Each call leaves its result in a buffer of
// the CALLING THREAD (the serve loop and every fetch handler have their
// own), valid until that thread's next call; kme_run_out reads it.
// Semantics authorities: broker._stamped_rows (json's
// encode_basestring_ascii) and tcp._pack_records; equivalence is pinned
// by tests/test_produce_stamped.py and tests/test_fetch_runs.py.

namespace {

// the calling thread's result buffer: grown, never shrunk, freed with
// the thread
struct RunOut {
  char* buf = nullptr;
  int64_t cap = 0, len = 0;
  ~RunOut() { delete[] buf; }
  void reserve(int64_t need) {
    if (cap >= need) return;
    delete[] buf;
    cap = need + need / 4;
    buf = new char[cap];
  }
};
thread_local RunOut run_out;

// bytes json's encode_basestring_ascii copies as they are: printable
// ASCII but '"' and '\\'
struct PlainTable {
  bool t[256];
  constexpr PlainTable() : t() {
    for (int c = 0; c < 256; c++)
      t[c] = c >= 0x20 && c < 0x7f && c != '"' && c != '\\';
  }
};
constexpr PlainTable PLAIN;

inline char* put_u4(char* p, uint32_t cp) {
  static const char hex[] = "0123456789abcdef";
  p[0] = '\\';
  p[1] = 'u';
  p[2] = hex[(cp >> 12) & 15];
  p[3] = hex[(cp >> 8) & 15];
  p[4] = hex[(cp >> 4) & 15];
  p[5] = hex[cp & 15];
  return p + 6;
}

// json.encoder.encode_basestring_ascii over utf-8 bytes, at most 6
// bytes out for one in, plus the quotes: nullptr where the bytes are
// not utf-8 (the caller then takes the Python twin).
inline char* put_json_string(char* p, const uint8_t* s, int64_t n) {
  *p++ = '"';
  int64_t i = 0;
  while (i < n) {
    const uint8_t ch = s[i];
    // a record's value is JSON, a quote every few bytes: a byte at a
    // time is as fast as finding stretches to memcpy
    if (PLAIN.t[ch]) {
      *p++ = static_cast<char>(ch);
      i++;
      continue;
    }
    if (ch < 0x80) {
      char e = 0;
      switch (ch) {
        case '"': e = '"'; break;
        case '\\': e = '\\'; break;
        case '\n': e = 'n'; break;
        case '\r': e = 'r'; break;
        case '\t': e = 't'; break;
        case '\b': e = 'b'; break;
        case '\f': e = 'f'; break;
      }
      if (e) {
        *p++ = '\\';
        *p++ = e;
      } else {
        p = put_u4(p, ch);
      }
      i++;
      continue;
    }
    const int extra = ch >= 0xf0 ? 3 : ch >= 0xe0 ? 2 : ch >= 0xc0 ? 1 : -1;
    if (extra < 0 || ch >= 0xf8 || i + extra >= n) return nullptr;
    uint32_t cp = ch & (0x3f >> extra);
    for (int k = 1; k <= extra; k++) {
      if ((s[i + k] & 0xc0) != 0x80) return nullptr;
      cp = (cp << 6) | (s[i + k] & 0x3f);
    }
    // what Python's decoder refuses — an overlong form, a code point
    // past U+10FFFF — goes to the twin too (a surrogate is taken:
    // "surrogatepass" on both sides)
    static const uint32_t least[4] = {0, 0x80, 0x800, 0x10000};
    if (cp < least[extra] || cp > 0x10ffff) return nullptr;
    i += extra + 1;
    if (cp >= 0x10000) {
      cp -= 0x10000;
      p = put_u4(p, 0xd800 | ((cp >> 10) & 0x3ff));
      p = put_u4(p, 0xdc00 | (cp & 0x3ff));
    } else {
      p = put_u4(p, cp);
    }
  }
  *p++ = '"';
  return p;
}

inline char* put_le(char* p, uint64_t v, int nbytes) {
  for (int k = 0; k < nbytes; k++) p[k] = static_cast<char>(v >> (8 * k));
  return p + nbytes;
}

}  // namespace

extern "C" {

// klen[i] = length of line i's key: the bytes before its first space,
// the whole line where it has none (str.partition(" ")).
void kme_run_split(const uint8_t* buf, const int64_t* off, int64_t n,
                   int32_t* klen) {
  for (int64_t i = 0; i < n; i++) {
    const uint8_t* s = buf + off[i];
    int64_t len = off[i + 1] - off[i];
    const void* sp = len > 0 ? std::memchr(s, ' ', len) : nullptr;
    klen[i] = static_cast<int32_t>(
        sp ? static_cast<const uint8_t*>(sp) - s : len);
  }
}

// The durable rows of lines [lo, hi): `["KEY","value",epoch,seq]\n`,
// line i stamped seq_lo + (i - lo). Returns the byte count, -1 where a
// line is not utf-8.
int64_t kme_run_rows(const uint8_t* buf, const int64_t* off,
                     const int32_t* klen, int64_t lo, int64_t hi,
                     int64_t epoch, int64_t seq_lo) {
  RunOut& o = run_out;
  o.len = 0;
  if (hi <= lo) return 0;
  // a row: '[', two strings (6 bytes out for one in at most, two
  // quotes each; "null"), three commas, two integers of up to 20
  // characters, "]\n" — 64 covers all but the strings' bytes
  o.reserve(6 * (off[hi] - off[lo]) + 64 * (hi - lo));
  char ep[24];
  const int64_t eplen = std::to_chars(ep, ep + sizeof ep, epoch).ptr - ep;
  char* p = o.buf;
  for (int64_t i = lo; i < hi; i++) {
    const uint8_t* s = buf + off[i];
    const int64_t len = off[i + 1] - off[i];
    const int64_t kl = klen[i];
    int64_t vs = kl + 1;
    if (vs > len) vs = len;
    *p++ = '[';
    if (kl < 0) {
      std::memcpy(p, "null", 4);
      p += 4;
    } else if (!(p = put_json_string(p, s, kl))) {
      return -1;
    }
    *p++ = ',';
    if (!(p = put_json_string(p, s + vs, len - vs))) return -1;
    *p++ = ',';
    std::memcpy(p, ep, eplen);
    p += eplen;
    *p++ = ',';
    p = std::to_chars(p, p + 24, seq_lo + (i - lo)).ptr;
    *p++ = ']';
    *p++ = '\n';
  }
  o.len = p - o.buf;
  return o.len;
}

// The fetch_bin rows of lines [lo, hi) (bridge/tcp.py): per record five
// little-endian i64 (offset, epoch, out_seq, ats, tid = absent), u8
// key length (255: null) + key, u32 value length + value. Returns the
// byte count, -1 where a key is too long for its length byte.
int64_t kme_run_pack(const uint8_t* buf, const int64_t* off,
                     const int32_t* klen, int64_t lo, int64_t hi,
                     int64_t offset_lo, int64_t epoch, int64_t seq_lo,
                     int64_t ats) {
  RunOut& o = run_out;
  o.len = 0;
  if (hi <= lo) return 0;
  o.reserve((off[hi] - off[lo]) + 45 * (hi - lo));
  char* p = o.buf;
  for (int64_t i = lo; i < hi; i++) {
    const char* s = reinterpret_cast<const char*>(buf) + off[i];
    const int64_t len = off[i + 1] - off[i];
    const int64_t kl = klen[i];
    if (kl >= 255) return -1;
    int64_t vs = kl + 1;
    if (vs > len) vs = len;
    p = put_le(p, static_cast<uint64_t>(offset_lo + (i - lo)), 8);
    p = put_le(p, static_cast<uint64_t>(epoch), 8);
    p = put_le(p, static_cast<uint64_t>(seq_lo + (i - lo)), 8);
    p = put_le(p, static_cast<uint64_t>(ats), 8);
    p = put_le(p, static_cast<uint64_t>(INT64_MIN), 8);
    if (kl < 0) {
      *p++ = static_cast<char>(255);
    } else {
      *p++ = static_cast<char>(kl);
      std::memcpy(p, s, kl);
      p += kl;
    }
    p = put_le(p, static_cast<uint64_t>(len - vs), 4);
    std::memcpy(p, s + vs, len - vs);
    p += len - vs;
  }
  o.len = p - o.buf;
  return o.len;
}

const char* kme_run_out() { return run_out.buf; }

}  // extern "C"

// ---------------------------------------------------------------------------
// kme_journal: a collected batch's reconstruction buffer -> the flight
// recorder's records (telemetry/journal.py: _REC, 96 bytes a record).
//
// The journal is evidence because it is made from the bytes that went
// to MatchOut, so this PARSES the buffer: each line is "KEY value" with
// the value in put_order's fixed shape (fast_line above). Semantics
// authority: journal.batch_events + Journal._commit's stamping +
// journal._encode; equivalence is pinned by tests/test_journal.py. A
// line of any other shape, or a value the record cannot hold, is an
// error and the caller takes that path for the batch instead.

namespace {

struct JRec {  // struct "<BBBBiii10q": naturally aligned, no padding
  uint8_t etype, rej, sh, pad;
  int32_t act, b, i;
  int64_t seq, ts, off, oid, aid, sid, px, qty, moid, maid;
};
static_assert(sizeof(JRec) == 96, "journal record is 96 bytes");

// journal.ETYPES, by index
enum : uint8_t { J_SUBMIT = 0, J_ACCEPT, J_REJECT, J_REST, J_FILL,
                 J_CANCEL, J_CREATE, J_TRANSFER, J_PAYOUT, J_ADD_SYMBOL,
                 J_REMOVE_SYMBOL };

constexpr int64_t REJ_UNSPECIFIED = 8;

// journal._ACT_EVENT: an accepted message that is no trade
inline uint8_t act_event(int64_t act) {
  switch (act) {
    case 4: return J_CANCEL;
    case 100: return J_CREATE;
    case 101: return J_TRANSFER;
    case 200: return J_PAYOUT;
    case 0: return J_ADD_SYMBOL;
    case 1: return J_REMOVE_SYMBOL;
    default: return J_ACCEPT;
  }
}

// wire.reason_for_reject: the cause by the rejected action, for
// engines that report none
inline int64_t reason_for_reject(int64_t act) {
  switch (act) {
    case 2: case 3: return 2;            // REJ_RISK
    case 4: return 3;                    // REJ_CANCEL
    case 1: case 200: return 5;          // REJ_BARRIER
    case 0: case 100: case 101: return 7;  // REJ_OTHER
    default: return REJ_UNSPECIFIED;
  }
}

// the eight integers of line `li`'s value (what follows its first
// space): action oid aid sid price size next prev
inline bool journal_line(const uint8_t* buf, int64_t len,
                         const int64_t* off, int64_t li, int64_t* v) {
  const int64_t s = off[li], e = off[li + 1];
  if (s < 0 || e < s || e > len) return false;
  const char* p = reinterpret_cast<const char*>(buf) + s;
  const char* end = reinterpret_cast<const char*>(buf) + e;
  const void* sp = std::memchr(p, ' ', end - p);
  if (!sp) return false;
  uint8_t has[8];
  return fast_line(static_cast<const char*>(sp) + 1, end, v, has);
}

inline bool fits_i32(int64_t v) { return v >= INT32_MIN && v <= INT32_MAX; }

}  // namespace

extern "C" {

// Records of the nmsg messages whose lines are buf[off[k], off[k+1])
// (msg_lines[i] lines for message i: the IN echo, OUT fill pairs, the
// OUT result echo), stamped seq0.., ts, b, sh, into `out` — room for
// (lines + nmsg) records is enough. `reasons` (per-message wire.REJ_*)
// and `offsets` (per-message input offsets) may be null: the action
// heuristic, -1. Returns the record count, or -(line + 1) for the
// first line this cannot take.
int64_t kme_journal_rows(const uint8_t* buf, int64_t len,
                         const int64_t* off, int64_t nmsg,
                         const int32_t* msg_lines, const int64_t* reasons,
                         const int64_t* offsets, int64_t seq0, int64_t ts,
                         int32_t b, int32_t sh, void* out) {
  JRec* rec = static_cast<JRec*>(out);
  int64_t n = 0, li = 0;
  int64_t m[8], res[8], mk[8], tk[8];
  for (int64_t i = 0; i < nmsg; i++) {
    const int64_t nl = msg_lines[i];
    if (nl < 1 || !journal_line(buf, len, off, li, m)) return -(li + 1);
    if (!fits_i32(m[0])) return -(li + 1);
    JRec base{};
    base.sh = static_cast<uint8_t>(sh);
    base.act = static_cast<int32_t>(m[0]);
    base.b = b;
    base.i = static_cast<int32_t>(i);
    base.ts = ts;
    base.off = offsets ? offsets[i] : -1;
    base.oid = m[1];
    base.aid = m[2];
    base.sid = m[3];
    base.px = m[4];
    base.qty = m[5];
    auto put = [&](uint8_t etype) -> JRec& {
      JRec& r = rec[n];
      r = base;
      r.etype = etype;
      r.seq = seq0 + n;
      n++;
      return r;
    };
    put(J_SUBMIT);
    if (nl >= 2) {
      const int64_t last = li + nl - 1;
      if (!journal_line(buf, len, off, last, res)) return -(last + 1);
      const bool rejected = res[0] == OP_REJECT;
      const bool trade = !rejected && (m[0] == 2 || m[0] == 3);
      if (rejected) {
        int64_t rej = reasons ? reasons[i] : reason_for_reject(m[0]);
        if (rej == 0) rej = REJ_UNSPECIFIED;
        if (rej < 0 || rej > 255) return -(last + 1);
        put(J_REJECT).rej = static_cast<uint8_t>(rej);
      } else if (trade) {
        // margin is reserved before matching: accept precedes fills
        put(J_ACCEPT);
      }
      // OUT pairs: the resting maker's line, then the taker's
      for (int64_t k = li + 1; k < last; k += 2) {
        if (!journal_line(buf, len, off, k, mk)) return -(k + 1);
        if (!journal_line(buf, len, off, k + 1, tk)) return -(k + 2);
        if (!trade) continue;
        int64_t px;
        if (!fits_i32(tk[0]) || __builtin_sub_overflow(m[4], tk[4], &px))
          return -(k + 2);
        JRec& r = put(J_FILL);
        r.act = static_cast<int32_t>(tk[0]);
        r.oid = tk[1];
        r.aid = tk[2];
        r.sid = tk[3];
        r.px = px;
        r.qty = tk[5];
        r.moid = mk[1];
        r.maid = mk[2];
      }
      if (trade && res[5] > 0) put(J_REST).qty = res[5];
      if (!rejected && !trade) put(act_event(m[0]));
    }
    li += nl;
  }
  return n;
}

}  // extern "C"
