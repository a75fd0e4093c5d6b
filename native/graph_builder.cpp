// libgraphbuild — native host-side graph builder for graphmine_tpu.
//
// The TPU-native replacement for the host/JVM work the reference pipeline
// delegated to Spark (CommunityDetection/Graphframes.py:53-74: RDD flatMap/
// distinct + per-row sha1 UDFs): streaming edge-list parsing and string
// interning to dense int32 vertex ids, in one pass, no Python per-row cost.
// Exposed to Python via ctypes (graphmine_tpu/io/native.py).
//
// Build: make -C native    (g++ -O3 -shared -fPIC)

#include <algorithm>
#include <cstdint>
#include <cstdio>
#include <cstdlib>
#include <cstring>
#include <functional>
#include <new>
#include <string>
#include <string_view>
#include <thread>
#include <unordered_map>
#include <vector>

namespace {

struct Interner {
  std::unordered_map<std::string, int32_t> map;
  std::vector<std::string> names;
  // Column count of the first data line of a chunked parse session; later
  // lines must match (np.loadtxt's rectangularity contract — the NumPy
  // paths raise "number of columns changed"). Lives here because the
  // interner IS the cross-chunk session state.
  int32_t ncols = -1;

  int32_t intern(std::string_view s) {
    auto it = map.find(std::string(s));
    if (it != map.end()) return it->second;
    int32_t id = static_cast<int32_t>(names.size());
    names.emplace_back(s);
    map.emplace(names.back(), id);
    return id;
  }
};

bool read_file(const char* path, std::string* out) {
  FILE* f = std::fopen(path, "rb");
  if (!f) return false;
  std::fseek(f, 0, SEEK_END);
  long n = std::ftell(f);
  if (n < 0) {
    std::fclose(f);
    return false;
  }
  std::fseek(f, 0, SEEK_SET);
  out->resize(static_cast<size_t>(n));
  size_t got = n ? std::fread(out->data(), 1, static_cast<size_t>(n), f) : 0;
  std::fclose(f);
  return got == static_cast<size_t>(n);
}

}  // namespace

extern "C" {

// Parses a whitespace-separated edge list ("src dst" per line; lines whose
// first non-space char equals `comment` are skipped). Returns the edge count
// (>= 0) and malloc'd arrays the caller must free via gb_free/gb_free_names,
// -1 on I/O error, -3 when a non-comment data line has fewer than 2
// tokens, or -4 when the column count changes between data lines (ADVICE
// r3 / code-review r4: all ingestion paths reject malformed files the
// same way np.loadtxt does). Endpoint tokens may be arbitrary strings;
// they are interned to dense int32 ids in first-appearance order
// (matching the NumPy fallback in graphmine_tpu/io/factorize.py).
int64_t gb_load_edge_list(const char* path, char comment, int32_t** src_out,
                          int32_t** dst_out, char*** names_out,
                          int64_t* num_vertices) {
  std::string buf;
  if (!read_file(path, &buf)) return -1;

  Interner interner;
  std::vector<int32_t> src, dst;
  const char* p = buf.data();
  const char* end = p + buf.size();
  while (p < end) {
    const char* line_end = static_cast<const char*>(memchr(p, '\n', end - p));
    if (!line_end) line_end = end;
    // Truncate at the comment char ANYWHERE in the line (np.loadtxt
    // semantics, which every NumPy fallback path inherits): "a b # note"
    // is an edge, "c # note" is a 1-token malformed line, a line whose
    // first char is the comment becomes blank. Parsing must not depend
    // on whether the .so is built.
    const char* cpos =
        static_cast<const char*>(memchr(p, comment, line_end - p));
    const char* data_end = cpos ? cpos : line_end;
    const char* q = p;
    while (q < data_end && (*q == ' ' || *q == '\t' || *q == '\r')) ++q;
    if (q < data_end) {
      const char* t0 = q;
      while (q < data_end && *q != ' ' && *q != '\t' && *q != '\r') ++q;
      const char* t0e = q;
      while (q < data_end && (*q == ' ' || *q == '\t')) ++q;
      const char* t1 = q;
      while (q < data_end && *q != ' ' && *q != '\t' && *q != '\r') ++q;
      const char* t1e = q;
      if (t0e > t0 && t1e > t1) {
        // Count the remaining tokens: np.loadtxt rejects files whose
        // data lines change column count ("number of columns changed"),
        // and .so parity demands the same verdict (code-review r4).
        int32_t tok = 2;
        while (q < data_end) {
          while (q < data_end && (*q == ' ' || *q == '\t' || *q == '\r')) ++q;
          const char* s0 = q;
          while (q < data_end && *q != ' ' && *q != '\t' && *q != '\r') ++q;
          if (q > s0) ++tok;
        }
        if (interner.ncols < 0) {
          interner.ncols = tok;
        } else if (tok != interner.ncols) {
          return -4;
        }
        src.push_back(interner.intern({t0, size_t(t0e - t0)}));
        dst.push_back(interner.intern({t1, size_t(t1e - t1)}));
      } else {
        // A non-comment data line with fewer than 2 tokens: hard error
        // (-3), matching the NumPy paths' "needs >= 2 columns" raise —
        // silently dropping edges of a malformed file is the worse bug.
        return -3;
      }
    }
    p = line_end + 1;
  }

  int64_t ne = static_cast<int64_t>(src.size());
  int64_t nv = static_cast<int64_t>(interner.names.size());
  *src_out = static_cast<int32_t*>(malloc(sizeof(int32_t) * (ne ? ne : 1)));
  *dst_out = static_cast<int32_t*>(malloc(sizeof(int32_t) * (ne ? ne : 1)));
  *names_out = static_cast<char**>(malloc(sizeof(char*) * (nv ? nv : 1)));
  if (!*src_out || !*dst_out || !*names_out) return -1;
  if (ne) {
    memcpy(*src_out, src.data(), sizeof(int32_t) * ne);
    memcpy(*dst_out, dst.data(), sizeof(int32_t) * ne);
  }
  for (int64_t i = 0; i < nv; ++i) {
    const std::string& s = interner.names[static_cast<size_t>(i)];
    char* c = static_cast<char*>(malloc(s.size() + 1));
    if (!c) return -1;
    memcpy(c, s.data(), s.size() + 1);
    (*names_out)[i] = c;
  }
  *num_vertices = nv;
  return ne;
}

// ---------------------------------------------------------------------------
// Chunked streaming parse (r3): the whole-file gb_load_edge_list above walls
// out at host RAM for top-rung edge lists (Twitter-2010 text is ~25 GB). The
// chunk API keeps ONE interner alive across calls while the caller feeds
// bounded buffers of complete lines — peak memory is O(chunk + vocabulary +
// edges-so-far int32), the same discipline as the parquet batch_rows path
// (graphmine_tpu/io/edges.py). Weighted columns parse natively here too
// (the old path pushed every weighted load through np.loadtxt(dtype=str)).
// ---------------------------------------------------------------------------

void* gb_interner_new() { return new (std::nothrow) Interner(); }

void gb_interner_free(void* it) { delete static_cast<Interner*>(it); }

int64_t gb_interner_size(void* it) {
  return static_cast<int64_t>(static_cast<Interner*>(it)->names.size());
}

// Snapshot of the interner's names (malloc'd; free via gb_free_names).
// On allocation failure everything already allocated is freed and
// *names_out is nulled — callers never inherit a partial buffer.
int64_t gb_interner_names(void* it, char*** names_out) {
  Interner* interner = static_cast<Interner*>(it);
  int64_t nv = static_cast<int64_t>(interner->names.size());
  *names_out = static_cast<char**>(malloc(sizeof(char*) * (nv ? nv : 1)));
  if (!*names_out) return -1;
  for (int64_t i = 0; i < nv; ++i) {
    const std::string& s = interner->names[static_cast<size_t>(i)];
    char* c = static_cast<char*>(malloc(s.size() + 1));
    if (!c) {
      for (int64_t j = 0; j < i; ++j) free((*names_out)[j]);
      free(*names_out);
      *names_out = nullptr;
      return -1;
    }
    memcpy(c, s.data(), s.size() + 1);
    (*names_out)[i] = c;
  }
  return nv;
}

// Parse a buffer of complete lines ("src dst [cols...]"), interning through
// the shared interner. weight_col: -1 = unweighted, else the 0-based token
// index of a float weight (>= 2; tokens 0-1 are the endpoints). Returns the
// edge count and malloc'd arrays (w_out only when weighted), -1 on
// allocation failure, -2 when a data line lacks the weight token or it does
// not parse as a float, -3 when a non-comment data line has fewer than 2
// tokens, -4 when the column count changes between data lines (all
// matching the NumPy fallback's hard errors; -4 spans chunks via the
// interner's ncols).
int64_t gb_parse_edge_chunk(void* it, const char* buf, int64_t len,
                            char comment, int32_t weight_col,
                            int32_t** src_out, int32_t** dst_out,
                            float** w_out) {
  Interner* interner = static_cast<Interner*>(it);
  std::vector<int32_t> src, dst;
  std::vector<float> w;
  const char* p = buf;
  const char* end = buf + len;
  while (p < end) {
    const char* line_end = static_cast<const char*>(memchr(p, '\n', end - p));
    if (!line_end) line_end = end;
    // Truncate at the comment char ANYWHERE in the line (np.loadtxt
    // semantics, matching every NumPy fallback path): "a b # note" is an
    // edge, "c # note" a 1-token malformed line, a leading-comment line
    // blank. Parsing must not depend on whether the .so is built.
    const char* cpos =
        static_cast<const char*>(memchr(p, comment, line_end - p));
    const char* data_end = cpos ? cpos : line_end;
    const char* q = p;
    while (q < data_end && (*q == ' ' || *q == '\t' || *q == '\r')) ++q;
    if (q < data_end) {
      // Tokenize; endpoints are tokens 0-1, the weight (if any) token
      // `weight_col`.
      const char* t[2] = {nullptr, nullptr};
      const char* te[2] = {nullptr, nullptr};
      const char* wt = nullptr;
      const char* wte = nullptr;
      int32_t tok = 0;
      while (q < data_end) {
        const char* s0 = q;
        while (q < data_end && *q != ' ' && *q != '\t' && *q != '\r') ++q;
        if (q > s0) {
          if (tok < 2) {
            t[tok] = s0;
            te[tok] = q;
          } else if (tok == weight_col) {
            wt = s0;
            wte = q;
          }
          ++tok;
        }
        while (q < data_end && (*q == ' ' || *q == '\t' || *q == '\r')) ++q;
      }
      if (!te[1]) {
        // Data line with < 2 tokens (te[0] is always set: the guard above
        // saw a non-space data char). -3, the same hard error the NumPy
        // paths raise as "needs >= 2 columns" (ADVICE r3).
        return -3;
      }
      if (interner->ncols < 0) {
        interner->ncols = tok;
      } else if (tok != interner->ncols) {
        // np.loadtxt rectangularity: a file whose data lines change
        // column count is rejected by the NumPy paths — .so parity
        // demands the same verdict (code-review r4).
        return -4;
      }
      if (weight_col >= 0) {
        if (!wt) return -2;
        char tmp[64];
        size_t n = static_cast<size_t>(wte - wt);
        if (n >= sizeof(tmp)) return -2;
        memcpy(tmp, wt, n);
        tmp[n] = '\0';
        char* parse_end = nullptr;
        float val = strtof(tmp, &parse_end);
        if (parse_end != tmp + n) return -2;
        w.push_back(val);
      }
      src.push_back(interner->intern({t[0], size_t(te[0] - t[0])}));
      dst.push_back(interner->intern({t[1], size_t(te[1] - t[1])}));
    }
    p = line_end + 1;
  }

  int64_t ne = static_cast<int64_t>(src.size());
  *src_out = static_cast<int32_t*>(malloc(sizeof(int32_t) * (ne ? ne : 1)));
  *dst_out = static_cast<int32_t*>(malloc(sizeof(int32_t) * (ne ? ne : 1)));
  if (!*src_out || !*dst_out) {
    // no partial buffers survive a failed allocation
    free(*src_out);
    free(*dst_out);
    *src_out = nullptr;
    *dst_out = nullptr;
    return -1;
  }
  if (ne) {
    memcpy(*src_out, src.data(), sizeof(int32_t) * ne);
    memcpy(*dst_out, dst.data(), sizeof(int32_t) * ne);
  }
  if (weight_col >= 0 && w_out) {
    *w_out = static_cast<float*>(malloc(sizeof(float) * (ne ? ne : 1)));
    if (!*w_out) {
      free(*src_out);
      free(*dst_out);
      *src_out = nullptr;
      *dst_out = nullptr;
      return -1;
    }
    if (ne) memcpy(*w_out, w.data(), sizeof(float) * ne);
  }
  return ne;
}

namespace {

// Shared body of the message-CSR builders (graphmine_tpu/graph/container.py
// contract): messages grouped by receiver in stable (input) order; when
// `symmetric`, messages flow both directions (recv = concat(dst, src),
// send = the opposite endpoints). A stable counting sort — O(M + V) vs
// NumPy's O(M log M) argsort, the hot host-side step of every graph build.
// `weights`/`w_sorted` are nullable: when present, both directions of an
// edge carry its weight through the same permutation.
// Stable counting sort of the messages by receiver, in threads over
// receiver ranges. Every thread reads all the edges (sequential, cheap)
// and counts, then places, only the messages whose receiver lies in its
// own range: a receiver's cursor has one owner, its dst-direction messages
// (edge order) still come before its src-direction ones (edge order), so
// the layout is the single-threaded one byte for byte — while the random
// writes of a thread stay inside 1/T of the arrays instead of all of them
// (at 10^9 messages the one-thread sort is a cache miss per message).
// Threads over ranges of a key space [0, v), for a counting sort of n
// items: every thread reads all the items and owns the keys of one range.
struct KeyRanges {
  int64_t v;
  int64_t threads = 1;
  KeyRanges(int64_t n, int64_t v) : v(v) {
    if (n >= (int64_t{1} << 22)) {
      threads = static_cast<int64_t>(std::thread::hardware_concurrency());
      threads = std::max<int64_t>(1, std::min<int64_t>(threads, 16));
      threads = std::min(threads, std::max<int64_t>(v, 1));
    }
  }
  int64_t bound(int64_t t) const { return v * t / threads; }
  // body(lo, hi) once per range, the first on the calling thread
  void run(const std::function<void(int64_t, int64_t)>& body) const {
    std::vector<std::thread> pool;
    for (int64_t t = 1; t < threads; ++t) {
      pool.emplace_back(body, bound(t), bound(t + 1));
    }
    body(bound(0), bound(1));
    for (auto& th : pool) th.join();
  }
};

int build_csr_impl(const int32_t* src, const int32_t* dst,
                   const float* weights, int64_t e, int64_t v, int symmetric,
                   int64_t* ptr, int32_t* recv_sorted, int32_t* send_sorted,
                   float* w_sorted) {
  for (int64_t i = 0; i < e; ++i) {
    if (src[i] < 0 || src[i] >= v || dst[i] < 0 || dst[i] >= v) return -1;
  }
  const KeyRanges receivers(e, v);
  // recv of message i: dst[i] for i < e, then src[i - e] (symmetric only).
  // ptr[r + 1] first holds receiver r's count, then the running sum.
  memset(ptr, 0, sizeof(int64_t) * (static_cast<size_t>(v) + 1));
  receivers.run([&](int64_t lo, int64_t hi) {
    for (int64_t i = 0; i < e; ++i) {
      if (dst[i] >= lo && dst[i] < hi) ++ptr[static_cast<size_t>(dst[i]) + 1];
    }
    if (symmetric) {
      for (int64_t i = 0; i < e; ++i) {
        if (src[i] >= lo && src[i] < hi) ++ptr[static_cast<size_t>(src[i]) + 1];
      }
    }
  });
  for (int64_t i = 0; i < v; ++i) ptr[i + 1] += ptr[i];
  std::vector<int64_t> cursor(ptr, ptr + v);
  receivers.run([&](int64_t lo, int64_t hi) {
    for (int64_t i = 0; i < e; ++i) {
      if (dst[i] < lo || dst[i] >= hi) continue;
      int64_t pos = cursor[static_cast<size_t>(dst[i])]++;
      recv_sorted[pos] = dst[i];
      send_sorted[pos] = src[i];
      if (weights) w_sorted[pos] = weights[i];
    }
    if (!symmetric) return;
    for (int64_t i = 0; i < e; ++i) {
      if (src[i] < lo || src[i] >= hi) continue;
      int64_t pos = cursor[static_cast<size_t>(src[i])]++;
      recv_sorted[pos] = src[i];
      send_sorted[pos] = dst[i];
      if (weights) w_sorted[pos] = weights[i];
    }
  });
  return 0;
}

}  // namespace

// Caller allocates: ptr[v+1] (int64), recv_sorted[m], send_sorted[m]
// (int32) where m = symmetric ? 2*e : e. Returns 0, or -1 when an endpoint
// is out of [0, v) — nothing is written in that case.
int gb_build_message_csr(const int32_t* src, const int32_t* dst, int64_t e,
                         int64_t v, int symmetric, int64_t* ptr,
                         int32_t* recv_sorted, int32_t* send_sorted) {
  return build_csr_impl(src, dst, nullptr, e, v, symmetric, ptr, recv_sorted,
                        send_sorted, nullptr);
}

// Weighted variant of gb_build_message_csr: same layout plus the float32
// weight payload. A separate entry point keeps the ABI compatible with
// older libgraphbuild.so builds.
int gb_build_message_csr_weighted(const int32_t* src, const int32_t* dst,
                                  const float* weights, int64_t e, int64_t v,
                                  int symmetric, int64_t* ptr,
                                  int32_t* recv_sorted, int32_t* send_sorted,
                                  float* w_sorted) {
  return build_csr_impl(src, dst, weights, e, v, symmetric, ptr, recv_sorted,
                        send_sorted, w_sorted);
}

// Positions grouped by key, a stable counting sort in threads over key
// ranges (as build_csr_impl): for each key k in [0, v), the positions i
// with keys[i] == k in ascending order; keys outside [0, v) name nothing.
// Caller allocates ptr[v+1] (int64) and out[number of keys in range]
// (int32; n at most, n < 2^31). The slot index of the carried-rows LPA
// scan (ops/bucketed_mode.py:with_slot_index): keys are the senders behind
// a plan's padded slots, positions the flat slots.
void gb_positions_by_key(const int32_t* keys, int64_t n, int64_t v,
                         int64_t* ptr, int32_t* out) {
  const KeyRanges owners(n, v);
  memset(ptr, 0, sizeof(int64_t) * (static_cast<size_t>(v) + 1));
  owners.run([&](int64_t lo, int64_t hi) {
    for (int64_t i = 0; i < n; ++i) {
      if (keys[i] >= lo && keys[i] < hi) ++ptr[static_cast<size_t>(keys[i]) + 1];
    }
  });
  for (int64_t i = 0; i < v; ++i) ptr[i + 1] += ptr[i];
  std::vector<int64_t> cursor(ptr, ptr + v);
  owners.run([&](int64_t lo, int64_t hi) {
    for (int64_t i = 0; i < n; ++i) {
      if (keys[i] < lo || keys[i] >= hi) continue;
      out[cursor[static_cast<size_t>(keys[i])]++] = static_cast<int32_t>(i);
    }
  });
}

void gb_free(void* p) { free(p); }

void gb_free_names(char** names, int64_t n) {
  if (!names) return;
  for (int64_t i = 0; i < n; ++i) free(names[i]);
  free(names);
}

}  // extern "C"
