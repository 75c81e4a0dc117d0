// Native ASA syslog parser + tuple packer (the host-side hot loop).
//
// SURVEY.md §8.2 names host-side syslog parsing as the end-to-end
// bottleneck at target rates: the device pipeline sustains millions of
// lines/sec/chip, so a Python regex parser starves it.  This library is
// the native tier of the runtime: it parses raw ASA syslog bytes and
// packs valid lines directly into the column-major [TUPLE_COLS, B]
// uint32 batch layout the device step consumes — one pass, no Python
// objects, no regex engine.
//
// Semantics mirror ruleset_analysis_tpu/hostside/syslog.py (parse_line)
// and pack.py (LinePacker) exactly; tests/test_fastparse.py asserts the
// two paths produce identical batches on synthetic and edge-case
// corpora.  Both paths skip lines whose IPv4 octets, ports (> 65535) or
// protocol numbers (> 255) exceed their field widths.
//
// SIMD layout (ISSUE 11): the line parser body lives in
// asaparse_line.inl and compiles once per ISA — the scalar reference
// here, AVX2 in asaparse_avx2.cpp, NEON in asaparse_neon.cpp — with the
// ISA's scan kernels inlined into the tokenizer loops.  This TU owns the
// runtime dispatch (CPU probe, RA_SIMD override, asa_simd_set A/B
// switch): chunk loops resolve ONE handle-line pointer per call and the
// bulk newline scans go through the ra_simd::ScanOps table.  Outputs are
// byte-identical across every dispatch state (the 12k mutant sweep in
// tests/test_fastparse.py pins it).
//
// C ABI only (loaded via ctypes; no pybind11 in this image).

#include <atomic>
#include <condition_variable>
#include <cstddef>
#include <cstdint>
#include <cstdlib>
#include <cstring>
#include <mutex>
#include <string>
#include <thread>
#include <unordered_map>
#include <vector>

#include "asaparse_types.h"
#include "simd_scan.h"

// ---------------------------------------------------------------------------
// Scalar scan kernels for the reference build of the line parser: plain
// byte loops (the compiler may auto-vectorize under -march=native, but
// the SEMANTICS are the reference), and a dotted-quad hook that always
// defers to the inline scalar parse.
// ---------------------------------------------------------------------------

static inline const char* ra_scan_token_end(const char* p, const char* end) {
    while (p < end &&
           !(*p == ' ' || *p == '\t' || *p == '\v' || *p == '\f' ||
             *p == '\r' || *p == '\n'))
        ++p;
    return p;
}

static inline const char* ra_scan_addr_end(const char* p, const char* end) {
    while (p < end &&
           ((*p >= '0' && *p <= '9') || (*p >= 'a' && *p <= 'f') ||
            (*p >= 'A' && *p <= 'F') || *p == ':' || *p == '.'))
        ++p;
    return p;
}

static inline int ra_scan_ipv4(const char** pp, const char* end,
                               uint32_t* out) {
    (void)pp;
    (void)end;
    (void)out;
    return -1;  // always use the inline scalar reference parse
}

#define RA_PARSE_NS ra_scalar
#include "asaparse_line.inl"
#undef RA_PARSE_NS

namespace ra_parse {
HandleLineFn scalar_handle_line() { return &ra_scalar::handle_line; }
}  // namespace ra_parse

namespace {

using ra_parse::HandleLineFn;
using ra_parse::LocalCtx;
using ra_parse::Packer;
using ra_parse::RawBuf;
using ra_parse::WorkerScratch;

constexpr int64_t TUPLE_COLS = 7;
constexpr int64_t TUPLE6_COLS = 13;

// ---------------------------------------------------------------------------
// Runtime SIMD dispatch: ONE handle-line pointer (whole-line parser,
// per-ISA build) plus one ScanOps table (bulk newline scans).  Selected
// once per process from the CPU probe; RA_SIMD=off/0/false forces
// scalar, asa_simd_set() flips at runtime so one process can A/B both
// sides of the identity sweep and the feedscale bench.
// ---------------------------------------------------------------------------

std::atomic<HandleLineFn> g_handle{nullptr};
std::atomic<const ra_simd::ScanOps*> g_scan_ops{nullptr};
std::once_flag g_simd_once;

void pick_dispatch(bool simd_on) {
    HandleLineFn h = nullptr;
    const ra_simd::ScanOps* o = nullptr;
    if (simd_on) {
        h = ra_parse::avx2_handle_line();
        if (!h) h = ra_parse::neon_handle_line();
        o = ra_simd::avx2_ops();
        if (!o) o = ra_simd::neon_ops();
    }
    g_handle.store(h ? h : ra_parse::scalar_handle_line(),
                   std::memory_order_relaxed);
    g_scan_ops.store(o, std::memory_order_relaxed);
}

void simd_init() {
    std::call_once(g_simd_once, [] {
        const char* e = std::getenv("RA_SIMD");
        bool off = e && (strcmp(e, "off") == 0 || strcmp(e, "0") == 0 ||
                         strcmp(e, "false") == 0);
        pick_dispatch(!off);
    });
}

inline HandleLineFn handle_line_fn() {
    return g_handle.load(std::memory_order_relaxed);
}

inline const ra_simd::ScanOps* scan_ops() {
    return g_scan_ops.load(std::memory_order_relaxed);
}

// Zero rows [from, to) of every column of a column-major [cols, cap]
// plane.  Callers allocate the output uninitialised (np.empty); the
// contract is "padding rows are all-zero", matching the pure-Python
// LinePacker while writing only the padding.
void zero_rows(uint32_t* out, int64_t cols, int64_t cap, int64_t from,
               int64_t to) {
    if (to <= from) return;
    for (int64_t c = 0; c < cols; ++c)
        memset(out + c * cap + from, 0, (size_t)(to - from) * sizeof(uint32_t));
}

// Barrier over the workers of one call.  The last thread to arrive runs
// `serial` (the cut and the prefix sums between two passes) before any
// thread is released, so every worker sees its results.
class Barrier {
  public:
    explicit Barrier(int n) : n_(n) {}

    template <class F>
    void arrive_and_wait(F&& serial) {
        std::unique_lock<std::mutex> lk(m_);
        const uint64_t gen = gen_;
        if (++waiting_ == n_) {
            serial();
            waiting_ = 0;
            ++gen_;
            cv_.notify_all();
            return;
        }
        cv_.wait(lk, [&] { return gen_ != gen; });
    }

  private:
    std::mutex m_;
    std::condition_variable cv_;
    const int n_;
    int waiting_ = 0;
    uint64_t gen_ = 0;
};

// Offsets from p of every '\n' in [p, p + n), into the grow-only `nl`;
// returns how many.  The SIMD path gathers positions 32 bytes at a time
// (classify + movemask) instead of one memchr call per line.
int64_t scan_newlines(const char* p, int64_t n, RawBuf<uint32_t>& nl) {
    const ra_simd::ScanOps* ops = scan_ops();
    uint32_t* out = nl.reserve((size_t)(n / 64 + 1024));
    int64_t c = 0, from = 0;  // found so far; where the scan resumes
    for (;;) {
        const int64_t room = (int64_t)nl.n - c;
        int64_t got = 0;
        if (ops) {
            got = ops->nl_positions(p + from, n - from, out + c, room);
        } else {
            const char* q = p + from;
            const char* end = p + n;
            while (got < room &&
                   (q = (const char*)memchr(q, '\n', end - q)) != nullptr) {
                out[c + got++] = (uint32_t)(q - (p + from));
                ++q;
            }
        }
        if (from)
            for (int64_t i = c; i < c + got; ++i) out[i] += (uint32_t)from;
        c += got;
        if (got < room) return c;
        // full: grow, and resume one past the last newline found
        from = (int64_t)out[c - 1] + 1;
        out = nl.reserve(2 * nl.n, (size_t)c);
    }
}

// The one-thread streaming loop: the reference semantics, and the path
// for a single worker.  Batches are line-atomic: when a line's rows (up
// to two: in- and out-evaluation) don't fit, it stays unconsumed and
// opens the next batch, exactly like the Python _TextSource.
int64_t pack_serial(Packer* pk, HandleLineFn handle, const char* buf,
                    int64_t len, int final_, int full_batch, int64_t max_lines,
                    uint32_t* out, int64_t cap, uint32_t* out6, int64_t cap6,
                    int64_t* n_lines_out, int64_t* n_valid_out,
                    int64_t* n_valid6_out) {
    LocalCtx cx{&pk->resolve, {}};
    const char* end = buf + len;
    const char* p = buf;
    int64_t lines = 0, valid = 0, valid6 = 0, parsed = 0, skipped = 0;
    bool closed = false;  // a line's rows did not fit
    while (p < end && lines < max_lines) {
        const char* nl = (const char*)memchr(p, '\n', end - p);
        if (!nl && !final_) break;  // incomplete tail line
        const char* le = nl ? nl : end;
        const int64_t v6_before = valid6;
        int n = handle(&cx, p, le, out, cap, valid, out6, cap6,
                       out6 ? &valid6 : nullptr);
        if (n < 0) {
            closed = true;
            break;
        }
        if (n == 0) ++skipped;
        else {
            parsed += n;
            if (valid6 == v6_before) valid += n;  // v4 rows advanced
        }
        ++lines;
        p = nl ? nl + 1 : end;
    }
    if (full_batch && !final_ && !closed && lines < max_lines) {
        *n_lines_out = lines;  // fewer than a batch of complete lines
        return -1;
    }
    pk->parsed += parsed;
    pk->skipped += skipped;
    zero_rows(out, TUPLE_COLS, cap, valid, cap);
    if (out6) zero_rows(out6, TUPLE6_COLS, cap6, valid6, cap6);
    *n_lines_out = lines;
    *n_valid_out = valid;
    *n_valid6_out = valid6;
    return p - buf;
}

// One worker's share of one parallel call.
struct Part {
    int64_t b0 = 0, b1 = 0;  // its byte range of the buffer
    int64_t n_nl = 0;        // newlines in that range
    int64_t g0 = 0;          // global index of the first of them
    int64_t l0 = 0, l1 = 0;  // the lines it parses
    int64_t slab_cap = 0;    // rows its slabs hold: 2 per line
    int64_t rows4 = 0, rows6 = 0, skip = 0;  // its lines' rows; lines with none
    int64_t take = 0, take4 = 0, take6 = 0;  // of those, what the batch consumes
    int64_t at4 = 0, at6 = 0;                // where its rows land in out, out6
};

// The parallel pass: every byte-proportional step runs in the W workers.
//   1. Each worker scans its own byte range for newlines.  A prefix sum
//      over the W counts places the cut after line `want` (fewer at the
//      end of the input) and gives each worker an even share of lines.
//   2. Each worker parses its lines into its slabs (kept by the Packer,
//      never zero-filled) with a thread-local context, recording each
//      line's row count.  The line-atomic row cap then fixes K, the lines
//      consumed, exactly as the one-thread loop would, and a prefix sum
//      gives each worker's row offset in out.
//   3. Each worker copies its consumed rows into out at that offset and
//      zeroes its share of the padding rows.
int64_t pack_parallel(Packer* pk, HandleLineFn handle, const char* buf,
                      int64_t len, int final_, int full_batch, int64_t want,
                      int W, uint32_t* out, int64_t cap, uint32_t* out6,
                      int64_t cap6, int64_t* n_lines_out,
                      int64_t* n_valid_out, int64_t* n_valid6_out) {
    if ((int)pk->scratch.size() < W) pk->scratch.resize((size_t)W);
    std::vector<WorkerScratch>& scratch = pk->scratch;
    std::vector<Part> part((size_t)W);
    for (int w = 0; w < W; ++w) {
        part[w].b0 = len * w / W;
        part[w].b1 = len * (w + 1) / W;
    }
    // byte offset one past global newline i
    auto nl_end = [&](int64_t i) -> int64_t {
        int w = 0;
        while (i >= part[w].g0 + part[w].n_nl) ++w;
        return part[w].b0 + scratch[w].nl.p[i - part[w].g0] + 1;
    };
    int64_t N = 0;        // complete lines in the buffer
    int64_t L = 0;        // lines parsed: the batch and at most the fragment
    int64_t end_L = 0;    // one past line L - 1
    int64_t K = 0, valid = 0, valid6 = 0, parsed = 0, skipped = 0, used = 0;
    bool is_short = false;
    Barrier bar(W);

    auto work = [&](int w) {
        Part& P = part[w];
        WorkerScratch& S = scratch[w];
        // ---- pass 1: newlines of this worker's byte range; the cut
        P.n_nl = scan_newlines(buf + P.b0, P.b1 - P.b0, S.nl);
        bar.arrive_and_wait([&] {
            for (int v = 0; v < W; ++v) {
                part[v].g0 = N;
                N += part[v].n_nl;
            }
            if (N >= want) {
                L = want;
                end_L = want ? nl_end(want - 1) : 0;
            } else if (final_) {  // the unterminated fragment is a line
                L = N + ((N ? nl_end(N - 1) : 0) < len ? 1 : 0);
                end_L = len;
            } else if (full_batch) {
                is_short = true;
            } else {
                L = N;
                end_L = N ? nl_end(N - 1) : 0;
            }
            for (int v = 0; v < W; ++v) {
                part[v].l0 = L * v / W;
                part[v].l1 = L * (v + 1) / W;
            }
        });
        if (is_short) return;

        // ---- pass 2: parse lines [l0, l1) into this worker's slabs
        const int64_t n = P.l1 - P.l0;
        P.slab_cap = 2 * n;
        uint32_t* slab4 = S.slab4.reserve((size_t)(TUPLE_COLS * P.slab_cap));
        uint32_t* slab6 =
            out6 ? S.slab6.reserve((size_t)(TUPLE6_COLS * P.slab_cap)) : nullptr;
        uint8_t* r4 = S.rows4.reserve((size_t)n);
        uint8_t* r6 = out6 ? S.rows6.reserve((size_t)n) : nullptr;
        if (n > 0) {
            // line l0 starts one past newline l0 - 1; each line ends at the
            // next newline, found by walking the workers' newline lists
            int v = 0;
            while (v < W - 1 && P.l0 >= part[v].g0 + part[v].n_nl) ++v;
            int64_t j = P.l0 - part[v].g0;
            const char* ls = buf + (P.l0 ? nl_end(P.l0 - 1) : 0);
            LocalCtx cx{&pk->resolve, {}};
            int64_t v4 = 0, v6 = 0, skip = 0;
            for (int64_t i = 0; i < n; ++i) {
                const char* le = buf + len;  // the unterminated fragment
                if (P.l0 + i < N) {
                    while (j == part[v].n_nl) {
                        ++v;
                        j = 0;
                    }
                    le = buf + part[v].b0 + scratch[v].nl.p[j++];
                }
                const int64_t v6_before = v6;
                int r = handle(&cx, ls, le, slab4, P.slab_cap, v4, slab6,
                               P.slab_cap, out6 ? &v6 : nullptr);
                // r < 0 impossible: slab caps are 2 * the worker's lines
                if (r > 0 && v6 != v6_before) {
                    r4[i] = 0;
                    r6[i] = (uint8_t)r;
                } else {
                    r4[i] = (uint8_t)(r > 0 ? r : 0);
                    if (r6) r6[i] = 0;
                    v4 += r4[i];
                    if (r <= 0) ++skip;
                }
                ls = le + 1;
            }
            P.rows4 = v4;
            P.rows6 = v6;
            P.skip = skip;
        }
        bar.arrive_and_wait([&] {
            // the line-atomic row cap on the v4 plane: K maximal with the
            // rows of lines 0..K-1 fitting in cap (cap6 >= 2 * max_lines by
            // the caller's contract, so v6 rows never close a batch)
            bool closed = false;
            for (int v = 0; v < W; ++v) {
                Part& Q = part[v];
                Q.at4 = valid;
                Q.at6 = valid6;
                if (closed) continue;
                if (valid + Q.rows4 <= cap) {
                    Q.take = Q.l1 - Q.l0;
                    Q.take4 = Q.rows4;
                    Q.take6 = Q.rows6;
                    skipped += Q.skip;
                } else {  // the first line that does not fit is in here
                    const uint8_t* q4 = scratch[v].rows4.p.get();
                    const uint8_t* q6 = out6 ? scratch[v].rows6.p.get() : nullptr;
                    int64_t i = 0;
                    for (; valid + Q.take4 + q4[i] <= cap; ++i) {
                        const int64_t rr6 = q6 ? q6[i] : 0;
                        Q.take4 += q4[i];
                        Q.take6 += rr6;
                        if (q4[i] == 0 && rr6 == 0) ++skipped;
                    }
                    Q.take = i;
                    closed = true;
                }
                K += Q.take;
                valid += Q.take4;
                valid6 += Q.take6;
                parsed += Q.take4 + Q.take6;
            }
            used = K < L ? (K ? nl_end(K - 1) : 0) : end_L;
        });

        // ---- pass 3: consumed rows into out; this worker's padding share
        if (P.take4)
            for (int64_t c = 0; c < TUPLE_COLS; ++c)
                memcpy(out + c * cap + P.at4, slab4 + c * P.slab_cap,
                       (size_t)P.take4 * sizeof(uint32_t));
        if (P.take6)
            for (int64_t c = 0; c < TUPLE6_COLS; ++c)
                memcpy(out6 + c * cap6 + P.at6, slab6 + c * P.slab_cap,
                       (size_t)P.take6 * sizeof(uint32_t));
        zero_rows(out, TUPLE_COLS, cap, valid + (cap - valid) * w / W,
                  valid + (cap - valid) * (w + 1) / W);
        if (out6)
            zero_rows(out6, TUPLE6_COLS, cap6, valid6 + (cap6 - valid6) * w / W,
                      valid6 + (cap6 - valid6) * (w + 1) / W);
    };

    std::vector<std::thread> threads;
    threads.reserve((size_t)(W - 1));
    for (int w = 1; w < W; ++w) threads.emplace_back(work, w);
    work(0);
    for (auto& t : threads) t.join();
    if (is_short) {
        *n_lines_out = N;
        return -1;
    }
    pk->parsed += parsed;
    pk->skipped += skipped;
    *n_lines_out = K;
    *n_valid_out = valid;
    *n_valid6_out = valid6;
    return used;
}

}  // namespace

extern "C" {

void* asa_packer_new() {
    simd_init();
    return new Packer();
}

void asa_packer_free(void* h) { delete (Packer*)h; }

void asa_packer_add_acl(void* h, const char* fw, const char* acl, uint32_t gid) {
    Packer* pk = (Packer*)h;
    std::string k(fw);
    k.push_back('\x01');
    k += acl;
    pk->resolve[k] = gid;
}

void asa_packer_add_binding(void* h, const char* fw, const char* iface, uint32_t gid) {
    Packer* pk = (Packer*)h;
    std::string k(fw);
    k.push_back('\x02');
    k += iface;
    pk->resolve[k] = gid;
}

// out-direction access-group: (firewall, egress interface) -> acl gid.
void asa_packer_add_binding_out(void* h, const char* fw, const char* iface, uint32_t gid) {
    Packer* pk = (Packer*)h;
    std::string k(fw);
    k.push_back('\x03');
    k += iface;
    pk->resolve[k] = gid;
}

int64_t asa_packer_parsed(void* h) { return ((Packer*)h)->parsed; }
int64_t asa_packer_skipped(void* h) { return ((Packer*)h)->skipped; }
void asa_packer_set_counts(void* h, int64_t parsed, int64_t skipped) {
    ((Packer*)h)->parsed = parsed;
    ((Packer*)h)->skipped = skipped;
}

// Parse up to max_lines lines of buf[0:len) into the column-major
// out[TUPLE_COLS][cap] (v4 rows) and, when out6 is given, out6[13][cap6]
// (v6 rows, limb layout, pack.py; callers size cap6 >= 2 * max_lines so
// the v6 side never closes a batch, as the Python _TextSource's side
// buffer never does).  With final==0 a trailing fragment without '\n' is
// left unconsumed; with final!=0 it is parsed as the last line.  Returns
// bytes consumed; *n_lines_out lines were consumed, *n_valid_out and
// *n_valid6_out rows written (rows beyond are zero).
//
// With full_batch!=0 and final==0, a buffer holding fewer than max_lines
// complete lines is left as it is: the call returns -1 with
// *n_lines_out = the complete lines it holds, so the caller can read
// more and call again.
//
// Up to n_threads workers (<= 0: one per core), at least 1024 lines of a
// batch each, run the one parallel pass above; one worker, buffers over
// 4 GiB (uint32 offsets) and max_lines > cap (the cut cannot keep taking
// lines while rows fit) run the one-thread loop.  *workers_out says how
// many ran.  The output -- rows, counts, consumed bytes -- is
// bit-identical for any worker count.
int64_t asa_pack_batch(void* h, const char* buf, int64_t len, int final_,
                       int64_t max_lines, uint32_t* out, int64_t cap,
                       uint32_t* out6, int64_t cap6, int64_t* n_lines_out,
                       int64_t* n_valid_out, int64_t* n_valid6_out,
                       int n_threads, int full_batch, int* workers_out) {
    simd_init();
    Packer* pk = (Packer*)h;
    const HandleLineFn handle = handle_line_fn();
    const int64_t want = max_lines < cap ? max_lines : cap;
    int64_t W = n_threads > 0 ? n_threads : (int64_t)std::thread::hardware_concurrency();
    if (W > want / 1024 + 1) W = want / 1024 + 1;  // tiny batches: few
    if (W < 1 || len > (int64_t)0xFFFFFFFF || max_lines > cap) W = 1;
    *workers_out = (int)W;
    if (W == 1)
        return pack_serial(pk, handle, buf, len, final_, full_batch, max_lines,
                           out, cap, out6, cap6, n_lines_out, n_valid_out,
                           n_valid6_out);
    return pack_parallel(pk, handle, buf, len, final_, full_batch, want, (int)W,
                         out, cap, out6, cap6, n_lines_out, n_valid_out,
                         n_valid6_out);
}

// Plain newline count (streaming buffer bookkeeping; the SIMD popcount
// pass beats even libc memchr chaining, and both beat Python-level
// bytes.count by ~5-10x).
int64_t asa_count_nl(const char* buf, int64_t len) {
    simd_init();
    if (const ra_simd::ScanOps* o = scan_ops()) return o->count_nl(buf, len);
    int64_t n = 0;
    const char* p = buf;
    const char* end = buf + len;
    while ((p = (const char*)memchr(p, '\n', end - p)) != nullptr) {
        ++n;
        ++p;
    }
    return n;
}

// Count newline-terminated lines in buf (resume fast-skip helper).
int64_t asa_count_lines(const char* buf, int64_t len, int final_,
                        int64_t max_lines, int64_t* bytes_out) {
    simd_init();
    if (const ra_simd::ScanOps* o = scan_ops()) {
        int64_t bytes = 0;
        int64_t lines = o->nl_skip(buf, len, max_lines, &bytes);
        if (lines < max_lines && bytes < len && final_) {
            // trailing unterminated fragment counts as a line when final
            ++lines;
            bytes = len;
        }
        *bytes_out = bytes;
        return lines;
    }
    const char* p = buf;
    const char* end = buf + len;
    int64_t lines = 0;
    while (p < end && lines < max_lines) {
        const char* nl = (const char*)memchr(p, '\n', end - p);
        if (!nl && !final_) break;
        ++lines;
        p = nl ? nl + 1 : end;
    }
    *bytes_out = p - buf;
    return lines;
}

// SIMD dispatch introspection/override (ISSUE 11): kind is 0 scalar,
// 1 AVX2, 2 NEON; asa_simd_set(0) forces scalar, (1) re-enables the
// detected ISA — the in-process A/B switch the identity sweep and the
// feedscale bench use (RA_SIMD=off is the env-level equivalent).
int asa_simd_kind() {
    simd_init();
    HandleLineFn h = handle_line_fn();
    if (h && h == ra_parse::avx2_handle_line()) return 1;
    if (h && h == ra_parse::neon_handle_line()) return 2;
    return 0;
}

void asa_simd_set(int on) {
    simd_init();
    pick_dispatch(on != 0);
}

// Flow coalescing (ISSUE 5): compact a column-major [rows, b] uint32
// plane into (unique column, summed weight) pairs in FIRST-OCCURRENCE
// order.  The LAST row is the weight/valid plane — zero-weight columns
// drop, the rest group by the remaining rows' values.  One linear pass
// with an open-addressing (linear-probe) table sized to the next power
// of two >= 2b; `out` must have capacity rows*b (laid out [rows, b] —
// the caller slices [:, :U]); `first_idx` (optional) receives each
// unique column's first source index.  Returns U.  ASA flow logs repeat
// the same 5-tuple across 106100/302013 lines, so U << b on real
// traffic — the MapReduce-combiner move applied to the device batch.
int64_t asa_coalesce(const uint32_t* in, int64_t rows, int64_t b,
                     uint32_t* out, int64_t* first_idx) {
    if (rows < 2 || b <= 0) return 0;
    const int64_t krows = rows - 1;
    const uint32_t* wrow = in + krows * b;
    int64_t nslots = 1;
    while (nslots < 2 * b) nslots <<= 1;
    std::vector<int64_t> table((size_t)nslots, -1);
    int64_t u = 0;
    for (int64_t j = 0; j < b; ++j) {
        uint32_t w = wrow[j];
        if (!w) continue;
        uint64_t h = 1469598103934665603ull;  // FNV-1a over the key rows
        for (int64_t r = 0; r < krows; ++r) {
            h ^= in[r * b + j];
            h *= 1099511628211ull;
        }
        h ^= h >> 32;  // fold: the table mask only sees the low bits
        int64_t s = (int64_t)(h & (uint64_t)(nslots - 1));
        for (;;) {
            int64_t p = table[(size_t)s];
            if (p < 0) {
                table[(size_t)s] = u;
                for (int64_t r = 0; r < krows; ++r) out[r * b + u] = in[r * b + j];
                out[krows * b + u] = w;
                if (first_idx) first_idx[u] = j;
                ++u;
                break;
            }
            bool eq = true;
            for (int64_t r = 0; r < krows; ++r) {
                if (out[r * b + p] != in[r * b + j]) { eq = false; break; }
            }
            if (eq) {
                out[krows * b + p] += w;
                break;
            }
            s = (s + 1) & (nslots - 1);
        }
    }
    return u;
}

}  // extern "C"
