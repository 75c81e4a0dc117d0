// Shared types of the ASA line parser (ISSUE 11 SIMD split).
//
// The line parser body (asaparse_line.inl) compiles once per ISA —
// scalar in asaparse.cpp, AVX2 in asaparse_avx2.cpp, NEON in
// asaparse_neon.cpp — and the chunk loops dispatch through a
// HandleLineFn pointer selected at runtime.  These types cross that
// boundary, so they live outside the per-ISA namespaces.

#pragma once

#include <cstdint>
#include <cstring>
#include <memory>
#include <string>
#include <unordered_map>
#include <vector>

namespace ra_parse {

// Grow-only array whose storage is never value-initialised: the parse
// writes every element before it reads it, so zero-filling would only
// add a pass over memory.  reserve() keeps the first `keep` elements
// across a regrowth.
template <class T>
struct RawBuf {
    std::unique_ptr<T[]> p;
    size_t n = 0;
    T* reserve(size_t want, size_t keep = 0) {
        if (want > n) {
            size_t m = want + want / 8;
            std::unique_ptr<T[]> q(new T[m]);
            if (keep) memcpy(q.get(), p.get(), keep * sizeof(T));
            p = std::move(q);
            n = m;
        }
        return p.get();
    }
};

// One parse worker's buffers, kept by the Packer across batches.
struct WorkerScratch {
    RawBuf<uint32_t> nl;     // newline offsets in the worker's byte range
    RawBuf<uint32_t> slab4;  // [TUPLE_COLS, 2 * lines] rows of its lines
    RawBuf<uint32_t> slab6;  // [TUPLE6_COLS, 2 * lines] (v6-capable rulesets)
    RawBuf<uint8_t> rows4;   // rows each of its lines emitted, per plane
    RawBuf<uint8_t> rows6;
};

struct Packer {
    // key: firewall + '\x01' + acl   -> acl gid  (named-ACL messages)
    //      firewall + '\x02' + iface -> acl gid  (in-direction binding)
    //      firewall + '\x03' + iface -> acl gid  (out-direction binding)
    std::unordered_map<std::string, uint32_t> resolve;
    int64_t parsed = 0;   // ACL evaluations emitted (LinePacker.parsed)
    int64_t skipped = 0;  // lines yielding none (LinePacker.skipped)
    std::vector<WorkerScratch> scratch;  // grown to the most workers used
};

// Per-thread parse context: the shared resolve table is read-only during a
// parse; everything mutable is thread-local so N workers can parse one
// batch's line ranges concurrently (the Hadoop input-split analog,
// SURVEY.md §2 L2).
struct LocalCtx {
    const std::unordered_map<std::string, uint32_t>* resolve;
    std::string keybuf;
};

// Parse one line; emit its ACL evaluations into the column-major output.
// Same contract for every ISA build — see the documentation block on
// handle_line in asaparse_line.inl.
using HandleLineFn = int (*)(LocalCtx* pk, const char* ls, const char* le,
                             uint32_t* out, int64_t cap, int64_t row,
                             uint32_t* out6, int64_t cap6, int64_t* row6);

// Per-ISA entry points: return the TU's handle_line, or nullptr when the
// TU was compiled without the ISA or the CPU lacks it at runtime.
HandleLineFn scalar_handle_line();
HandleLineFn avx2_handle_line();
HandleLineFn neon_handle_line();

}  // namespace ra_parse
