// The server's float32 loops: every weight a server moves, it moves in
// one of these.
//
// The trajectories are oracle-pinned to the bit, so the contract of
// each loop is the IEEE operations of its one-line body, in that order,
// once a coordinate: `(lr * g) / W` is a correctly rounded multiply,
// then a correctly rounded divide, then a correctly rounded subtract,
// never `lr * (g / W)`, never a multiply by `1 / W`, never a fused
// multiply-add (one rounding where the reference has two).  HOW MANY
// coordinates go through those operations at a time is not part of the
// contract: a packed `mulps` / `divps` / `subps` is the same three
// roundings a lane, so the release build's Makefile asks the compiler
// to vectorise these loops (four SSE2 lanes, the x86-64 baseline, no
// dispatch) and the sanitizer builds keep them scalar, and both give
// the same bits (tests/test_ps_apply_bits.py holds them to NumPy's, one
// operation at a time).  What keeps a vectorised build from fusing is
// `-ffp-contract=off` on the Makefile's CXXFLAGS line; nothing here may
// be built with -ffast-math or for the building host's own ISA.
//
// `__restrict`: a gradient never aliases the state it is applied to (a
// frame's buffer or a connection's request area against weights_ /
// merge_), which is what lets the compiler drop the runtime overlap
// check.  `noinline` on the loops proper: one copy of each in the
// binary, under its own name, so the disassembly that
// tests/test_ps_native_build.py reads is the code that runs.  A span
// shorter than one vector (a keyed frame of single rows is 500,000 of
// them) has nothing to pack and does not pay the call: the same
// expression, inline.

#ifndef DISTLR_KV_LOOPS_H_
#define DISTLR_KV_LOOPS_H_

#include <cstdint>

namespace distlr {
namespace loops {

constexpr uint64_t kLanes = 4;  // float32 lanes of an SSE2 vector
static_assert(kLanes == 4, "the short spans below are spelled out for 1..3");

__attribute__((noinline)) inline void SgdStepPacked(
    float* __restrict w, const float* __restrict g, uint64_t n, float lr) {
  for (uint64_t j = 0; j < n; ++j) w[j] -= lr * g[j];
}

__attribute__((noinline)) inline void MergeAddPacked(
    float* __restrict m, const float* __restrict g, uint64_t n) {
  for (uint64_t j = 0; j < n; ++j) m[j] += g[j];
}

__attribute__((noinline)) inline void MeanStepPacked(
    float* __restrict w, const float* __restrict g, uint64_t n, float lr,
    float workers) {
  for (uint64_t j = 0; j < n; ++j) w[j] -= lr * g[j] / workers;
}

// Async SGD (ApplySpan): w[j] -= lr * g[j].
inline void SgdStep(float* __restrict w, const float* __restrict g,
                    uint64_t n, float lr) {
  if (n >= kLanes) return SgdStepPacked(w, g, n, lr);
  switch (n) {
    case 3: w[2] -= lr * g[2]; [[fallthrough]];
    case 2: w[1] -= lr * g[1]; [[fallthrough]];
    case 1: w[0] -= lr * g[0];
  }
}

// A BSP push merged on arrival: m[j] += g[j].  The order of a round's
// additions is the order of these calls (arrival order, under mu_).
inline void MergeAdd(float* __restrict m, const float* __restrict g,
                     uint64_t n) {
  if (n >= kLanes) return MergeAddPacked(m, g, n);
  switch (n) {
    case 3: m[2] += g[2]; [[fallthrough]];
    case 2: m[1] += g[1]; [[fallthrough]];
    case 1: m[0] += g[0];
  }
}

// The BSP release's mean step: w[j] -= lr * g[j] / W — `(lr * g) / W`,
// left to right.
inline void MeanStep(float* __restrict w, const float* __restrict g,
                     uint64_t n, float lr, float workers) {
  if (n >= kLanes) return MeanStepPacked(w, g, n, lr, workers);
  switch (n) {
    case 3: w[2] -= lr * g[2] / workers; [[fallthrough]];
    case 2: w[1] -= lr * g[1] / workers; [[fallthrough]];
    case 1: w[0] -= lr * g[0] / workers;
  }
}

}  // namespace loops
}  // namespace distlr

#endif  // DISTLR_KV_LOOPS_H_
