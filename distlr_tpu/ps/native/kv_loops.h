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
//
// The FTRL-Proximal step (Algorithm 1 of McMahan et al., KDD 2013; the
// order of its operations is FtrlStepOne's body) is under the same
// contract: FtrlStepPacked takes four coordinates of a keyed frame
// through FtrlStepOne's operations lane for lane, and `sqrtps`, `divps`,
// `mulps`, `addps` and `subps` round a lane as `sqrtss`, `divss`, ...
// do.  No compiler packs a step whose three tables are gathered by key,
// so this one is spelled in SSE2 intrinsics (<emmintrin.h>: the x86-64
// baseline again, no -march, no dispatch, nothing fused), with
// FtrlStepOne four times over as its body on any other target and
// wherever DISTLR_SCALAR_LOOPS is defined: the Makefile's SANFLAGS line
// defines it, so the sanitizer twins step one coordinate at a time, a
// matter of the build and never of a run.  The branch `|z| <= l1` is a
// mask there; the lanes it takes still go through the divide, whose
// result is dropped.

#ifndef DISTLR_KV_LOOPS_H_
#define DISTLR_KV_LOOPS_H_

#include <cmath>
#include <cstdint>

#if defined(__SSE2__) && !defined(DISTLR_SCALAR_LOOPS)
#include <emmintrin.h>
#define DISTLR_FTRL_SSE2 1
#endif

namespace distlr {

struct FtrlParams {
  float alpha = 0.1f;
  float beta = 1.0f;
  float l1 = 0.0f;
  float l2 = 0.0f;
};

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

// One coordinate's FTRL-Proximal step on the tables (w, z, n) at key k,
// g != 0; true where it ended with |z| <= l1 (the weight exactly 0.0:
// L1 sparsification, the CTR memory saver).  All arithmetic is float32,
// matching the NumPy oracles the parity tests compare against
// (tests/test_ftrl.py, tests/test_ps_apply_bits.py) operation for
// operation.
inline bool FtrlStepOne(float* w, float* z, float* n, uint64_t k, float g,
                        const FtrlParams& p) {
  const float n_old = n[k];
  const float n_new = n_old + g * g;
  const float sigma = (std::sqrt(n_new) - std::sqrt(n_old)) / p.alpha;
  z[k] += g - sigma * w[k];
  n[k] = n_new;
  const float zk = z[k];
  if (std::fabs(zk) <= p.l1) {
    w[k] = 0.0f;
    return true;
  }
  const float sgn = zk > 0.0f ? 1.0f : -1.0f;
  w[k] = -(zk - sgn * p.l1) / ((p.beta + std::sqrt(n_new)) / p.alpha + p.l2);
  return false;
}

// Whether four entries of a keyed frame may be stepped together: their
// keys strictly ascending (a raw frame may repeat a key, and a repeated
// key inside one group would read the n, z and w of before its first
// entry), none of their gradients 0.0 (a zero entry steps nothing).
inline bool FtrlGroupPacks(const uint64_t* k, const float* g) {
  return k[0] < k[1] && k[1] < k[2] && k[2] < k[3] && g[0] != 0.0f &&
         g[1] != 0.0f && g[2] != 0.0f && g[3] != 0.0f;
}

// FtrlStepOne on the four coordinates k[0..3] (FtrlGroupPacks holds) at
// once; how many of them ended with |z| <= l1.
__attribute__((noinline)) inline unsigned FtrlStepPacked(
    float* w, float* z, float* n, const uint64_t* k, const float* g,
    const FtrlParams& p) {
#ifdef DISTLR_FTRL_SSE2
  const uint64_t k0 = k[0], k1 = k[1], k2 = k[2], k3 = k[3];
  const __m128 alpha = _mm_set1_ps(p.alpha);
  const __m128 l1 = _mm_set1_ps(p.l1);
  const __m128 sign_bit = _mm_set1_ps(-0.0f);
  const __m128 gv = _mm_loadu_ps(g);
  const __m128 n_old = _mm_setr_ps(n[k0], n[k1], n[k2], n[k3]);
  const __m128 n_new = _mm_add_ps(n_old, _mm_mul_ps(gv, gv));
  const __m128 root_new = _mm_sqrt_ps(n_new);
  const __m128 sigma =
      _mm_div_ps(_mm_sub_ps(root_new, _mm_sqrt_ps(n_old)), alpha);
  const __m128 w_old = _mm_setr_ps(w[k0], w[k1], w[k2], w[k3]);
  const __m128 zv =
      _mm_add_ps(_mm_setr_ps(z[k0], z[k1], z[k2], z[k3]),
                 _mm_sub_ps(gv, _mm_mul_ps(sigma, w_old)));
  // fabs(z) <= l1, and z > 0 ? 1 : -1: a NaN is false in both, so it
  // takes the -1 lane of the divide, as the scalar step does
  const __m128 under = _mm_cmple_ps(_mm_andnot_ps(sign_bit, zv), l1);
  const __m128 pos = _mm_cmpgt_ps(zv, _mm_setzero_ps());
  const __m128 sgn = _mm_or_ps(_mm_and_ps(pos, _mm_set1_ps(1.0f)),
                               _mm_andnot_ps(pos, _mm_set1_ps(-1.0f)));
  const __m128 over = _mm_sub_ps(zv, _mm_mul_ps(sgn, l1));
  const __m128 rate = _mm_add_ps(
      _mm_div_ps(_mm_add_ps(_mm_set1_ps(p.beta), root_new), alpha),
      _mm_set1_ps(p.l2));
  const __m128 wv =
      _mm_andnot_ps(under, _mm_div_ps(_mm_xor_ps(over, sign_bit), rate));
  alignas(16) float wo[kLanes], zo[kLanes], no[kLanes];
  _mm_store_ps(no, n_new);
  _mm_store_ps(zo, zv);
  _mm_store_ps(wo, wv);
  n[k0] = no[0]; n[k1] = no[1]; n[k2] = no[2]; n[k3] = no[3];
  z[k0] = zo[0]; z[k1] = zo[1]; z[k2] = zo[2]; z[k3] = zo[3];
  w[k0] = wo[0]; w[k1] = wo[1]; w[k2] = wo[2]; w[k3] = wo[3];
  const unsigned m = static_cast<unsigned>(_mm_movemask_ps(under));
  return (m & 1u) + ((m >> 1) & 1u) + ((m >> 2) & 1u) + (m >> 3);
#else
  unsigned zeroed = 0;
  for (uint64_t j = 0; j < kLanes; ++j)
    zeroed += FtrlStepOne(w, z, n, k[j], g[j], p);
  return zeroed;
#endif
}

}  // namespace loops
}  // namespace distlr

#endif  // DISTLR_KV_LOOPS_H_
