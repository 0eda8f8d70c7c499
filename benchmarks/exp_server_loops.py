"""Experiment: the servers' three float32 loops, scalar against packed,
on whatever host this runs on: alone, and where a server runs them.

``distlr_tpu/ps/native/kv_loops.h`` holds the loops a native server
moves its weights with: the async apply ``w -= lr * g``, the BSP merge
``m += g`` and the release's mean step ``w -= lr * g / W``.  This
compiles that header round a small timing ``main`` under several flag
sets and prints, for each and for each size, the best and the median
time of each loop over ``--reps`` calls and a hash over the weights and
the merge buffer afterwards: the hash has to be the same in every build
(the same IEEE operations a coordinate, however many at a time).

``O2``       ``-O2`` alone: GCC 12's very-cheap vectoriser refuses a loop
             with an epilogue, so this is the scalar code the servers ran
             until PR 48.
``shipped``  the ``CXXFLAGS`` of ``ps/native/Makefile`` as it stands.
``avx2``     ``shipped`` with ``-mavx2`` beside it (eight lanes; still
             no FMA target, and contraction off).
``O1``       the sanitizer variants' level (scalar).

The release's two passes (the step, then ``std::fill`` over the merge
buffer) are timed together as ``release``, which is what kStats
``release_apply_seconds`` spans in the server; ``release_1pass`` is the
step that clears the buffer as it reads it.  ``--busy N`` runs N
spinning threads beside the timed one.  Sizes: 500,000 is a server's
half of the binary model's 1M weights, 620,610 its half of the
multiclass model's 1,241,220.

Alone, one thread has both arrays in its own cache, which no server
has.  ``--rounds N`` runs N lock-step rounds as a server's threads do:
four connection threads, each on a core of its own, take turns at the
one merge buffer (each adds a gradient another thread wrote), the last
applies the mean, then all four copy the weights out (the replies) and
copy ``--churn-mb`` of their own (a worker's copies go through the same
caches).  The apply three ways: ``one_pass`` (step and clear together),
``two_pass`` (the server's), ``split`` (each thread a quarter of the
range, then a barrier: not built, the size of what is left).  PR 48's
reading on the chip's host is in ``PERF.md`` section 6: alone the one
pass wins, in rounds it loses to two, and either way the weights' lines
coming back from the cores that copied the replies cost more than the
arithmetic.

The FTRL-Proximal step (PR 54): an asynchronous server's apply of a
keyed push, ``--ftrl-keys`` strictly ascending keys of the first size a
frame, sixteen frames in turn over the three tables (w, z, n), alpha
0.1, beta 1, l1 1.5e-4 (the cell's rule), stepped five ways: ``scalar``
(``FtrlStepOne`` a key, what the servers ran until PR 54), ``packed``
(``FtrlStepPacked`` four keys at a time: the server's walk), and
``packed+2``, ``packed+4``, ``packed+8`` (the same, asking for the three
tables' lines of the group that many groups ahead first: not kept).
Nanoseconds a step (best / median over the frames), the steps, those
that ended with ``|z| <= l1`` and a hash of w, z and n, which has to be
the same for every way and in the ``twin`` build (``-O1
-DDISTLR_SCALAR_LOOPS``: what the sanitizer variants run in
``FtrlStepPacked``'s place).  Under ``--rounds`` four connection threads
take turns at the one server's tables, each with a frame of its own and
fresh values, and after its turn each gathers the weights of another
thread's keys (the next pull's reply, copied out on another core) and
copies ``--churn-mb``: ``ftrl_ns`` there is the median over a thread's
turns.  PR 54's reading on the chip's host is in ``PERF.md`` section 6.

Run on the chip's host: python benchmarks/exp_server_loops.py --rounds 400
A host number, never a device metric.
"""

from __future__ import annotations

import argparse
import os
import re
import shutil
import subprocess
import sys
import tempfile

HERE = os.path.dirname(os.path.abspath(__file__))
NATIVE = os.path.join(os.path.dirname(HERE), "distlr_tpu", "ps", "native")

ONE_PASS = r"""// The release's step and the clearing of the merge buffer in one pass:
// tried for the server in PR 48 and not kept (faster alone, slower where
// a server runs it: see --rounds).
__attribute__((noinline)) static void MeanStepClear(
    float* __restrict w, float* __restrict m, uint64_t n, float lr,
    float workers) {
  for (uint64_t j = 0; j < n; ++j) {
    w[j] -= lr * m[j] / workers;
    m[j] = 0.0f;
  }
}
"""

MAIN = r"""
#include <algorithm>
#include <atomic>
#include <chrono>
#include <cstdio>
#include <cstdlib>
#include <cstring>
#include <thread>
#include <vector>

#include "kv_loops.h"

using distlr::loops::MeanStep;
using distlr::loops::MergeAdd;
using distlr::loops::SgdStep;

@ONE_PASS@

static double Now() {
  return std::chrono::duration<double>(
             std::chrono::steady_clock::now().time_since_epoch()).count();
}

static uint64_t Hash(const std::vector<float>& v, uint64_t h) {
  for (float f : v) {
    uint32_t b;
    std::memcpy(&b, &f, 4);
    h = (h ^ b) * 1099511628211ull;
  }
  return h;
}

struct Stat { double best, median; };
static Stat Of(std::vector<double> t) {
  std::sort(t.begin(), t.end());
  return {t.front() * 1e3, t[t.size() / 2] * 1e3};
}

int main(int argc, char** argv) {
  if (argc != 4) return 2;
  const uint64_t n = std::strtoull(argv[1], nullptr, 10);
  const int reps = std::atoi(argv[2]);
  const int busy = std::atoi(argv[3]);
  std::atomic<bool> stop{false};
  std::vector<std::thread> spin;
  for (int i = 0; i < busy; ++i)
    spin.emplace_back([&] { volatile uint64_t x = 0; while (!stop) x = x + 1; });

  std::vector<float> w(n), m(n, 0.0f), g(n);
  uint32_t s = 12345;
  auto rnd = [&] { s = s * 1664525u + 1013904223u;
                   return (static_cast<float>(s >> 8) / 8388608.0f) - 1.0f; };
  for (auto& x : w) x = rnd();
  for (auto& x : g) x = rnd() * 0.01f;
  const float lr = 0.2f, workers = 4.0f;
  std::vector<double> t_sgd, t_merge, t_release, t_fused;
  for (int r = 0; r < reps; ++r) {
    // a frame's values are fresh bytes every time in the server (a
    // socket read or a worker's copy): perturb so nothing is hoisted
    g[r % n] += 1e-6f;
    double t0 = Now();
    SgdStep(w.data(), g.data(), n, lr);
    t_sgd.push_back(Now() - t0);
    for (int v = 0; v < 4; ++v) {
      t0 = Now();
      MergeAdd(m.data(), g.data(), n);
      if (v == 0) t_merge.push_back(Now() - t0);
    }
    t0 = Now();
    MeanStep(w.data(), m.data(), n, lr, workers);
    std::fill(m.begin(), m.end(), 0.0f);
    t_release.push_back(Now() - t0);
    for (int v = 0; v < 4; ++v) MergeAdd(m.data(), g.data(), n);
    t0 = Now();
    MeanStepClear(w.data(), m.data(), n, lr, workers);
    t_fused.push_back(Now() - t0);
  }
  stop = true;
  for (auto& t : spin) t.join();
  const Stat a = Of(t_sgd), b = Of(t_merge), c = Of(t_release),
             d = Of(t_fused);
  std::printf("n=%llu sgd_ms=%.4f/%.4f merge_ms=%.4f/%.4f "
              "release_ms=%.4f/%.4f release_1pass_ms=%.4f/%.4f hash=%016llx\n",
              static_cast<unsigned long long>(n), a.best, a.median, b.best,
              b.median, c.best, c.median, d.best, d.median,
              static_cast<unsigned long long>(Hash(m, Hash(w, 14695981039346656037ull))));
  return 0;
}
"""

ROUNDS = r"""
// The loops where a server runs them: W connection threads, each on a
// core of its own, take turns at one merge buffer; the last one applies;
// then all W copy the weights out (the replies).  Between rounds every
// thread copies --churn MB (a worker's device_put, readback and
// exchange copies go through the same caches).
#include <algorithm>
#include <atomic>
#include <chrono>
#include <cstdio>
#include <cstdlib>
#include <cstring>
#include <thread>
#include <vector>

#include "kv_loops.h"

using namespace distlr::loops;

@ONE_PASS@

static double Now() {
  return std::chrono::duration<double>(
             std::chrono::steady_clock::now().time_since_epoch()).count();
}
static double Median(std::vector<double> t) {
  std::sort(t.begin(), t.end());
  return t[t.size() / 2] * 1e3;
}

int main(int argc, char** argv) {
  if (argc != 6) return 2;
  const uint64_t n = std::strtoull(argv[1], nullptr, 10);
  const int rounds = std::atoi(argv[2]);
  const int W = std::atoi(argv[3]);
  const size_t churn = std::strtoull(argv[4], nullptr, 10) << 20;
  const int how = std::atoi(argv[5]);  // 0 one pass, 1 two passes, 2 split
  std::vector<float> w(n, 1.0f), m(n, 0.0f);
  std::vector<std::vector<float>> g(W, std::vector<float>(n, 0.001f)),
      reply(W, std::vector<float>(n));
  std::vector<std::vector<char>> a(W, std::vector<char>(churn + 1)),
      b(W, std::vector<char>(churn + 1));
  std::atomic<int> turn{0}, applied{0}, copied{0}, round{0};
  std::vector<std::vector<double>> tm(W);
  std::vector<double> t_apply, t_reply;
  const float lr = 0.2f, workers = static_cast<float>(W);
  auto run = [&](int r) {
    for (int k = 0; k < rounds; ++k) {
      while (round.load() != k) {}
      // the "client": the neighbour's gradient, written on this core
      std::vector<float>& mine = g[(r + 1) % W];
      for (uint64_t j = 0; j < n; j += 16) mine[j] += 1e-6f;
      if (churn) std::memcpy(a[r].data(), b[r].data(), churn);
      const int at = (r + k) % W;  // the order of arrival turns with k
      while (turn.load() != at) {}
      double t0 = Now();
      MergeAdd(m.data(), g[r].data(), n);
      double t1 = Now();
      tm[r].push_back(t1 - t0);
      if (how == 2) {
        turn.store(at + 1);
        while (turn.load() != W) {}
        const uint64_t lo = n * r / W, hi = n * (r + 1) / W;
        MeanStepClear(w.data() + lo, m.data() + lo, hi - lo, lr, workers);
        applied.fetch_add(1);
        while (applied.load() != W) {}
        if (at == W - 1) t_apply.push_back(Now() - t1);
      } else if (at == W - 1) {
        if (how == 0) {
          MeanStepClear(w.data(), m.data(), n, lr, workers);
        } else {
          MeanStep(w.data(), m.data(), n, lr, workers);
          std::fill(m.begin(), m.end(), 0.0f);
        }
        t_apply.push_back(Now() - t1);
        turn.store(W);
      } else {
        turn.store(at + 1);
        while (turn.load() != W) {}
      }
      t0 = Now();
      std::memcpy(reply[r].data(), w.data(), n * sizeof(float));
      if (at == 0) t_reply.push_back(Now() - t0);
      if (copied.fetch_add(1) + 1 == W) {
        copied.store(0);
        applied.store(0);
        turn.store(0);
        round.store(k + 1);
      }
    }
  };
  std::vector<std::thread> th;
  for (int r = 0; r < W; ++r) th.emplace_back(run, r);
  for (auto& t : th) t.join();
  std::vector<double> t_merge;
  for (auto& v : tm) t_merge.insert(t_merge.end(), v.begin(), v.end());
  std::printf("n=%llu W=%d churn_mb=%zu apply=%s merge_ms=%.4f apply_ms=%.4f "
              "reply_copy_ms=%.4f w0=%.6f\n",
              static_cast<unsigned long long>(n), W, churn >> 20,
              how == 0 ? "one_pass" : how == 1 ? "two_pass" : "split",
              Median(t_merge), Median(t_apply), Median(t_reply), w[0]);
  return 0;
}
"""


FTRL = r"""
// The FTRL-Proximal step of an asynchronous keyed push: kv_server.cc's
// ApplyFtrlRows over three tables of its own; kAhead < 0 is FtrlStepOne
// a key, kAhead > 0 asks for the lines of the group that many groups on
// first (tried for the server in PR 54 and not kept: it bought nothing
// alone, in --rounds or in the cell).
#include <algorithm>
#include <atomic>
#include <chrono>
#include <cstdio>
#include <cstdlib>
#include <cstring>
#include <thread>
#include <vector>

#include "kv_loops.h"

using distlr::FtrlParams;
using namespace distlr::loops;

static double Now() {
  return std::chrono::duration<double>(
             std::chrono::steady_clock::now().time_since_epoch()).count();
}

struct Tables {
  std::vector<float> w, z, n;
  uint64_t steps = 0, zeroed = 0, packed = 0;
};

template <int kAhead>
static void Walk(Tables& t, const uint64_t* k, const float* g, uint64_t n,
                 const FtrlParams& p) {
  float* const w = t.w.data();
  float* const z = t.z.data();
  float* const acc = t.n.data();
  uint64_t i = 0;
  if (kAhead >= 0) {
    for (; i + kLanes <= n; i += kLanes) {
      if (kAhead > 0 && i + (kAhead + 1) * kLanes <= n) {
        const uint64_t* const a = k + i + kAhead * kLanes;
        for (uint64_t j = 0; j < kLanes; ++j) {
          __builtin_prefetch(acc + a[j], 1);
          __builtin_prefetch(z + a[j], 1);
          __builtin_prefetch(w + a[j], 1);
        }
      }
      if (FtrlGroupPacks(k + i, g + i)) {
        t.zeroed += FtrlStepPacked(w, z, acc, k + i, g + i, p);
        t.steps += kLanes;
        t.packed += kLanes;
      } else {
        for (uint64_t j = 0; j < kLanes; ++j) {
          if (g[i + j] == 0.0f) continue;
          ++t.steps;
          t.zeroed += FtrlStepOne(w, z, acc, k[i + j], g[i + j], p);
        }
      }
    }
  }
  for (; i < n; ++i) {
    if (g[i] == 0.0f) continue;
    ++t.steps;
    t.zeroed += FtrlStepOne(w, z, acc, k[i], g[i], p);
  }
}

static void (*const kWalks[])(Tables&, const uint64_t*, const float*,
                              uint64_t, const FtrlParams&) = {
    Walk<-1>, Walk<0>, Walk<2>, Walk<4>, Walk<8>};
static const char* const kNames[] = {"scalar", "packed", "packed+2",
                                     "packed+4", "packed+8"};

static uint64_t Hash(const std::vector<float>& v, uint64_t h) {
  for (float f : v) {
    uint32_t b;
    std::memcpy(&b, &f, 4);
    h = (h ^ b) * 1099511628211ull;
  }
  return h;
}

struct Frames {
  std::vector<std::vector<uint64_t>> k;
  std::vector<std::vector<float>> g;
};

// `count` frames of `keys` strictly ascending keys under `dim`, values
// round the cell's (a window's mean gradient: most far under 1)
static Frames Make(uint64_t dim, uint64_t keys, int count) {
  Frames f;
  uint32_t s = 2463534242u;
  auto rnd = [&] { s ^= s << 13; s ^= s >> 17; s ^= s << 5; return s; };
  for (int c = 0; c < count; ++c) {
    std::vector<uint64_t> k;
    const double stride = static_cast<double>(dim) / keys;
    for (uint64_t i = 0; i < keys; ++i) {
      const uint64_t lo = static_cast<uint64_t>(i * stride);
      const uint64_t hi = static_cast<uint64_t>((i + 1) * stride);
      k.push_back(lo + rnd() % std::max<uint64_t>(hi - lo, 1));
    }
    std::vector<float> g(keys);
    for (auto& x : g)
      x = (static_cast<float>(rnd() >> 8) / 8388608.0f - 1.0f) * 1e-3f;
    f.k.push_back(std::move(k));
    f.g.push_back(std::move(g));
  }
  return f;
}

int main(int argc, char** argv) {
  if (argc != 7) return 2;
  const uint64_t dim = std::strtoull(argv[1], nullptr, 10);
  const uint64_t keys = std::strtoull(argv[2], nullptr, 10);
  const int how = std::atoi(argv[3]);
  const int reps = std::atoi(argv[4]);    // alone: passes over the frames
  const int rounds = std::atoi(argv[5]);  // > 0: four threads in turn
  const size_t churn = std::strtoull(argv[6], nullptr, 10) << 20;
  const FtrlParams p{0.1f, 1.0f, 1.5e-4f, 0.0f};
  Tables t;
  t.w.assign(dim, 0.0f);
  t.z.assign(dim, 0.0f);
  t.n.assign(dim, 0.0f);
  const auto walk = kWalks[how];
  std::vector<double> ns;
  if (rounds == 0) {
    Frames f = Make(dim, keys, 16);
    for (int r = 0; r < reps; ++r) {
      for (size_t c = 0; c < f.k.size(); ++c) {
        f.g[c][r % keys] += 1e-7f;  // fresh bytes, as a frame's are
        const uint64_t before = t.steps;
        const double t0 = Now();
        walk(t, f.k[c].data(), f.g[c].data(), keys, p);
        ns.push_back(1e9 * (Now() - t0) / (t.steps - before));
      }
    }
  } else {
    const int W = 4;
    Frames f = Make(dim, keys, W);
    std::vector<std::vector<float>> reply(W, std::vector<float>(keys));
    std::vector<std::vector<char>> a(W, std::vector<char>(churn + 1)),
        b(W, std::vector<char>(churn + 1));
    std::vector<std::vector<double>> mine(W);
    std::atomic<int> turn{0}, done{0}, round{0};
    auto run = [&](int r) {
      for (int k = 0; k < rounds; ++k) {
        while (round.load() != k) {}
        // the "worker": this round's gradient, written on this core
        for (uint64_t j = 0; j < keys; j += 16) f.g[r][j] += 1e-7f;
        if (churn) std::memcpy(a[r].data(), b[r].data(), churn);
        const int at = (r + k) % W;  // the order of arrival turns with k
        while (turn.load() != at) {}
        const uint64_t before = t.steps;
        const double t0 = Now();
        walk(t, f.k[r].data(), f.g[r].data(), keys, p);
        mine[r].push_back(1e9 * (Now() - t0) / (t.steps - before));
        turn.store(at + 1);
        while (turn.load() != W) {}
        // the next pull: another worker's keys, copied out on this core
        const std::vector<uint64_t>& other = f.k[(r + 1) % W];
        for (uint64_t j = 0; j < keys; ++j) reply[r][j] = t.w[other[j]];
        if (done.fetch_add(1) + 1 == W) {
          done.store(0);
          turn.store(0);
          round.store(k + 1);
        }
      }
    };
    std::vector<std::thread> th;
    for (int r = 0; r < W; ++r) th.emplace_back(run, r);
    for (auto& x : th) x.join();
    for (auto& v : mine) ns.insert(ns.end(), v.begin(), v.end());
  }
  std::sort(ns.begin(), ns.end());
  std::printf("dim=%llu keys=%llu rounds=%d churn_mb=%zu step=%s "
              "ftrl_ns=%.2f/%.2f steps=%llu zeroed=%llu packed=%llu "
              "hash=%016llx\n",
              static_cast<unsigned long long>(dim),
              static_cast<unsigned long long>(keys), rounds, churn >> 20,
              kNames[how], ns.front(), ns[ns.size() / 2],
              static_cast<unsigned long long>(t.steps),
              static_cast<unsigned long long>(t.zeroed),
              static_cast<unsigned long long>(t.packed),
              static_cast<unsigned long long>(Hash(
                  t.n, Hash(t.z, Hash(t.w, 14695981039346656037ull)))));
  return 0;
}
"""

FTRL_STEPS = ("scalar", "packed", "packed+2", "packed+4", "packed+8")


def shipped_flags() -> list[str]:
    """The ``CXXFLAGS ?=`` line of the servers' Makefile."""
    with open(os.path.join(NATIVE, "Makefile")) as f:
        line = re.search(r"^CXXFLAGS \?= (.*)$", f.read(), re.M).group(1)
    return line.split()


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--sizes", default="500000,620610")
    ap.add_argument("--reps", type=int, default=200)
    ap.add_argument("--busy", type=int, default=2)
    ap.add_argument("--rounds", type=int, default=0,
                    help="also run this many lock-step rounds of four "
                         "threads at one merge buffer (medians)")
    ap.add_argument("--churn-mb", default="0,16",
                    help="MB each thread copies between rounds")
    ap.add_argument("--ftrl-keys", type=int, default=44000,
                    help="keys of an FTRL frame (0: leave the FTRL rows out)")
    args = ap.parse_args()
    sizes = args.sizes.split(",")
    cxx = shutil.which(os.environ.get("CXX", "g++"))
    if cxx is None:
        print("no C++ compiler here", file=sys.stderr)
        return 2
    base = ["-std=c++17", "-pthread", f"-I{NATIVE}"]
    shipped = shipped_flags()
    builds = {
        "O2": ["-O2"],
        "shipped": shipped,
        "avx2": [*shipped, "-mavx2"],
        "O1": ["-O1"],
    }
    with open("/proc/cpuinfo") as f:
        model = re.search(r"model name\s*:\s*(.*)", f.read())
    print(f"host cpu={model.group(1) if model else '?'} cores={os.cpu_count()} "
          f"cxx={cxx} busy={args.busy} reps={args.reps} (ms: best/median)")
    hashes: dict[str, set[str]] = {}
    with tempfile.TemporaryDirectory() as tmp:
        src = os.path.join(tmp, "loops_main.cc")
        with open(src, "w") as f:
            f.write(MAIN.replace("@ONE_PASS@", ONE_PASS))
        for name, flags in builds.items():
            exe = os.path.join(tmp, f"loops_{name}")
            subprocess.run([cxx, *base, *flags, "-o", exe, src], check=True)
            for n in sizes:
                out = subprocess.run(
                    [exe, n, str(args.reps), str(args.busy)], check=True,
                    capture_output=True, text=True).stdout.strip()
                print(f"{name:8s} {out}   [{' '.join(flags)}]")
                hashes.setdefault(n, set()).add(out.rsplit("hash=", 1)[1])
        if args.rounds:
            src = os.path.join(tmp, "rounds_main.cc")
            with open(src, "w") as f:
                f.write(ROUNDS.replace("@ONE_PASS@", ONE_PASS))
            for name in ("O2", "shipped"):
                exe = os.path.join(tmp, f"rounds_{name}")
                subprocess.run([cxx, *base, *builds[name], "-o", exe, src],
                               check=True)
                for churn in args.churn_mb.split(","):
                    for how in ("0", "1", "2"):
                        out = subprocess.run(
                            [exe, sizes[0], str(args.rounds),
                             "4", churn, how], check=True,
                            capture_output=True, text=True).stdout.strip()
                        print(f"{name:8s} {out}")
        if args.ftrl_keys:
            src = os.path.join(tmp, "ftrl_main.cc")
            with open(src, "w") as f:
                f.write(FTRL)
            ftrl_builds = {"shipped": shipped,
                           "twin": ["-O1", "-DDISTLR_SCALAR_LOOPS",
                                    "-ffp-contract=off"]}
            for name, flags in ftrl_builds.items():
                exe = os.path.join(tmp, f"ftrl_{name}")
                subprocess.run([cxx, *base, *flags, "-o", exe, src],
                               check=True)
                runs = [("0", "0")] + [
                    (str(args.rounds), churn)
                    for churn in args.churn_mb.split(",") if args.rounds]
                for rounds, churn in runs:
                    for how in range(len(FTRL_STEPS)):
                        out = subprocess.run(
                            [exe, sizes[0], str(args.ftrl_keys), str(how),
                             str(max(args.reps // 10, 2)), rounds, churn],
                            check=True, capture_output=True,
                            text=True).stdout.strip()
                        print(f"{name:8s} {out}")
                        hashes.setdefault(f"ftrl rounds={rounds}",
                                          set()).add(
                            out.rsplit("hash=", 1)[1])
    same = all(len(h) == 1 for h in hashes.values())
    print(f"hashes_agree={same}")
    return 0 if same else 1


if __name__ == "__main__":
    sys.exit(main())
