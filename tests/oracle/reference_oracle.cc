// Independent single-process oracle of the reference's TRAINING PROTOCOL,
// used by tests/test_reference_parity.py to pin compat_mode="reference"
// epoch-by-epoch against an implementation that shares no code with the
// framework (and, via glibc srand/rand, none with utils/reference_rng.py).
//
// Protocol reimplemented from observed reference behavior (not copied):
//   * Q2 init: srand(seed); w[i] = rand()/RAND_MAX        [src/lr.cc:92-98]
//   * per-epoch fresh shard pass, B-sized batches, final batch WRAPS to
//     the shard head (Q5)                                 [include/data_iter.h:44-56]
//   * worker gradient at the pulled weight:
//       g = sum_i (sigmoid(w.x_i) - y_i) x_i / B + C*w/B  (Q4 L2/B)
//                                                         [src/lr.cc:35-41]
//   * sync server: BSP round collects all W gradients, then applies ONLY
//     the last-arriving one, divided by W (Q1); arrival order is modeled
//     as rank order, so "last" = rank W-1 — the same convention the
//     framework's SPMD/PS Q1 gates use                    [src/main.cc:66-75]
//   * async server: applies each gradient immediately, undivided; the
//     oracle serializes workers round-robin by rank       [src/main.cc:80-84]
//   * eval: rank 0, every test_interval epochs, accuracy of (w.x > 0)
//     on test/part-001                                    [src/lr.cc:47-63]
//   * libsvm parse: first token ToInt()==1 -> 1 else 0; "idx:val" pairs,
//     1-based idx                                         [include/data_iter.h:25-35]
//
// Output (machine-readable, full precision):
//   TRAJ <epoch> <accuracy>
//   WEIGHTS <w0> <w1> ...
//
// Usage: reference_oracle --data_dir=D [--dim=16] [--workers=1]
//          [--iters=20] [--batch=100] [--test_interval=5] [--lr=0.1]
//          [--C=1] [--sync=1] [--seed=0] [--save_model=PATH]
//
// --save_model additionally writes the final weights in the reference's
// exact SaveModel layout (src/lr.cc:73-82: line 1 = dim via
// `fout << dim << endl`, line 2 = each weight via default-precision
// `fout << w << ' '`, then endl) so the framework's text import/export
// can be golden-tested byte-for-byte against reference-written bytes.

#include <cmath>
#include <cstdio>
#include <cstdlib>
#include <cstring>
#include <fstream>
#include <string>
#include <vector>

namespace {

long Arg(int argc, char** argv, const char* name, long dflt) {
  const std::string prefix = std::string("--") + name + "=";
  for (int i = 1; i < argc; ++i) {
    if (std::string(argv[i]).rfind(prefix, 0) == 0)
      return std::atol(argv[i] + prefix.size());
  }
  return dflt;
}

double ArgF(int argc, char** argv, const char* name, double dflt) {
  const std::string prefix = std::string("--") + name + "=";
  for (int i = 1; i < argc; ++i) {
    if (std::string(argv[i]).rfind(prefix, 0) == 0)
      return std::atof(argv[i] + prefix.size());
  }
  return dflt;
}

std::string ArgS(int argc, char** argv, const char* name, const char* dflt) {
  const std::string prefix = std::string("--") + name + "=";
  for (int i = 1; i < argc; ++i) {
    if (std::string(argv[i]).rfind(prefix, 0) == 0)
      return std::string(argv[i] + prefix.size());
  }
  return dflt;
}

// Dense row-major shard: n x dim features + n labels.
struct Shard {
  int n = 0;
  std::vector<float> x;  // n * dim
  std::vector<int> y;    // n
};

Shard LoadLibsvm(const std::string& path, int dim) {
  FILE* f = std::fopen(path.c_str(), "r");
  if (!f) {
    std::fprintf(stderr, "cannot open %s\n", path.c_str());
    std::exit(2);
  }
  Shard s;
  char line[1 << 16];
  while (std::fgets(line, sizeof line, f)) {
    char* p = line;
    while (*p == ' ' || *p == '\t') ++p;
    if (*p == '\n' || *p == '\0') continue;
    char* end;
    long label = std::strtol(p, &end, 10);
    p = end;
    s.y.push_back(label == 1 ? 1 : 0);
    s.x.resize(s.x.size() + dim, 0.0f);
    float* row = s.x.data() + (size_t)s.n * dim;
    while (true) {
      while (*p == ' ' || *p == '\t') ++p;
      if (*p == '\n' || *p == '\0' || *p == '\r') break;
      long idx = std::strtol(p, &end, 10);
      p = end;
      if (*p != ':') break;
      ++p;
      float val = std::strtof(p, &end);
      p = end;
      if (idx >= 1 && idx <= dim) row[idx - 1] = val;  // 1-based indices
    }
    ++s.n;
  }
  std::fclose(f);
  return s;
}

float SigmoidAt(const std::vector<float>& w, const float* row, int dim) {
  float z = 0.0f;
  for (int j = 0; j < dim; ++j) z += w[j] * row[j];
  return (float)(1.0 / (1.0 + std::exp((double)-z)));
}

// One worker's gradient over batch rows [start, start+b) with Q5 wrap.
std::vector<float> BatchGrad(const Shard& s, const std::vector<float>& w,
                             int dim, int start, int b, float C) {
  std::vector<float> g(dim, 0.0f);
  for (int i = 0; i < b; ++i) {
    const float* row = s.x.data() + (size_t)((start + i) % s.n) * dim;
    const float r = SigmoidAt(w, row, dim) - (float)s.y[(start + i) % s.n];
    for (int j = 0; j < dim; ++j) g[j] += r * row[j];
  }
  for (int j = 0; j < dim; ++j) g[j] = g[j] / b + C * w[j] / b;
  return g;
}

}  // namespace

int main(int argc, char** argv) {
  const std::string data_dir = ArgS(argc, argv, "data_dir", "");
  const int dim = (int)Arg(argc, argv, "dim", 16);
  const int workers = (int)Arg(argc, argv, "workers", 1);
  const int iters = (int)Arg(argc, argv, "iters", 20);
  const int batch = (int)Arg(argc, argv, "batch", 100);
  const int test_interval = (int)Arg(argc, argv, "test_interval", 5);
  const float lr = (float)ArgF(argc, argv, "lr", 0.1);
  const float C = (float)ArgF(argc, argv, "C", 1.0);
  const bool sync = Arg(argc, argv, "sync", 1) != 0;
  const int seed = (int)Arg(argc, argv, "seed", 0);
  if (data_dir.empty()) {
    std::fprintf(stderr, "--data_dir is required\n");
    return 2;
  }

  std::vector<Shard> shards;
  for (int k = 0; k < workers; ++k) {
    char name[32];
    std::snprintf(name, sizeof name, "/train/part-%03d", k + 1);
    shards.push_back(LoadLibsvm(data_dir + name, dim));
  }
  Shard test = LoadLibsvm(data_dir + "/test/part-001", dim);

  // Q2 init — actual glibc srand/rand, the thing reference_rng.py mimics.
  srand(seed);
  std::vector<float> w(dim);
  for (int j = 0; j < dim; ++j)
    w[j] = (float)rand() / (float)RAND_MAX;

  if (batch <= 0) {
    std::fprintf(stderr, "--batch must be positive (use the shard size "
                         "for full-batch runs)\n");
    return 2;
  }
  // ceil(n/B) rounds per epoch; every batch is exactly B rows because the
  // final one wraps to the shard head (Q5).  Sync BSP needs every worker
  // to push the same number of rounds per epoch or the reference's merge
  // counter deadlocks.
  std::vector<int> rounds_k;
  int max_rounds = 0;
  for (const auto& s : shards) {
    rounds_k.push_back((s.n + batch - 1) / batch);
    if (rounds_k.back() > max_rounds) max_rounds = rounds_k.back();
    if (sync && rounds_k.back() != rounds_k[0]) {
      std::fprintf(stderr, "unequal per-worker batch counts deadlock the "
                           "reference sync server\n");
      return 2;
    }
  }

  for (int epoch = 0; epoch < iters; ++epoch) {
    if (sync) {
      for (int r = 0; r < rounds_k[0]; ++r) {
        // BSP: every worker pulls the same w; only the last-arriving
        // (rank W-1) gradient is applied, divided by W (Q1).
        std::vector<float> g_last;
        for (int k = 0; k < workers; ++k)
          g_last = BatchGrad(shards[k], w, dim, r * batch, batch, C);
        for (int j = 0; j < dim; ++j)
          w[j] -= lr * g_last[j] / (float)workers;
      }
    } else {
      // Round-robin serialization of the async free-for-all: each worker
      // pulls the current w and its gradient applies immediately.
      for (int r = 0; r < max_rounds; ++r) {
        for (int k = 0; k < workers; ++k) {
          if (r < rounds_k[k]) {
            std::vector<float> g = BatchGrad(shards[k], w, dim, r * batch, batch, C);
            for (int j = 0; j < dim; ++j) w[j] -= lr * g[j];
          }
        }
      }
    }
    if (test_interval > 0 && (epoch + 1) % test_interval == 0) {
      int correct = 0;
      for (int i = 0; i < test.n; ++i) {
        float z = 0.0f;
        const float* row = test.x.data() + (size_t)i * dim;
        for (int j = 0; j < dim; ++j) z += w[j] * row[j];
        if ((z > 0.0f ? 1 : 0) == test.y[i]) ++correct;
      }
      std::printf("TRAJ %d %.9g\n", epoch + 1, (double)correct / test.n);
    }
  }

  std::printf("WEIGHTS");
  for (int j = 0; j < dim; ++j) std::printf(" %.9g", w[j]);
  std::printf("\n");

  const std::string save_model = ArgS(argc, argv, "save_model", "");
  if (!save_model.empty()) {
    // Reference SaveModel layout, reproduced stream-op for stream-op
    // (src/lr.cc:73-82) — default ostream precision (6 sig. digits).
    std::ofstream fout(save_model.c_str());
    fout << dim << std::endl;
    for (int j = 0; j < dim; ++j) fout << w[j] << ' ';
    fout << std::endl;
  }
  return 0;
}
