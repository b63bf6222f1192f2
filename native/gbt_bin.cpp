// Quantile binning of a float32 table for the boosted-tree trainer
// (models/common/gbt.py::bin_columns): bin = the number of edges strictly
// below the value, which is NumPy's searchsorted(edges, x, side="left")
// on ascending edges; a NaN value takes the number of edges that are not
// NaN (where NumPy's NaN-last order puts it).
//
// The edges are float64 and the values float32.  For a float32 x,
// x > e  <=>  x > t  where t is the largest float32 not above e, so each
// edge is turned into that float32 once and the comparisons run on
// float32 lanes: the same bins as comparing in float64.
//
// Rows are cut into chunks of CHUNK; a thread takes a run of chunks, and
// for each chunk and feature gathers the feature's values into a buffer
// and counts edge by edge over it (a loop the compiler vectorises).  The
// output is feature-major: out[j * out_stride + i].

#include <cmath>
#include <cstdint>
#include <thread>
#include <vector>

namespace {

constexpr int64_t CHUNK = 2048;

float floor_to_float(double e) {
  float t = static_cast<float>(e);
  if (static_cast<double>(t) > e) t = std::nextafter(t, -INFINITY);
  return t;
}

void bin_rows(const float* x, int64_t lo, int64_t hi, int64_t d,
              const float* thr, const int32_t* not_nan, int64_t n_edges,
              int32_t* out, int64_t out_stride) {
  float buf[CHUNK];
  int32_t cnt[CHUNK];
  for (int64_t c0 = lo; c0 < hi; c0 += CHUNK) {
    const int64_t m = (hi - c0 < CHUNK) ? hi - c0 : CHUNK;
    for (int64_t j = 0; j < d; ++j) {
      for (int64_t i = 0; i < m; ++i) {
        buf[i] = x[(c0 + i) * d + j];
        cnt[i] = 0;
      }
      const float* t = thr + j * n_edges;
      for (int64_t k = 0; k < n_edges; ++k) {
        const float tk = t[k];
        for (int64_t i = 0; i < m; ++i) cnt[i] += buf[i] > tk;
      }
      int32_t* o = out + j * out_stride + c0;
      for (int64_t i = 0; i < m; ++i)
        o[i] = std::isnan(buf[i]) ? not_nan[j] : cnt[i];
    }
  }
}

}  // namespace

extern "C" {

// x (n, d) float32 row-major; edges (d, n_edges) float64, each row
// ascending (NaN last); out: d rows of out_stride >= n int32, of which
// the first n are written.  Returns 0.
int bin_columns(const float* x, int64_t n, int64_t d, const double* edges,
                int64_t n_edges, int32_t* out, int64_t out_stride,
                int threads) {
  std::vector<float> thr(d * n_edges);
  std::vector<int32_t> not_nan(d, 0);
  for (int64_t j = 0; j < d; ++j) {
    for (int64_t k = 0; k < n_edges; ++k) {
      const double e = edges[j * n_edges + k];
      // a NaN edge compares false, as no float32 is above it
      thr[j * n_edges + k] = std::isnan(e) ? NAN : floor_to_float(e);
      not_nan[j] += !std::isnan(e);
    }
  }
  if (threads < 1) threads = 1;
  const int64_t chunks = (n + CHUNK - 1) / CHUNK;
  if (chunks < threads) threads = chunks > 0 ? static_cast<int>(chunks) : 1;
  std::vector<std::thread> pool;
  for (int w = 0; w < threads; ++w) {
    const int64_t lo = chunks * w / threads * CHUNK;
    int64_t hi = chunks * (w + 1) / threads * CHUNK;
    if (hi > n) hi = n;
    if (lo >= hi) continue;
    pool.emplace_back(bin_rows, x, lo, hi, d, thr.data(), not_nan.data(),
                      n_edges, out, out_stride);
  }
  for (auto& t : pool) t.join();
  return 0;
}

}  // extern "C"
