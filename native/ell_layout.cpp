// Native ELL layout builder — the host hot path of every mixed/sparse
// linear fit (ops/ell_scatter.py::ell_layout).
//
// The numpy builder costs ~1.2 us/slot (argsort + two searchsorted
// passes + np.add.at + large temporaries): ~32 s for the default
// product fit's 26M slots — about as long as the training itself.  The
// layout is a counting-sort problem: indices live in [0, rows*128), so
// one count pass + one placement pass per step does everything in O(n)
// with no sort.  Semantics exactly mirror _ell_one_step:
//   - sentinel indices (>= rows*128, streaming pad rows) drop out
//   - a slot's pos = its stable rank among ALL of its table row's slots
//   - heavy = run length (== index count) > heavy_threshold; whole run
//     leaves the grid for the (H, batch) count/value-sum matrix
//   - keep = pos < 128 && !heavy; the rest spill to the overflow list
//     in sorted order
//   - P[row, lane] = (inclusive count of kept slots with lane' <= lane)
//     - 1, clamped at 0, mask = count > 0
//
// With an order, ell_build reads a step's rows through it instead of a
// (steps, batch, nnz) epoch tensor: row b of step s is the table's row
// order[s*batch + b], or a row of index 0 (value 0) where that is n_table
// or more -- the pad prepare_epoch_tensor gives the last step.  The
// layout is then that of the permuted, padded tensor, which is never made.
//
// Capacity protocol: the caller passes ovf_cap/heavy_cap and
// preallocated outputs; per-step needs are always written to
// need_ovf/need_heavy.  Returns 0 on success, 1 when any step's needs
// exceed a cap (outputs are then partial garbage — the caller re-calls
// with caps >= the returned needs).

#include <atomic>
#include <cstdint>
#include <cstring>
#include <thread>
#include <vector>
#include <algorithm>

namespace {

struct Spill {
  int64_t sorted_pos;
  int32_t idx;
  int32_t src;
  float val;
};

}  // namespace

extern "C" {

// flat: (steps*batch*nnz) int32, or the (n_table, nnz) table read
// through order (steps*batch) int32; values: same shape float32 or nullptr.
// Outputs (caller-allocated, row-major):
//   src  (steps, rows, 128) int32     pos (steps, rows, 128) int32
//   mask (steps, rows, 128) float32   val (steps, rows, 128) f32 | null
//   ovf_idx/ovf_src (steps, ovf_cap) int32, ovf_val f32 | null
//   heavy_idx (steps, heavy_cap) int32
//   heavy_cnt (steps, heavy_cap, batch) int16 without values, f32 with
//   need_ovf/need_heavy (steps,) int32
static void build_steps(const int32_t* flat, const float* values,
                        const int32_t* order, int64_t n_table,
                        int64_t s_begin, int64_t s_end,
                        int64_t batch, int64_t nnz, int64_t rows,
                        int64_t heavy_threshold, int64_t ovf_cap,
                        int64_t heavy_cap,
                        int32_t* src, int32_t* pos, float* mask, float* val,
                        int32_t* ovf_idx, int32_t* ovf_src, float* ovf_val,
                        int32_t* heavy_idx, void* heavy_cnt,
                        int32_t* need_ovf, int32_t* need_heavy,
                        std::atomic<int>* rc_out) {
  const int64_t d = rows * 128;
  const int64_t n = batch * nnz;
  const int64_t grid = rows * 128;
  std::vector<int32_t> cnt(d), offs(d);
  std::vector<int32_t> hist(grid);
  std::vector<int64_t> row_start(rows);
  std::vector<Spill> spills;
  std::vector<int32_t> hvec;
  std::vector<Spill> heavy_slots;
  std::vector<int32_t> rows_f(order ? n : 0);
  std::vector<float> rows_v(order && values ? n : 0);

  for (int64_t s = s_begin; s < s_end; ++s) {
    const int32_t* f = flat + s * n;
    const float* fv = values ? values + s * n : nullptr;
    if (order) {
      // this step's rows, gathered through the order (a pad row is 0s)
      const int32_t* o = order + s * batch;
      for (int64_t b = 0; b < batch; ++b) {
        const int64_t row = o[b];
        const bool real = row >= 0 && row < n_table;
        if (real) {
          std::memcpy(&rows_f[b * nnz], flat + row * nnz,
                      nnz * sizeof(int32_t));
        } else {
          std::memset(&rows_f[b * nnz], 0, nnz * sizeof(int32_t));
        }
        if (values) {
          if (real) {
            std::memcpy(&rows_v[b * nnz], values + row * nnz,
                        nnz * sizeof(float));
          } else {
            std::memset(&rows_v[b * nnz], 0, nnz * sizeof(float));
          }
        }
      }
      f = rows_f.data();
      fv = values ? rows_v.data() : nullptr;
    }
    std::memset(cnt.data(), 0, d * sizeof(int32_t));
    for (int64_t i = 0; i < n; ++i) {
      int32_t idx = f[i];
      if (idx >= 0 && idx < d) cnt[idx]++;
    }
    // exclusive prefix; also remember each table row's first sorted slot
    int64_t run = 0;
    for (int64_t r = 0; r < rows; ++r) {
      row_start[r] = run;
      const int64_t base = r << 7;
      for (int64_t l = 0; l < 128; ++l) {
        offs[base + l] = static_cast<int32_t>(run);
        run += cnt[base + l];
      }
    }

    int32_t* src_s = src + s * grid;
    int32_t* pos_s = pos + s * grid;
    float* mask_s = mask + s * grid;
    float* val_s = val ? val + s * grid : nullptr;
    for (int64_t i = 0; i < grid; ++i) src_s[i] = static_cast<int32_t>(batch);
    if (val_s) std::memset(val_s, 0, grid * sizeof(float));
    std::memset(hist.data(), 0, grid * sizeof(int32_t));
    spills.clear();
    hvec.clear();
    heavy_slots.clear();

    // stable placement in original order; offs[idx] walks the sorted
    // position of each slot without materializing the sorted array
    for (int64_t i = 0; i < n; ++i) {
      const int32_t idx = f[i];
      if (idx < 0 || idx >= d) continue;   // sentinel / padding row
      const int32_t b = static_cast<int32_t>(i / nnz);
      const int64_t p = offs[idx]++;
      const int64_t r = idx >> 7;
      const bool heavy = cnt[idx] > heavy_threshold;
      if (heavy) {
        bool seen = false;
        for (int32_t h : hvec) {
          if (h == idx) { seen = true; break; }
        }
        if (!seen) hvec.push_back(idx);
        heavy_slots.push_back({p, idx, b, fv ? fv[i] : 0.0f});
        continue;
      }
      const int64_t rank = p - row_start[r];
      if (rank < 128) {
        src_s[(r << 7) + rank] = b;
        if (val_s) val_s[(r << 7) + rank] = fv[i];
        hist[(r << 7) + (idx & 127)]++;
      } else {
        spills.push_back({p, idx, b, fv ? fv[i] : 0.0f});
      }
    }

    // P / mask from the kept-slot histogram
    for (int64_t r = 0; r < rows; ++r) {
      int32_t acc = 0;
      const int64_t base = r << 7;
      for (int64_t l = 0; l < 128; ++l) {
        acc += hist[base + l];
        const int32_t p_incl = acc - 1;
        mask_s[base + l] = p_incl >= 0 ? 1.0f : 0.0f;
        pos_s[base + l] = p_incl >= 0 ? p_incl : 0;
      }
    }

    // overflow list, in sorted order (parity with the numpy builder)
    need_ovf[s] = static_cast<int32_t>(spills.size());
    need_heavy[s] = static_cast<int32_t>(hvec.size());
    if (static_cast<int64_t>(spills.size()) > ovf_cap ||
        static_cast<int64_t>(hvec.size()) > heavy_cap) {
      rc_out->store(1);
      continue;  // still fill remaining steps' needs
    }
    std::sort(spills.begin(), spills.end(),
              [](const Spill& a, const Spill& b) {
                return a.sorted_pos < b.sorted_pos;
              });
    int32_t* oi = ovf_idx + s * ovf_cap;
    int32_t* os = ovf_src + s * ovf_cap;
    float* ov = ovf_val ? ovf_val + s * ovf_cap : nullptr;
    for (int64_t i = 0; i < ovf_cap; ++i) {
      oi[i] = 0;
      os[i] = static_cast<int32_t>(batch);
      if (ov) ov[i] = 0.0f;
    }
    for (size_t i = 0; i < spills.size(); ++i) {
      oi[i] = spills[i].idx;
      os[i] = spills[i].src;
      if (ov) ov[i] = spills[i].val;
    }

    // heavy: unique sorted indices + per-source count/value-sum matrix
    std::sort(hvec.begin(), hvec.end());
    int32_t* hi = heavy_idx + s * heavy_cap;
    for (int64_t i = 0; i < heavy_cap; ++i) hi[i] = 0;
    for (size_t i = 0; i < hvec.size(); ++i) hi[i] = hvec[i];
    if (values) {
      float* hc = static_cast<float*>(heavy_cnt) + s * heavy_cap * batch;
      std::memset(hc, 0, heavy_cap * batch * sizeof(float));
      for (const Spill& hs : heavy_slots) {
        const int64_t rank =
            std::lower_bound(hvec.begin(), hvec.end(), hs.idx) - hvec.begin();
        hc[rank * batch + hs.src] += hs.val;
      }
    } else {
      int16_t* hc = static_cast<int16_t*>(heavy_cnt) + s * heavy_cap * batch;
      std::memset(hc, 0, heavy_cap * batch * sizeof(int16_t));
      for (const Spill& hs : heavy_slots) {
        const int64_t rank =
            std::lower_bound(hvec.begin(), hvec.end(), hs.idx) - hvec.begin();
        hc[rank * batch + hs.src] += 1;
      }
    }
  }
}

// Entry point: steps are independent (disjoint output slices), so they
// split across hardware threads, each with its own ~9 MB scratch.  On
// the 1-core bench host this degenerates to the serial loop.  order is
// nullptr where flat is the (steps, batch, nnz) epoch tensor itself.
int ell_build(const int32_t* flat, const float* values,
              const int32_t* order, int64_t n_table,
              int64_t steps, int64_t batch, int64_t nnz, int64_t rows,
              int64_t heavy_threshold, int64_t ovf_cap, int64_t heavy_cap,
              int32_t* src, int32_t* pos, float* mask, float* val,
              int32_t* ovf_idx, int32_t* ovf_src, float* ovf_val,
              int32_t* heavy_idx, void* heavy_cnt,
              int32_t* need_ovf, int32_t* need_heavy) {
  std::atomic<int> rc(0);
  int64_t hw = static_cast<int64_t>(std::thread::hardware_concurrency());
  if (hw < 1) hw = 1;
  const int64_t n_threads = std::min<int64_t>(std::min<int64_t>(hw, 8),
                                              steps);
  if (n_threads <= 1) {
    build_steps(flat, values, order, n_table, 0, steps, batch, nnz, rows,
                heavy_threshold, ovf_cap, heavy_cap, src, pos, mask, val,
                ovf_idx, ovf_src, ovf_val, heavy_idx, heavy_cnt, need_ovf,
                need_heavy, &rc);
    return rc.load();
  }
  std::vector<std::thread> pool;
  const int64_t per = (steps + n_threads - 1) / n_threads;
  for (int64_t t = 0; t < n_threads; ++t) {
    const int64_t b = t * per;
    const int64_t e = std::min(steps, b + per);
    if (b >= e) break;
    pool.emplace_back(build_steps, flat, values, order, n_table, b, e,
                      batch, nnz, rows, heavy_threshold, ovf_cap, heavy_cap,
                      src, pos, mask, val, ovf_idx, ovf_src, ovf_val,
                      heavy_idx, heavy_cnt, need_ovf, need_heavy, &rc);
  }
  for (auto& th : pool) th.join();
  return rc.load();
}

}  // extern "C"
