// Native placement of one side's ratings in the grouped plan's slots —
// the host hot path of a grouped ALS fit
// (models/recommendation/als.py::GroupedPlan.arrays).
//
// The plan's lay-out gives every group its first slot; rating k of group
// g goes to slot0[g] + (the number of g's ratings before k).  That is a
// counting sort whose counts are already in hand: no order of the
// ratings is made, no slot index materialised, no temporary the size of
// the ratings.  The NumPy form (a stable argsort in two radix passes, two
// 8-bytes-a-rating temporaries, a scatter of the slot index and one
// scatter a column through it) gives the same slots, bit for bit.
//
// Threads: a contiguous part of the ratings each.  Pass 1 counts a
// part's ratings by group; a prefix over the parts turns the counts into
// each part's first slot in every group (the parts in order, so the
// placement is stable); pass 2 places every column as it goes.  A group
// split into parts of block_slots needs nothing of its own: its slots
// are consecutive from slot0 too.

#include <algorithm>
#include <atomic>
#include <cstdint>
#include <thread>
#include <vector>

namespace {

// fn(t) for t in [0, threads), on that many threads (inline for one)
template <typename Fn>
void run_parts(int64_t threads, Fn fn) {
  if (threads <= 1) {
    fn(0);
    return;
  }
  std::vector<std::thread> pool;
  for (int64_t t = 0; t < threads; ++t) pool.emplace_back(fn, t);
  for (auto& th : pool) th.join();
}

}  // namespace

extern "C" {

// group (n,) int64 in [0, n_groups); slot0 (n_groups,) int64, a group's
// first slot; other (n,) int64, rating (n,) float32, weight (n,) float32
// or nullptr.  out_* (n_slots,) are the caller's zero-filled flat slots
// (out_weight nullptr exactly where weight is); a slot no rating fills
// is not touched.  Returns 0, 1 where a group index is out of range, 2
// where a group's ratings would leave [0, n_slots): nothing outside the
// outputs is ever written, and after 1 nothing at all.
int als_place(const int64_t* group, int64_t n, const int64_t* slot0,
              int64_t n_groups, int64_t n_slots, const int64_t* other,
              const float* rating, const float* weight, int32_t* out_other,
              float* out_rating, float* out_weight, int64_t threads) {
  const int64_t parts = std::max<int64_t>(1, std::min(threads, n));
  // next[p * n_groups + g]: pass 1 the count of g's ratings in part p,
  // after the prefix the slot of part p's next rating of g
  std::vector<int64_t> next(parts * n_groups, 0);
  std::atomic<int> rc(0);
  auto part_begin = [&](int64_t p) { return n * p / parts; };

  run_parts(parts, [&](int64_t p) {
    int64_t* mine = next.data() + p * n_groups;
    for (int64_t k = part_begin(p); k < part_begin(p + 1); ++k) {
      const int64_t g = group[k];
      if (g < 0 || g >= n_groups) {
        rc.store(1);
        return;
      }
      mine[g]++;
    }
  });
  if (rc.load()) return rc.load();

  // a range of the groups a thread, the parts in order inside it
  run_parts(parts, [&](int64_t t) {
    const int64_t lo = n_groups * t / parts, hi = n_groups * (t + 1) / parts;
    std::vector<int64_t> run(slot0 + lo, slot0 + hi);
    for (int64_t p = 0; p < parts; ++p) {
      int64_t* theirs = next.data() + p * n_groups;
      for (int64_t g = lo; g < hi; ++g) {
        const int64_t count = theirs[g];
        theirs[g] = run[g - lo];
        run[g - lo] += count;
      }
    }
    for (int64_t g = lo; g < hi; ++g) {
      if (slot0[g] < 0 || run[g - lo] > n_slots) rc.store(2);
    }
  });
  if (rc.load()) return rc.load();

  run_parts(parts, [&](int64_t p) {
    int64_t* mine = next.data() + p * n_groups;
    for (int64_t k = part_begin(p); k < part_begin(p + 1); ++k) {
      const int64_t s = mine[group[k]]++;
      out_other[s] = static_cast<int32_t>(other[k]);
      out_rating[s] = rating[k];
      if (weight) out_weight[s] = weight[k];
    }
  });
  return 0;
}

}  // extern "C"
