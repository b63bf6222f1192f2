// The host hot paths of an ALS fit (models/recommendation/als.py): the
// index of a label column (als_index, _index_labels) and the placement of
// one side's ratings in the grouped plan's slots (als_place,
// GroupedPlan.arrays).
//
// als_index gives np.unique(labels, return_inverse=True) without sorting
// the labels.  Threads: a contiguous part of the labels each.  Pass 1
// puts a part's labels in a hash map of its own, label -> its ordinal
// among the part's distinct labels, and writes every label's ordinal to
// the output.  Then the parts' distinct labels are merged in one map and
// only those are sorted; the map then gives a label's rank among them.
// Pass 2 turns a part's ordinals into ranks through a table of the
// part's distinct labels: a gather, no hashing.
//
// als_place:
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
#include <limits>
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

// int64 label -> int64 value, open addressing with linear probing, at most
// half full, grown by doubling: memory follows the distinct labels.  Every
// int64 is a label, the one that marks an empty slot too: that label's
// value is kept beside the slots.
class LabelMap {
 public:
  // the value of key; where key is new, inserts it with `value`
  int64_t find_or_insert(int64_t key, int64_t value) {
    if (key == kEmpty) {
      if (!has_empty_) {
        has_empty_ = true;
        empty_value_ = value;
      }
      return empty_value_;
    }
    if (2 * (size_ + 1) > static_cast<int64_t>(slots_.size())) grow();
    for (uint64_t s = mix(key) & mask_;; s = (s + 1) & mask_) {
      Slot& slot = slots_[s];
      if (slot.key == key) return slot.value;
      if (slot.key == kEmpty) {
        slot = {key, value};
        ++size_;
        return value;
      }
    }
  }

  // the value of a key that is in the map
  int64_t& at(int64_t key) {
    if (key == kEmpty) return empty_value_;
    uint64_t s = mix(key) & mask_;
    while (slots_[s].key != key) s = (s + 1) & mask_;
    return slots_[s].value;
  }

 private:
  struct Slot {
    int64_t key, value;
  };
  static constexpr int64_t kEmpty = std::numeric_limits<int64_t>::min();

  static uint64_t mix(int64_t key) {  // murmur3's 64-bit finaliser
    uint64_t x = static_cast<uint64_t>(key);
    x ^= x >> 33;
    x *= 0xff51afd7ed558ccdULL;
    x ^= x >> 33;
    x *= 0xc4ceb9fe1a85ec53ULL;
    return x ^ (x >> 33);
  }

  void grow() {
    std::vector<Slot> old(std::max<size_t>(1024, 2 * slots_.size()),
                          Slot{kEmpty, 0});
    old.swap(slots_);
    mask_ = slots_.size() - 1;
    for (const Slot& slot : old) {
      if (slot.key == kEmpty) continue;
      uint64_t s = mix(slot.key) & mask_;
      while (slots_[s].key != kEmpty) s = (s + 1) & mask_;
      slots_[s] = slot;
    }
  }

  std::vector<Slot> slots_;
  uint64_t mask_ = 0;
  int64_t size_ = 0;
  bool has_empty_ = false;
  int64_t empty_value_ = 0;
};

}  // namespace

extern "C" {

// labels (n,) int64.  ids (n,) and index (n,) int64 are the caller's: the
// sorted distinct labels go to the front of ids, and index[k] is the rank
// of labels[k] among them.  Returns the number of distinct labels.  A
// thread's part is ceil(n / threads) labels, the last part the rest.
int64_t als_index(const int64_t* labels, int64_t n, int64_t* ids,
                  int64_t* index, int64_t threads) {
  if (n <= 0) return 0;
  threads = std::max<int64_t>(1, threads);
  const int64_t chunk = (n + threads - 1) / threads;
  const int64_t parts = (n + chunk - 1) / chunk;
  // a part's distinct labels, in the order of their ordinals
  std::vector<std::vector<int64_t>> seen(parts);

  run_parts(parts, [&](int64_t p) {
    LabelMap ordinal;
    std::vector<int64_t>& mine = seen[p];
    for (int64_t k = p * chunk; k < std::min(n, (p + 1) * chunk); ++k) {
      const int64_t o = ordinal.find_or_insert(labels[k], mine.size());
      if (o == static_cast<int64_t>(mine.size())) mine.push_back(labels[k]);
      index[k] = o;
    }
  });

  LabelMap rank;
  int64_t m = 0;
  for (const auto& mine : seen) {
    for (int64_t label : mine) {
      if (rank.find_or_insert(label, m) == m) ids[m++] = label;
    }
  }
  std::sort(ids, ids + m);
  for (int64_t r = 0; r < m; ++r) rank.at(ids[r]) = r;

  run_parts(parts, [&](int64_t p) {
    std::vector<int64_t>& mine = seen[p];
    for (int64_t& label : mine) label = rank.at(label);  // now its rank
    for (int64_t k = p * chunk; k < std::min(n, (p + 1) * chunk); ++k) {
      index[k] = mine[index[k]];
    }
  });
  return m;
}

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
