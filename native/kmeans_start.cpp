// KMeans's random start without the permutation: the first k entries of
// NumPy's Generator.permutation(n), exactly, from the generator's own
// stream (models/clustering/kmeans.py::random_start).
//
// NumPy 2's permutation(n) is arange(n) shuffled by a reversed
// Fisher-Yates: for i = n-1 down to 1, j_i = random_interval(i) (a
// next_uint32 masked to the smallest all-ones mask >= i, redrawn while
// above i), then a[i] <-> a[j_i].  The k values it keeps depend on every
// draw, but not on an array of n values:
//
//   (a) draw every j_i, in NumPy's order, through NumPy's own next_uint32
//       (no second PCG64 to keep in step), into a buffer of n;
//   (b) steps n-1 .. k only move values INTO a[0:k] from above: follow
//       each slot p < k back in time, i.e. up in i.  A chain sits at one
//       position; when j_i is that position, the value there came from
//       position i at step i, so the chain moves to i.  A position left is
//       never entered again (chains only move up), so a bitmap of n bits
//       says which positions hold a chain, and the chain at a position c
//       >= k is written into the consumed draw j_c itself; c < k is chain
//       c.  Where a chain ends is its value (arange);
//   (c) steps k-1 .. 1 swap inside a[0:k]: apply them to the k values.
//
// The generator ends in NumPy's state: the same draws, in the same order.

#include <cstdint>
#include <cstdlib>

extern "C" {

typedef uint32_t (*next_uint32_fn)(void* state);

// out (k,) int64.  Returns 0; 1 where n - 1 does not fit 32 bits (NumPy
// draws 64 there), k is outside [0, n], or the buffer cannot be had.  An
// error returns before the first draw: the generator is untouched and the
// caller's NumPy path gives the same answer.
int perm_prefix(next_uint32_fn next_uint32, void* state, int64_t n,
                int64_t k, int64_t* out) {
  if (n < 0 || k < 0 || k > n || n - 1 > int64_t(0xFFFFFFFF)) return 1;
  if (n <= 1) {  // NumPy draws nothing
    for (int64_t p = 0; p < k; ++p) out[p] = p;
    return 0;
  }
  uint32_t* draw = static_cast<uint32_t*>(std::malloc(n * sizeof(uint32_t)));
  uint64_t* held = static_cast<uint64_t*>(
      std::calloc((n + 63) / 64, sizeof(uint64_t)));
  if (!draw || !held) {
    std::free(draw);
    std::free(held);
    return 1;
  }
  // (a)
  for (int64_t i = n - 1; i >= 1; --i) {
    uint32_t mask = uint32_t(i);
    mask |= mask >> 1;
    mask |= mask >> 2;
    mask |= mask >> 4;
    mask |= mask >> 8;
    mask |= mask >> 16;
    uint32_t value;
    while ((value = next_uint32(state) & mask) > uint32_t(i)) {
    }
    draw[i] = value;
  }
  // (b)
  for (int64_t p = 0; p < k; ++p) held[p >> 6] |= uint64_t(1) << (p & 63);
  for (int64_t i = k; i < n; ++i) {
    const uint32_t j = draw[i];
    if (!(held[j >> 6] >> (j & 63) & 1)) continue;
    held[j >> 6] &= ~(uint64_t(1) << (j & 63));
    held[i >> 6] |= uint64_t(1) << (i & 63);
    draw[i] = j < k ? j : draw[j];  // the chain moves from j to i
  }
  for (int64_t p = 0; p < k; ++p) out[p] = p;
  for (int64_t w = 0; w < (n + 63) / 64; ++w) {
    for (uint64_t bits = held[w]; bits; bits &= bits - 1) {
      const int64_t c = w * 64 + __builtin_ctzll(bits);
      if (c >= k) out[draw[c]] = c;
    }
  }
  // (c)
  for (int64_t i = k - 1; i >= 1; --i) {
    const int64_t j = draw[i], v = out[i];
    out[i] = out[j];
    out[j] = v;
  }
  std::free(draw);
  std::free(held);
  return 0;
}

}  // extern "C"
