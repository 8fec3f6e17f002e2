// Reference-baseline proxy: the Go reference's roaring container kernels
// and benchmark workloads, re-implemented in scalar C++ and compiled with
// -O2 (no SIMD intrinsics, no threading — the Go originals are scalar
// single-goroutine loops too).
//
// WHY THIS EXISTS: BASELINE.md requires the reference's microbenchmarks
// (roaring/roaring_test.go:1364-1423,1504-1560 and
// fragment_internal_test.go:1156) to be MEASURED, but this image has no Go
// toolchain (`go`/`gccgo` absent) and no network egress to install one —
// see BASELINE.md "Go toolchain attempt". Scalar C++ at -O2 is the closest
// available stand-in for gc-compiled Go on branchy integer loops; for this
// class of code C++ is consistently as fast or faster than Go (no bounds
// checks, same data layout), so treating these numbers as the Go baseline
// makes OUR speedup claims conservative (the true Go denominator would be
// the same or slower).
//
// Workload fidelity: data shapes and iteration counts mirror
// getBenchData (roaring_test.go:1243-1283) and the benchmark bodies; the
// kernel algorithms mirror the specializations' structure
// (roaring.go:2162-2295 intersectionCount*, popcountAndSlice) without
// copying code. Two additional workloads give the engine benches a
// like-for-like denominator:
//   exec_128shard_1pct  — Count(Intersect) of two 1%-dense rows over 128
//                         shards (the engine's served-Count data
//                         shape; executor.go:1521 + roaring fan-in)
//   kernel_2rows_dense  — Count(Intersect) of two 50%-dense rows over
//                         1024 shards (the dense kernel's shape;
//                         all bitmap×bitmap popcount-AND)
//   bsi_sum_16shard     — Sum(Range(v>thr)): 10-plane range walk + 11
//                         filtered plane counts over 16 shards of dense
//                         bitmap containers (fragment.go:718-985 rangeOp,
//                         executor.go:363 executeSum)
//
// Output: one line per bench: `<name> <ns_per_op> <ops>`.

#include <algorithm>
#include <chrono>
#include <cstdint>
#include <cstdio>
#include <cstring>
#include <random>
#include <string>
#include <vector>

namespace {

constexpr int kArrayMaxSize = 4096;    // roaring.go ArrayMaxSize
constexpr int kBitmapWords = 1024;     // 65536 bits / 64

struct Run {
  uint16_t start, last;
};

// One 16-bit keyspace container, array/bitmap/run — roaring.go Container.
struct Container {
  enum Kind { kArray, kBitmap, kRun } kind = kArray;
  std::vector<uint16_t> array;
  std::vector<uint64_t> bitmap;  // kBitmapWords words when kind==kBitmap
  std::vector<Run> runs;

  int32_t n() const {
    switch (kind) {
      case kArray:
        return (int32_t)array.size();
      case kRun: {
        int32_t t = 0;
        for (const Run& r : runs) t += r.last - r.start + 1;
        return t;
      }
      case kBitmap: {
        int64_t t = 0;
        for (uint64_t w : bitmap) t += __builtin_popcountll(w);
        return (int32_t)t;
      }
    }
    return 0;
  }
};

// -- construction ------------------------------------------------------------

void add_sorted_unique(std::vector<uint16_t>* v, uint16_t x) {
  auto it = std::lower_bound(v->begin(), v->end(), x);
  if (it == v->end() || *it != x) v->insert(it, x);
}

Container make_array(std::vector<uint16_t> sorted_vals) {
  Container c;
  c.kind = Container::kArray;
  c.array = std::move(sorted_vals);
  return c;
}

Container to_bitmap(const Container& a) {
  Container c;
  c.kind = Container::kBitmap;
  c.bitmap.assign(kBitmapWords, 0);
  if (a.kind == Container::kArray) {
    for (uint16_t v : a.array) c.bitmap[v >> 6] |= 1ull << (v & 63);
  } else if (a.kind == Container::kRun) {
    for (const Run& r : a.runs)
      for (uint32_t v = r.start; v <= r.last; v++)
        c.bitmap[v >> 6] |= 1ull << (v & 63);
  } else {
    c.bitmap = a.bitmap;
  }
  return c;
}

Container make_runs(const std::vector<uint16_t>& sorted_vals) {
  Container c;
  c.kind = Container::kRun;
  for (size_t i = 0; i < sorted_vals.size();) {
    uint16_t s = sorted_vals[i];
    size_t j = i;
    while (j + 1 < sorted_vals.size() &&
           sorted_vals[j + 1] == sorted_vals[j] + 1)
      j++;
    c.runs.push_back({s, sorted_vals[j]});
    i = j + 1;
  }
  return c;
}

// optimize(): pick the smallest representation, mirroring Optimize()'s
// size rule (roaring.go: runs win if few, arrays under ArrayMaxSize,
// else bitmap).
Container optimize(const Container& c) {
  std::vector<uint16_t> vals;
  if (c.kind == Container::kArray) {
    vals = c.array;
  } else if (c.kind == Container::kRun) {
    for (const Run& r : c.runs)
      for (uint32_t v = r.start; v <= r.last; v++) vals.push_back((uint16_t)v);
  } else {
    for (int w = 0; w < (int)c.bitmap.size(); w++)
      for (uint64_t bits = c.bitmap[w]; bits; bits &= bits - 1)
        vals.push_back((uint16_t)((w << 6) + __builtin_ctzll(bits)));
  }
  Container r = make_runs(vals);
  size_t run_bytes = r.runs.size() * 4, arr_bytes = vals.size() * 2;
  if (run_bytes < arr_bytes && run_bytes < 8192) return r;
  if ((int)vals.size() <= kArrayMaxSize) return make_array(std::move(vals));
  return to_bitmap(make_array(std::move(vals)));
}

// -- intersectionCount specializations (roaring.go:2190-2295) ---------------

int32_t ic_array_array(const Container& a, const Container& b) {
  const std::vector<uint16_t>*ca = &a.array, *cb = &b.array;
  if (ca->empty() || cb->empty()) return 0;
  if (ca->size() > cb->size()) std::swap(ca, cb);
  int32_t n = 0;
  size_t j = 0, nb = cb->size();
  for (uint16_t va : *ca) {
    while ((*cb)[j] < va) {
      if (++j >= nb) return n;
    }
    if ((*cb)[j] == va) n++;
  }
  return n;
}

int32_t ic_array_run(const Container& a, const Container& b) {
  int32_t n = 0;
  size_t i = 0, j = 0, na = a.array.size(), nb = b.runs.size();
  while (i < na && j < nb) {
    uint16_t va = a.array[i];
    const Run& vb = b.runs[j];
    if (va < vb.start) {
      i++;
    } else if (va <= vb.last) {
      i++;
      n++;
    } else {
      j++;
    }
  }
  return n;
}

int32_t ic_run_run(const Container& a, const Container& b) {
  int32_t n = 0;
  size_t i = 0, j = 0;
  while (i < a.runs.size() && j < b.runs.size()) {
    const Run &va = a.runs[i], &vb = b.runs[j];
    uint16_t lo = std::max(va.start, vb.start);
    uint16_t hi = std::min(va.last, vb.last);
    if (lo <= hi) n += hi - lo + 1;
    if (va.last < vb.last)
      i++;
    else
      j++;
  }
  return n;
}

int32_t bitmap_count_range(const Container& a, int32_t start, int32_t end) {
  // bitmapCountRange (roaring.go): popcount of bits in [start, end)
  int32_t n = 0;
  int i = start >> 6, j = (end - 1) >> 6;
  uint64_t first_mask = ~0ull << (start & 63);
  uint64_t last_mask = (end & 63) ? ((1ull << (end & 63)) - 1) : ~0ull;
  if (i == j) return __builtin_popcountll(a.bitmap[i] & first_mask & last_mask);
  n += __builtin_popcountll(a.bitmap[i] & first_mask);
  for (int w = i + 1; w < j; w++) n += __builtin_popcountll(a.bitmap[w]);
  n += __builtin_popcountll(a.bitmap[j] & last_mask);
  return n;
}

int32_t ic_bitmap_run(const Container& a, const Container& b) {
  int32_t n = 0;
  for (const Run& r : b.runs) n += bitmap_count_range(a, r.start, r.last + 1);
  return n;
}

int32_t ic_array_bitmap(const Container& a, const Container& b) {
  int32_t n = 0;
  for (uint16_t v : a.array) n += (b.bitmap[v >> 6] >> (v & 63)) & 1;
  return n;
}

int32_t ic_bitmap_bitmap(const Container& a, const Container& b) {
  // popcountAndSlice (roaring.go / generic.go)
  int64_t n = 0;
  for (int w = 0; w < kBitmapWords; w++)
    n += __builtin_popcountll(a.bitmap[w] & b.bitmap[w]);
  return (int32_t)n;
}

int32_t intersection_count(const Container& a, const Container& b) {
  using K = Container;
  if (a.kind == K::kArray) {
    if (b.kind == K::kArray) return ic_array_array(a, b);
    if (b.kind == K::kRun) return ic_array_run(a, b);
    return ic_array_bitmap(a, b);
  }
  if (a.kind == K::kRun) {
    if (b.kind == K::kArray) return ic_array_run(b, a);
    if (b.kind == K::kRun) return ic_run_run(a, b);
    return ic_bitmap_run(b, a);
  }
  if (b.kind == K::kArray) return ic_array_bitmap(b, a);
  if (b.kind == K::kRun) return ic_bitmap_run(a, b);
  return ic_bitmap_bitmap(a, b);
}

// -- union (for BenchmarkUnion/UnionBulk analogs) ----------------------------

Container union_any(const Container& a, const Container& b) {
  // materializing Union (roaring.go union* specializations): arrays merge;
  // anything involving a bitmap ORs into a bitmap; runs expand lazily
  if (a.kind == Container::kArray && b.kind == Container::kArray) {
    std::vector<uint16_t> out;
    out.reserve(a.array.size() + b.array.size());
    std::set_union(a.array.begin(), a.array.end(), b.array.begin(),
                   b.array.end(), std::back_inserter(out));
    if ((int)out.size() <= kArrayMaxSize) return make_array(std::move(out));
    return to_bitmap(make_array(std::move(out)));
  }
  Container out = a.kind == Container::kBitmap ? a : to_bitmap(a);
  if (b.kind == Container::kBitmap) {
    for (int w = 0; w < kBitmapWords; w++) out.bitmap[w] |= b.bitmap[w];
  } else if (b.kind == Container::kArray) {
    for (uint16_t v : b.array) out.bitmap[v >> 6] |= 1ull << (v & 63);
  } else {
    for (const Run& r : b.runs) {
      for (uint32_t v = r.start; v <= r.last; v++)
        out.bitmap[v >> 6] |= 1ull << (v & 63);
    }
  }
  return out;
}

// -- bitmap = keyed container set (roaring.go Bitmap, hi-48 keys) -----------

struct Bitmap {
  std::vector<uint64_t> keys;        // sorted hi keys
  std::vector<Container> containers;  // parallel to keys

  Container* get(uint64_t key) {
    auto it = std::lower_bound(keys.begin(), keys.end(), key);
    if (it == keys.end() || *it != key) return nullptr;
    return &containers[it - keys.begin()];
  }
  const Container* get(uint64_t key) const {
    return const_cast<Bitmap*>(this)->get(key);
  }

  static Bitmap from_values(std::vector<uint64_t> vals) {
    std::sort(vals.begin(), vals.end());
    vals.erase(std::unique(vals.begin(), vals.end()), vals.end());
    Bitmap b;
    size_t i = 0;
    while (i < vals.size()) {
      uint64_t key = vals[i] >> 16;
      std::vector<uint16_t> lows;
      while (i < vals.size() && (vals[i] >> 16) == key)
        lows.push_back((uint16_t)(vals[i++] & 0xffff));
      b.keys.push_back(key);
      b.containers.push_back(optimize(make_array(std::move(lows))));
    }
    return b;
  }

  int64_t intersection_count_with(const Bitmap& o) const {
    // keyed merge walk (roaring.go:819 IntersectionCount -> per-container
    // specialization)
    int64_t n = 0;
    size_t i = 0, j = 0;
    while (i < keys.size() && j < o.keys.size()) {
      if (keys[i] < o.keys[j])
        i++;
      else if (keys[i] > o.keys[j])
        j++;
      else
        n += intersection_count(containers[i++], o.containers[j++]);
    }
    return n;
  }

  Bitmap union_with(const Bitmap& o) const {
    Bitmap out;
    size_t i = 0, j = 0;
    while (i < keys.size() || j < o.keys.size()) {
      if (j >= o.keys.size() || (i < keys.size() && keys[i] < o.keys[j])) {
        out.keys.push_back(keys[i]);
        out.containers.push_back(containers[i++]);
      } else if (i >= keys.size() || o.keys[j] < keys[i]) {
        out.keys.push_back(o.keys[j]);
        out.containers.push_back(o.containers[j++]);
      } else {
        out.keys.push_back(keys[i]);
        out.containers.push_back(union_any(containers[i++], o.containers[j++]));
      }
    }
    return out;
  }

  void union_in_place(const std::vector<const Bitmap*>& others) {
    // UnionInPlace (roaring.go:467-520): OR every source into bitmap-kind
    // targets, container by container
    for (const Bitmap* o : others) {
      for (size_t j = 0; j < o->keys.size(); j++) {
        Container* mine = get(o->keys[j]);
        if (mine == nullptr) {
          auto it = std::lower_bound(keys.begin(), keys.end(), o->keys[j]);
          size_t pos = it - keys.begin();
          keys.insert(it, o->keys[j]);
          containers.insert(containers.begin() + pos,
                            to_bitmap(o->containers[j]));
        } else {
          *mine = union_any(*mine, o->containers[j]);
        }
      }
    }
  }
};

// -- getBenchData (roaring_test.go:1243-1283) -------------------------------

struct BenchData {
  Bitmap a1, a2, b, r1, r2;
};

BenchData make_bench_data() {
  std::mt19937_64 rng(42);
  const uint64_t max = (1 << 24) / 64;
  BenchData d;
  std::vector<uint64_t> v1, v2;
  for (int i = 0; i < kArrayMaxSize / 3; i++) {
    v1.push_back(rng() % max);
    v2.push_back(rng() % max);
  }
  for (int i = 0; i < kArrayMaxSize / 3; i++) v1.push_back(rng() % max);
  d.a1 = Bitmap::from_values(std::move(v1));
  d.a2 = Bitmap::from_values(std::move(v2));

  std::vector<uint64_t> vb;
  for (int i = 0; i < 0xffff / 3; i++) vb.push_back((uint64_t)i * 3);
  d.b = Bitmap::from_values(std::move(vb));

  std::vector<uint64_t> vr1;
  for (int i = 0; i < 0xffff; i++) vr1.push_back(i);
  d.r1 = Bitmap::from_values(std::move(vr1));

  std::vector<uint64_t> vr2;
  for (int i = 0; i < 0xffff; i++) {
    vr2.push_back(i);
    if ((i & 0xfff) == 0xfff) i += 5;  // 16 runs
  }
  d.r2 = Bitmap::from_values(std::move(vr2));
  return d;
}

// -- harness ----------------------------------------------------------------

volatile int64_t g_sink;  // defeat dead-code elimination

template <typename F>
void bench(const char* name, F body, double min_seconds = 0.5) {
  body();  // warm
  int64_t iters = 1;
  double elapsed = 0;
  for (;;) {
    auto t0 = std::chrono::steady_clock::now();
    for (int64_t i = 0; i < iters; i++) g_sink = body();
    elapsed = std::chrono::duration<double>(std::chrono::steady_clock::now() -
                                            t0)
                  .count();
    if (elapsed >= min_seconds || iters > (int64_t)1e9) break;
    int64_t next = (int64_t)(iters * std::max(2.0, min_seconds / std::max(
                                                       elapsed, 1e-9) * 1.2));
    iters = std::min(next, iters * 100);
  }
  std::printf("%s %.1f %lld\n", name, elapsed / (double)iters * 1e9,
              (long long)iters);
  std::fflush(stdout);
}

}  // namespace

int main(int argc, char** argv) {
  std::string only = argc > 1 ? argv[1] : "";
  auto want = [&](const char* n) {
    return only.empty() || only == n;
  };
  BenchData d = make_bench_data();

  // roaring_test.go:1364-1423 IntersectionCount microbenches
  if (want("IntersectionCount_ArrayRun"))
    bench("IntersectionCount_ArrayRun",
          [&] { return d.a1.intersection_count_with(d.r1); });
  if (want("IntersectionCount_ArrayRuns"))
    bench("IntersectionCount_ArrayRuns",
          [&] { return d.a1.intersection_count_with(d.r2); });
  if (want("IntersectionCount_BitmapRun"))
    bench("IntersectionCount_BitmapRun",
          [&] { return d.b.intersection_count_with(d.r1); });
  if (want("IntersectionCount_BitmapRuns"))
    bench("IntersectionCount_BitmapRuns",
          [&] { return d.b.intersection_count_with(d.r2); });
  if (want("IntersectionCount_ArrayArray"))
    bench("IntersectionCount_ArrayArray", [&] {
      return d.a1.intersection_count_with(d.a2) +
             d.a2.intersection_count_with(d.a1);
    });
  if (want("IntersectionCount_ArrayBitmap"))
    bench("IntersectionCount_ArrayBitmap",
          [&] { return d.a1.intersection_count_with(d.b); });

  // roaring_test.go:1504-1522 Union / UnionBulk
  if (want("Union"))
    bench("Union", [&] {
      Bitmap u = d.a1.union_with(d.a2).union_with(d.b).union_with(
          d.r1).union_with(d.r2);
      return (int64_t)u.keys.size();
    });
  if (want("UnionBulk"))
    bench("UnionBulk", [&] {
      Bitmap bm;
      bm.union_in_place({&d.a1, &d.a2, &d.b, &d.r1, &d.r2});
      return (int64_t)bm.keys.size();
    });

  // fragment_internal_test.go:1156 BenchmarkFragment_IntersectionCount:
  // row1 = every 2nd of [0,10000) (5001 bits -> bitmap after optimize),
  // row2 = every 3rd (3334 -> array); intersection over the fragment
  {
    std::vector<uint64_t> r1v, r2v;
    for (int i = 0; i < 10000; i += 2) r1v.push_back(i);
    for (int i = 0; i < 10000; i += 3) r2v.push_back(i);
    Bitmap row1 = Bitmap::from_values(std::move(r1v));
    Bitmap row2 = Bitmap::from_values(std::move(r2v));
    if (want("Fragment_IntersectionCount"))
      bench("Fragment_IntersectionCount",
            [&] { return row1.intersection_count_with(row2); });
  }

  // engine-comparable workloads -------------------------------------------
  std::mt19937_64 rng(7);

  // executor shape: 2 rows x 128 shards x 1% of 2^20 cols
  {
    const int n_shards = 128, per_shard = 1 << 20;
    const int n_bits = per_shard / 100;
    std::vector<uint64_t> va, vb2;
    va.reserve((size_t)n_shards * n_bits);
    vb2.reserve((size_t)n_shards * n_bits);
    for (int s = 0; s < n_shards; s++) {
      for (int k = 0; k < n_bits; k++) {
        va.push_back((uint64_t)s * per_shard + rng() % per_shard);
        vb2.push_back((uint64_t)s * per_shard + rng() % per_shard);
      }
    }
    Bitmap rowa = Bitmap::from_values(std::move(va));
    Bitmap rowb = Bitmap::from_values(std::move(vb2));
    if (want("exec_128shard_1pct"))
      bench("exec_128shard_1pct",
            [&] { return rowa.intersection_count_with(rowb); }, 1.0);
  }

  // kernel shape: 2 rows x 1024 shards x ~50% density
  // (random words -> all bitmap containers; 128MB per row)
  {
    const int n_shards = 1024, conts = 16;  // 16 containers per 2^20 shard
    Bitmap rowa, rowb;
    for (int s = 0; s < n_shards; s++) {
      for (int c = 0; c < conts; c++) {
        Container ca, cb;
        ca.kind = cb.kind = Container::kBitmap;
        ca.bitmap.resize(kBitmapWords);
        cb.bitmap.resize(kBitmapWords);
        for (int w = 0; w < kBitmapWords; w++) {
          ca.bitmap[w] = rng();
          cb.bitmap[w] = rng();
        }
        rowa.keys.push_back((uint64_t)s * conts + c);
        rowa.containers.push_back(std::move(ca));
        rowb.keys.push_back((uint64_t)s * conts + c);
        rowb.containers.push_back(std::move(cb));
      }
    }
    if (want("kernel_2rows_dense_1024shard"))
      bench("kernel_2rows_dense_1024shard",
            [&] { return rowa.intersection_count_with(rowb); }, 2.0);
  }

  // groupby shape: two axes of 100 rows over 4 shards,
  // 2000 bits/row; one op = the full 100x100 cross product of pairwise
  // intersection counts — the reference's groupByIterator walks exactly
  // this per-combination count loop (executor.go:897-1090)
  {
    const int n_rows = 100, n_shards = 4, per_shard = 1 << 20;
    const int n_bits = 2000;
    const uint64_t span = (uint64_t)n_shards * per_shard;
    std::vector<Bitmap> g1(n_rows), g2(n_rows);
    for (int r = 0; r < n_rows; r++) {
      std::vector<uint64_t> v1, v2;
      v1.reserve(n_bits);
      v2.reserve(n_bits);
      for (int k = 0; k < n_bits; k++) {
        v1.push_back(rng() % span);
        v2.push_back(rng() % span);
      }
      g1[r] = Bitmap::from_values(std::move(v1));
      g2[r] = Bitmap::from_values(std::move(v2));
    }
    if (want("groupby_100x100_4shard"))
      bench("groupby_100x100_4shard", [&] {
        int64_t live = 0;
        for (int a = 0; a < n_rows; a++)
          for (int b = 0; b < n_rows; b++)
            live += g1[a].intersection_count_with(g2[b]) > 0 ? 1 : 0;
        return live;
      }, 1.0);
  }

  // http shape: Count(Intersect) of 2 rows x 100k bits over
  // 8 shards — the serving work behind one HTTP query (the Go reference's
  // wire+parse overhead is negligible against it)
  {
    const int n_shards = 8, per_shard = 1 << 20, n_bits = 100000;
    const uint64_t span = (uint64_t)n_shards * per_shard;
    std::vector<uint64_t> va, vb2;
    va.reserve(n_bits);
    vb2.reserve(n_bits);
    for (int k = 0; k < n_bits; k++) {
      va.push_back(rng() % span);
      vb2.push_back(rng() % span);
    }
    Bitmap rowa = Bitmap::from_values(std::move(va));
    Bitmap rowb = Bitmap::from_values(std::move(vb2));
    if (want("http_count_8shard"))
      bench("http_count_8shard",
            [&] { return rowa.intersection_count_with(rowb); }, 1.0);
  }

  // distributed shape: Count(Intersect) of 2 rows x 0.5%
  // density over 16 shards — what each fan-out query costs the reference
  // in kernel work before its own HTTP scatter-gather overhead
  {
    const int n_shards = 16, per_shard = 1 << 20;
    const int n_bits_per_shard = per_shard / 200;
    std::vector<uint64_t> va, vb2;
    for (int s = 0; s < n_shards; s++) {
      for (int k = 0; k < n_bits_per_shard; k++) {
        va.push_back((uint64_t)s * per_shard + rng() % per_shard);
        vb2.push_back((uint64_t)s * per_shard + rng() % per_shard);
      }
    }
    Bitmap rowa = Bitmap::from_values(std::move(va));
    Bitmap rowb = Bitmap::from_values(std::move(vb2));
    if (want("dist_count_16shard"))
      bench("dist_count_16shard",
            [&] { return rowa.intersection_count_with(rowb); }, 1.0);
  }

  // bsi shape: Sum(Range(v > thr)) over 16 shards of dense
  // BSI planes (10 bit planes + exists): range walk materializes the
  // filter row plane-by-plane (fragment.go:718-985 rangeOp GT), then the
  // sum is a filtered popcount per plane (executor.go:363 executeSum)
  {
    const int n_shards = 16, conts = 16, depth = 10;
    std::vector<std::vector<Container>> planes(depth + 1);
    for (int p = 0; p <= depth; p++) {
      planes[p].resize((size_t)n_shards * conts);
      for (auto& c : planes[p]) {
        c.kind = Container::kBitmap;
        c.bitmap.resize(kBitmapWords);
        if (p == depth) {  // exists: all set
          std::fill(c.bitmap.begin(), c.bitmap.end(), ~0ull);
        } else {
          for (int w = 0; w < kBitmapWords; w++) c.bitmap[w] = rng();
        }
      }
    }
    if (want("bsi_sum_range_16shard"))
      bench("bsi_sum_range_16shard", [&] {
        int64_t sum = 0;
        const int thr = 511;
        std::vector<uint64_t> keep(kBitmapWords), scratch(kBitmapWords);
        for (int s = 0; s < n_shards * conts; s++) {
          // rangeOp GT walk: keep := exists; descend planes
          std::memcpy(keep.data(), planes[depth][s].bitmap.data(),
                      kBitmapWords * 8);
          std::fill(scratch.begin(), scratch.end(), 0);  // matched
          for (int p = depth - 1; p >= 0; p--) {
            const uint64_t* pb = planes[p][s].bitmap.data();
            if ((thr >> p) & 1) {
              for (int w = 0; w < kBitmapWords; w++) keep[w] &= pb[w];
            } else {
              for (int w = 0; w < kBitmapWords; w++) {
                scratch[w] |= keep[w] & pb[w];
                keep[w] &= ~pb[w];
              }
            }
          }
          // sum = Σ_p 2^p * popcount(plane_p & filter)
          for (int p = 0; p < depth; p++) {
            const uint64_t* pb = planes[p][s].bitmap.data();
            int64_t n = 0;
            for (int w = 0; w < kBitmapWords; w++)
              n += __builtin_popcountll(pb[w] & scratch[w]);
            sum += n << p;
          }
        }
        return sum;
      }, 1.0);
  }

  return 0;
}
