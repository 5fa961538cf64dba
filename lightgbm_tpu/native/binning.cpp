// Native host-side binning kernels.
//
// The greedy equal-count bin boundary search (reference: bin.cpp:78-155
// GreedyFindBin) walks every distinct sampled value sequentially — a
// Python-loop hotspot at dataset-construction time (≈40% of
// from_matrix at HIGGS scale). The algorithm here transliterates the
// package's Python implementation (io/binning.py greedy_find_bin),
// which itself carries the reference's parity semantics, so the two
// must return bit-identical boundaries (tests/test_native.py).
//
// lgbt_bin_rows pushes every row of a dense matrix through the bin
// mappers in one pass (io/dataset.py::_bin_rows): each block of rows is
// read from memory once and walked feature by feature in cache, its codes
// written row-major, blocks split over OpenMP threads. Its codes equal
// the per-column path's byte for byte (tests/test_native.py).
//
// lgbt_find_bins finds the bins of every dense numerical column of the
// construction sample in one pass (io/dataset.py::_find_bin_mappers_local):
// blocks of columns over OpenMP threads, each block's values read row by
// row so that a fetched cache line serves all of its columns, then per
// column what io/binning.py BinMapper.find_bin does before its tail: the
// sort, the run-merge, the zero pseudo-value, the bounds and the per-bin
// counts. Its mappers equal find_bin's to the bit (tests/test_native.py).
//
// Built on demand by lightgbm_tpu/native/__init__.py:
//   g++ -O3 -std=c++17 -fopenmp -shared -fPIC binning.cpp -o _native.so

#include <algorithm>
#include <cmath>
#include <cstdint>
#include <cstring>
#include <limits>
#include <vector>

#ifdef _OPENMP
#include <omp.h>
#endif

namespace {

inline double next_after_up(double x) {
  return std::nextafter(x, std::numeric_limits<double>::infinity());
}

inline bool double_equal_ordered(double a, double b) {
  // b <= nextafter(a, inf) (reference Common::CheckDoubleEqualOrdered)
  return b <= next_after_up(a);
}

}  // namespace

extern "C" {

// Writes bin upper bounds (last = +inf) into out (capacity >= max_bin+1).
// Returns the number of bounds written.
int lgbt_greedy_find_bin(const double* dv, const int64_t* counts,
                         int64_t num_distinct, int max_bin,
                         int64_t total_cnt, int min_data_in_bin,
                         double* out) {
  const double kInf = std::numeric_limits<double>::infinity();
  int n_out = 0;

  if (num_distinct <= max_bin) {
    int64_t cur_cnt = 0;
    for (int64_t i = 0; i + 1 < num_distinct; ++i) {
      cur_cnt += counts[i];
      if (cur_cnt >= min_data_in_bin) {
        double val = next_after_up((dv[i] + dv[i + 1]) / 2.0);
        if (n_out == 0 || !double_equal_ordered(out[n_out - 1], val)) {
          out[n_out++] = val;
          cur_cnt = 0;
        }
      }
    }
    out[n_out++] = kInf;
    return n_out;
  }

  if (min_data_in_bin > 0) {
    max_bin = std::min<int64_t>(max_bin,
                                std::max<int64_t>(1, total_cnt / min_data_in_bin));
  }
  double mean_bin_size = static_cast<double>(total_cnt) / max_bin;
  int64_t rest_bin_cnt = max_bin;
  int64_t rest_sample_cnt = total_cnt;

  // is_big flags (counts >= mean_bin_size with the INITIAL mean)
  for (int64_t i = 0; i < num_distinct; ++i) {
    if (static_cast<double>(counts[i]) >= mean_bin_size) {
      --rest_bin_cnt;
      rest_sample_cnt -= counts[i];
    }
  }
  const double init_mean = mean_bin_size;
  mean_bin_size = static_cast<double>(rest_sample_cnt) /
                  std::max<int64_t>(rest_bin_cnt, 1);

  // upper/lower bound buffers on the stack of the caller's max_bin size
  // are avoided: we emit pair midpoints on the fly. We need the
  // previous upper bound and the next lower bound, which the streaming
  // structure provides.
  double* uppers = new double[max_bin];
  double* lowers = new double[max_bin];
  for (int i = 0; i < max_bin; ++i) uppers[i] = lowers[i] = kInf;
  int bin_cnt = 0;
  lowers[0] = dv[0];
  int64_t cur_cnt = 0;
  for (int64_t i = 0; i + 1 < num_distinct; ++i) {
    const bool big_i = static_cast<double>(counts[i]) >= init_mean;
    const bool big_next = static_cast<double>(counts[i + 1]) >= init_mean;
    if (!big_i) rest_sample_cnt -= counts[i];
    cur_cnt += counts[i];
    if (big_i || static_cast<double>(cur_cnt) >= mean_bin_size ||
        (big_next &&
         static_cast<double>(cur_cnt) >= std::max(1.0, mean_bin_size * 0.5))) {
      uppers[bin_cnt] = dv[i];
      ++bin_cnt;
      lowers[bin_cnt] = dv[i + 1];
      if (bin_cnt >= max_bin - 1) break;
      cur_cnt = 0;
      if (!big_i) {
        --rest_bin_cnt;
        mean_bin_size = static_cast<double>(rest_sample_cnt) /
                        std::max<int64_t>(rest_bin_cnt, 1);
      }
    }
  }
  ++bin_cnt;
  for (int i = 0; i + 1 < bin_cnt; ++i) {
    double val = next_after_up((uppers[i] + lowers[i + 1]) / 2.0);
    if (n_out == 0 || !double_equal_ordered(out[n_out - 1], val)) {
      out[n_out++] = val;
    }
  }
  out[n_out++] = kInf;
  delete[] uppers;
  delete[] lowers;
  return n_out;
}

// Numerical value->bin conversion over a full column (reference
// BinMapper::ValueToBin binary search, bin.h:457-495): out[i] = first j
// with bounds[j] >= v (NaN handled by the caller). uint16 output covers
// every bin width the package produces.
void lgbt_values_to_bins(const double* vals, int64_t n, const double* bounds,
                         int32_t nb, uint16_t* out) {
#ifdef _OPENMP
#pragma omp parallel for schedule(static)
#endif
  for (int64_t i = 0; i < n; ++i) {
    const double v = vals[i];
    int32_t lo = 0, hi = nb - 1;
    while (lo < hi) {
      int32_t mid = (lo + hi) >> 1;
      if (bounds[mid] < v) {
        lo = mid + 1;
      } else {
        hi = mid;
      }
    }
    out[i] = static_cast<uint16_t>(lo);
  }
}

}  // extern "C"

namespace {

// One used feature of the row pass, as io/dataset.py::_bin_rows_native
// lays it out: eight int32 fields a feature, in the order of the groups
// and of their members.
enum Field {
  kSrc = 0,     // column of the input matrix
  kDst = 1,     // column of the code matrix
  kMode = 2,    // kDirect, kFirstMember or kLaterMember
  kSkip = 3,    // a bundle member's most frequent bin
  kOffset = 4,  // a bundle member's first code in its group column
  kLen = 5,     // numerical: bounds searched; categorical: table length
  kNanBin = 6,  // numerical: NaN's bin, or -1 to bin NaN as 0.0
  kTable = 7,   // -1 numerical; else the category table's offset
  kFields = 8
};

enum Mode { kDirect = 0, kFirstMember = 1, kLaterMember = 2 };

// For each of N values, the count of bounds < v over a row of `width`
// (a power of two) bounds sorted ascending and padded with +inf: a
// branchless binary search, N searches interleaved step by step so that
// their loads overlap.
template <int N>
inline void count_below(const double* b, int32_t width, const double* v,
                        int32_t* pos) {
  for (int l = 0; l < N; ++l) pos[l] = 0;
  for (int32_t step = width >> 1; step > 0; step >>= 1) {
    for (int l = 0; l < N; ++l) {
      pos[l] += (b[pos[l] + step - 1] < v[l]) ? step : 0;
    }
  }
  for (int l = 0; l < N; ++l) pos[l] += (b[pos[l]] < v[l]) ? 1 : 0;
}

// Rows [r, r + N) of one feature: BinMapper.values_to_bins (io/binning.py)
// value by value, written as the feature's mode says. Numerical: NaN is
// its own bin under MISSING_NAN and 0.0 otherwise, else the count of
// bounds < v clipped to the last searched bound. Categorical: the
// category's bin by table, 0 for NaN, a negative value or one past the
// table (values truncate toward zero, as numpy's astype(int64)).
// Returns the count of +-inf among them.
template <int N, typename In, typename Out>
inline int64_t bin_lanes(const In* col, int64_t r, int64_t row_stride,
                         const int32_t* m, const double* bounds,
                         int32_t width, const int32_t* tab, Out* o,
                         int32_t out_cols) {
  double v[N];
  int32_t b[N];
  int64_t inf = 0;
  for (int l = 0; l < N; ++l) {
    v[l] = static_cast<double>(col[(r + l) * row_stride]);
    inf += std::isinf(v[l]) ? 1 : 0;
  }
  const int32_t len = m[kLen];
  if (tab != nullptr) {
    for (int l = 0; l < N; ++l) {
      b[l] = (v[l] > -1.0 && v[l] < static_cast<double>(len))
                 ? tab[static_cast<int64_t>(v[l])]
                 : 0;
    }
  } else {
    double key[N];
    for (int l = 0; l < N; ++l) key[l] = std::isnan(v[l]) ? 0.0 : v[l];
    count_below<N>(bounds, width, key, b);
    for (int l = 0; l < N; ++l) {
      b[l] = (std::isnan(v[l]) && m[kNanBin] >= 0) ? m[kNanBin]
                                                   : std::min(b[l], len - 1);
    }
  }
  const int32_t skip = m[kSkip];
  for (int l = 0; l < N; ++l) {
    Out* dst = o + (r + l) * out_cols;
    if (m[kMode] == kDirect) {
      *dst = static_cast<Out>(b[l]);
    } else if (b[l] != skip) {
      *dst = static_cast<Out>(m[kOffset] + b[l] - (b[l] > skip));
    } else if (m[kMode] == kFirstMember) {
      *dst = 0;
    }
  }
  return inf;
}

// Rows [r0, r1) of one feature, eight at a time.
template <typename In, typename Out>
int64_t bin_feature(const In* data, int64_t r0, int64_t r1,
                    int64_t row_stride, int64_t col_stride, const int32_t* m,
                    const double* bounds, int32_t width,
                    const int32_t* table, Out* out, int32_t out_cols) {
  const In* col = data + m[kSrc] * col_stride;
  const int32_t* tab = m[kTable] >= 0 ? table + m[kTable] : nullptr;
  Out* o = out + m[kDst];
  int64_t inf = 0, r = r0;
  for (; r + 8 <= r1; r += 8) {
    inf += bin_lanes<8>(col, r, row_stride, m, bounds, width, tab, o,
                        out_cols);
  }
  for (; r < r1; ++r) {
    inf += bin_lanes<1>(col, r, row_stride, m, bounds, width, tab, o,
                        out_cols);
  }
  return inf;
}

template <typename In, typename Out>
int bin_rows(const In* data, int64_t n, int64_t row_stride,
             int64_t col_stride, int32_t k, const int32_t* meta,
             const double* bounds, int32_t width, const int32_t* table,
             Out* out, int32_t out_cols, int64_t* inf_counts,
             int num_threads) {
  // a block of rows whose inputs (~256 KB) stay in the core's caches
  // while each feature in turn walks it, a whole number of lane groups
  const int64_t block =
      std::max<int64_t>(8, (int64_t{1} << 18) / (std::max(k, 1) * 4) / 8 * 8);
  const int64_t blocks = (n + block - 1) / block;
  int used = 1;
#ifdef _OPENMP
  if (num_threads <= 0) num_threads = omp_get_max_threads();
#pragma omp parallel num_threads(num_threads)
#endif
  {
    std::vector<int64_t> inf(k, 0);
#ifdef _OPENMP
#pragma omp single
    used = omp_get_num_threads();
#pragma omp for schedule(static)
#endif
    for (int64_t blk = 0; blk < blocks; ++blk) {
      const int64_t r0 = blk * block, r1 = std::min(n, r0 + block);
      for (int32_t j = 0; j < k; ++j) {
        inf[j] += bin_feature(data, r0, r1, row_stride, col_stride,
                              meta + j * kFields,
                              bounds + static_cast<int64_t>(j) * width,
                              width, table, out, out_cols);
      }
    }
#ifdef _OPENMP
#pragma omp critical
#endif
    for (int32_t j = 0; j < k; ++j) inf_counts[j] += inf[j];
  }
  return used;
}

}  // namespace

extern "C" {

// Every row of a dense float32 (is_f64 == 0) or float64 matrix through
// the mappers of `meta` (kFields int32 a feature) into the row-major
// [n, out_cols] code matrix, uint8 (out_u16 == 0) or uint16, rows split
// over `num_threads` OpenMP threads (<= 0: the OpenMP default). Strides
// are in elements. Adds each feature's count of +-inf to inf_counts[k]
// and returns the number of threads that ran.
int lgbt_bin_rows(const void* data, int32_t is_f64, int64_t n,
                  int64_t row_stride, int64_t col_stride, int32_t k,
                  const int32_t* meta, const double* bounds, int32_t width,
                  const int32_t* table, void* out, int32_t out_u16,
                  int32_t out_cols, int64_t* inf_counts, int num_threads) {
  if (is_f64) {
    const double* in = static_cast<const double*>(data);
    return out_u16
        ? bin_rows(in, n, row_stride, col_stride, k, meta, bounds, width,
                   table, static_cast<uint16_t*>(out), out_cols,
                   inf_counts, num_threads)
        : bin_rows(in, n, row_stride, col_stride, k, meta, bounds, width,
                   table, static_cast<uint8_t*>(out), out_cols,
                   inf_counts, num_threads);
  }
  const float* in = static_cast<const float*>(data);
  return out_u16
      ? bin_rows(in, n, row_stride, col_stride, k, meta, bounds, width,
                 table, static_cast<uint16_t*>(out), out_cols, inf_counts,
                 num_threads)
      : bin_rows(in, n, row_stride, col_stride, k, meta, bounds, width,
                 table, static_cast<uint8_t*>(out), out_cols, inf_counts,
                 num_threads);
}

}  // extern "C"

namespace {

constexpr double kZeroThreshold = 1e-35;  // io/binning.py K_ZERO_THRESHOLD

enum Missing { kMissingNone = 0, kMissingZero = 1, kMissingNan = 2 };

// io/binning.py find_bin_with_zero_as_one_bin: the negatives and the
// positives of the sorted distinct values dv (counts cnt) searched by the
// greedy walk apart, the bins shared by their counts, one bin about zero
// between them. Writes at most max_bin bounds into out; returns how many.
int zero_as_one_bin(const double* dv, const int64_t* cnt, int64_t nd,
                    int max_bin, int64_t total, int min_data_in_bin,
                    double* out) {
  int64_t left_data = 0, zero_data = 0, right_data = 0, left_cnt = nd;
  for (int64_t i = 0; i < nd; ++i) {
    if (dv[i] <= -kZeroThreshold) {
      left_data += cnt[i];
      continue;
    }
    if (left_cnt == nd) left_cnt = i;
    (dv[i] > kZeroThreshold ? right_data : zero_data) += cnt[i];
  }
  int n = 0;
  if (left_cnt > 0 && max_bin > 1) {
    const double share =
        static_cast<double>(left_data) /
        static_cast<double>(std::max<int64_t>(total - zero_data, 1));
    const int left_max_bin =
        std::max(1, static_cast<int>(share * (max_bin - 1)));
    n = lgbt_greedy_find_bin(dv, cnt, left_cnt, left_max_bin, left_data,
                             min_data_in_bin, out);
    out[n - 1] = -kZeroThreshold;
  }
  int64_t right_start = left_cnt;
  while (right_start < nd && !(dv[right_start] > kZeroThreshold)) {
    ++right_start;
  }
  const int right_max_bin = max_bin - 1 - n;
  if (right_start < nd && right_max_bin > 0) {
    out[n++] = kZeroThreshold;
    n += lgbt_greedy_find_bin(dv + right_start, cnt + right_start,
                              nd - right_start, right_max_bin, right_data,
                              min_data_in_bin, out + n);
  } else {
    out[n++] = std::numeric_limits<double>::infinity();
  }
  return n;
}

// A double's bits as a uint64 of the same order. For x neither NaN nor
// +-0, the key of nextafter(x, +inf) is the key of x plus one.
inline uint64_t ordered_key(double x) {
  uint64_t u;
  std::memcpy(&u, &x, sizeof u);
  return (u >> 63) ? ~u : u | (uint64_t{1} << 63);
}

inline double key_value(uint64_t k) {
  const uint64_t u = (k >> 63) ? k & ~(uint64_t{1} << 63) : ~k;
  double x;
  std::memcpy(&x, &u, sizeof x);
  return x;
}

constexpr int kBits = 11, kDigits = 6, kRadix = 1 << kBits;

// LSD radix sort of a[0, n) by 11-bit digits through tmp[n] and
// hist[kDigits * kRadix]; a digit that every key shares takes no pass.
void sort_keys(uint64_t* a, uint64_t* tmp, int64_t n, int64_t* hist) {
  std::fill(hist, hist + kDigits * kRadix, 0);
  for (int64_t i = 0; i < n; ++i) {
    for (int d = 0; d < kDigits; ++d) {
      ++hist[d * kRadix + ((a[i] >> (kBits * d)) & (kRadix - 1))];
    }
  }
  uint64_t *src = a, *dst = tmp;
  for (int d = 0; d < kDigits && n > 0; ++d) {
    int64_t* h = hist + d * kRadix;
    if (h[(src[0] >> (kBits * d)) & (kRadix - 1)] == n) continue;
    int64_t sum = 0;
    for (int b = 0; b < kRadix; ++b) {
      const int64_t c = h[b];
      h[b] = sum;
      sum += c;
    }
    for (int64_t i = 0; i < n; ++i) {
      dst[h[(src[i] >> (kBits * d)) & (kRadix - 1)]++] = src[i];
    }
    std::swap(src, dst);
  }
  if (src != a) std::copy(src, src + n, a);
}

// One numerical column as BinMapper.find_bin (io/binning.py) finds it,
// from the ordered keys k[0, nk) of its values of |x| > kZeroThreshold
// (NaN left out and counted in nan_cnt) among `total` sampled rows.
// Sorts k in place through tmp[nk] and hist; dv and dc are scratch.
// Writes the bounds and each bin's count (at most max_bin each), the
// number of bins and the missing type (info[0], info[1]), the least and
// the largest distinct value (range[0], range[1]).
void find_column(uint64_t* k, uint64_t* tmp, int64_t* hist, int64_t nk,
                 int64_t nan_cnt, int64_t total, int max_bin,
                 int min_data_in_bin, int use_missing, int zero_as_missing,
                 std::vector<double>& dv, std::vector<int64_t>& dc,
                 double* bounds, int64_t* cnt_in_bin, int32_t* info,
                 double* range) {
  int missing = !use_missing      ? kMissingNone
                : zero_as_missing ? kMissingZero
                : nan_cnt > 0     ? kMissingNan
                                  : kMissingNone;
  const int64_t na_cnt = missing == kMissingNan ? nan_cnt : 0;
  const int64_t zero_cnt = total - nk - na_cnt;
  sort_keys(k, tmp, nk, hist);
  // a value within one ulp of the one before (x > nextafter(prev, +inf)
  // is false) joins its run, and the run keeps its largest value
  dv.clear();
  dc.clear();
  for (int64_t i = 0; i < nk; ++i) {
    if (i == 0 || k[i] > k[i - 1] + 1) {
      dv.push_back(key_value(k[i]));
      dc.push_back(1);
    } else {
      dv.back() = key_value(k[i]);
      ++dc.back();
    }
  }
  // the zero pseudo-value: in the middle always, at either end only if
  // some sampled value is zero
  if (dv.empty()) {
    dv.push_back(0.0);
    dc.push_back(zero_cnt);
  } else {
    const int64_t pos =
        std::lower_bound(dv.begin(), dv.end(), 0.0) - dv.begin();
    const int64_t nd = static_cast<int64_t>(dv.size());
    bool insert = true;
    if (pos < nd && dv[pos] == 0.0) {
      insert = false;
    } else if (pos == 0 || pos == nd) {
      insert = zero_cnt > 0;
    }
    if (insert) {
      dv.insert(dv.begin() + pos, 0.0);
      dc.insert(dc.begin() + pos, zero_cnt);
    }
  }
  range[0] = dv.front();
  range[1] = dv.back();
  const int64_t nd = static_cast<int64_t>(dv.size());
  int nb;
  if (missing == kMissingNan) {  // the last bin is NaN's
    nb = zero_as_one_bin(dv.data(), dc.data(), nd, max_bin - 1,
                         total - na_cnt, min_data_in_bin, bounds);
    bounds[nb++] = std::numeric_limits<double>::quiet_NaN();
  } else {
    nb = zero_as_one_bin(dv.data(), dc.data(), nd, max_bin, total,
                         min_data_in_bin, bounds);
    if (missing == kMissingZero && nb == 2) missing = kMissingNone;
  }
  // each distinct value's count to the first bound at or above it
  const int n_search = nb - (missing == kMissingNan ? 1 : 0);
  std::fill(cnt_in_bin, cnt_in_bin + nb, 0);
  int j = 0;
  for (int64_t i = 0; i < nd; ++i) {
    while (j + 1 < n_search && bounds[j] < dv[i]) ++j;
    cnt_in_bin[j] += dc[i];
  }
  if (missing == kMissingNan) cnt_in_bin[nb - 1] = na_cnt;
  info[0] = nb;
  info[1] = missing;
}

template <typename In>
int find_bins(const In* data, int64_t n, int64_t row_stride,
              int64_t col_stride, int32_t k, const int32_t* cols,
              const int32_t* max_bins, int min_data_in_bin, int use_missing,
              int zero_as_missing, int32_t width, double* bounds,
              int64_t* cnt_in_bin, int32_t* info, double* range,
              int num_threads) {
  // up to eight columns a block (a row of a C-ordered block is one cache
  // line), fewer where there are fewer than eight a thread, and no more
  // than 2^22 keys (32 MB) a thread where the sample is tall
  constexpr int32_t kBlock = 8;
  int used = 1;
#ifdef _OPENMP
  if (num_threads <= 0) num_threads = omp_get_max_threads();
#endif
  const int32_t per_thread = (k + std::max(num_threads, 1) - 1) /
                             std::max(num_threads, 1);
  const int32_t block = static_cast<int32_t>(std::max<int64_t>(
      1, std::min<int64_t>({kBlock, per_thread,
                            (int64_t{1} << 22) / std::max<int64_t>(n, 1)})));
  const int32_t blocks = (k + block - 1) / block;
#ifdef _OPENMP
#pragma omp parallel num_threads(num_threads)
#endif
  {
    std::vector<uint64_t> keys(static_cast<size_t>(block) * n), tmp(n);
    std::vector<int64_t> hist(kDigits * kRadix);
    std::vector<double> dv;
    std::vector<int64_t> dc;
    dv.reserve(n + 1);
    dc.reserve(n + 1);
#ifdef _OPENMP
#pragma omp single
    used = omp_get_num_threads();
#pragma omp for schedule(dynamic, 1)
#endif
    for (int32_t blk = 0; blk < blocks; ++blk) {
      const int32_t c0 = blk * block, w = std::min(block, k - c0);
      const In* col[kBlock];
      int64_t nv[kBlock], nan_cnt[kBlock];
      for (int32_t l = 0; l < w; ++l) {
        col[l] = data + cols[c0 + l] * col_stride;
        nv[l] = nan_cnt[l] = 0;
      }
      for (int64_t r = 0; r < n; ++r) {
        for (int32_t l = 0; l < w; ++l) {
          const double x = static_cast<double>(col[l][r * row_stride]);
          if (std::isnan(x)) {
            ++nan_cnt[l];
          } else if (std::fabs(x) > kZeroThreshold) {
            keys[l * n + nv[l]++] = ordered_key(x);
          }
        }
      }
      for (int32_t l = 0; l < w; ++l) {
        const int64_t c = c0 + l;
        find_column(keys.data() + l * n, tmp.data(), hist.data(), nv[l],
                    nan_cnt[l], n, max_bins[c], min_data_in_bin, use_missing, zero_as_missing, dv, dc,
                    bounds + c * width, cnt_in_bin + c * width, info + c * 2,
                    range + c * 2);
      }
    }
  }
  return used;
}

}  // namespace

extern "C" {

// The bins of columns cols[k] of a dense float32 (is_f64 == 0) or float64
// sample of n rows (strides in elements), each as BinMapper.find_bin
// finds a numerical column's with max_bins[j] bins, blocks of columns
// over `num_threads` OpenMP threads (<= 0: the OpenMP default). Column j
// gets its bounds and per-bin counts in row j of bounds / cnt_in_bin
// ([k, width], width >= every max_bins[j]), its number of bins and
// missing type in info[2 j], info[2 j + 1], its least and largest
// distinct value in range[2 j], range[2 j + 1]. Returns the number of
// threads that ran.
int lgbt_find_bins(const void* data, int32_t is_f64, int64_t n,
                   int64_t row_stride, int64_t col_stride, int32_t k,
                   const int32_t* cols, const int32_t* max_bins,
                   int32_t min_data_in_bin, int32_t use_missing,
                   int32_t zero_as_missing, int32_t width, double* bounds,
                   int64_t* cnt_in_bin, int32_t* info, double* range,
                   int num_threads) {
  if (is_f64) {
    return find_bins(static_cast<const double*>(data), n, row_stride,
                     col_stride, k, cols, max_bins, min_data_in_bin,
                     use_missing, zero_as_missing, width, bounds, cnt_in_bin,
                     info, range, num_threads);
  }
  return find_bins(static_cast<const float*>(data), n, row_stride,
                   col_stride, k, cols, max_bins, min_data_in_bin,
                   use_missing, zero_as_missing, width, bounds, cnt_in_bin,
                   info, range, num_threads);
}

}  // extern "C"
