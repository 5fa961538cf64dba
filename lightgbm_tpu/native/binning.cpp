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
// Built on demand by lightgbm_tpu/native/__init__.py:
//   g++ -O3 -std=c++17 -fopenmp -shared -fPIC binning.cpp -o _native.so

#include <algorithm>
#include <cmath>
#include <cstdint>
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
