"""Binned training dataset — the TPU data plane.

TPU-native re-design of the reference Dataset/DatasetLoader/Metadata
(reference: src/io/dataset.cpp, src/io/dataset_loader.cpp, src/io/metadata.cpp,
include/LightGBM/dataset.h).  Instead of per-feature ``Bin`` objects with
virtual push/iterate calls and EFB feature-group packing into column blobs
(dataset.cpp:50-302), the whole dataset is one packed integer ndarray
``bins [num_data, num_features]`` (uint8 when every feature has <=256 bins)
that is uploaded to TPU HBM once; histogramming, split finding and
partitioning consume it as dense arrays.  Bin finding itself
(``BinMapper.find_bin``) runs host-side on a bounded sample, exactly like the
reference (bin_construct_sample_cnt, dataset_loader.cpp:527
ConstructFromSampleData).

Exclusive Feature Bundling: sparse near-mutually-exclusive features are
packed into shared uint8 bundle columns (io/efb.py; reference
dataset.cpp:50-302 GetConflictCount/FindGroups/FastFeatureBundling), so
the HBM matrix is [N, num_groups] with num_groups << num_features on
sparse data, and every histogram pass touches only the bundled columns.
scipy CSR/CSC inputs are consumed without densifying the raw floats —
only the bundled bin-code matrix is ever materialized.
"""
from __future__ import annotations

import json
from typing import Any, Dict, List, Optional, Sequence, Union

import numpy as np

from .. import native, obs
from ..compile import get_manager
from ..config import Config
from ..obs.spans import span
from ..utils import log
from .binning import (BIN_CATEGORICAL, BIN_NUMERICAL, K_ZERO_THRESHOLD,
                      MISSING_NAN, MISSING_NONE, MISSING_ZERO, BinMapper)
from .efb import BundleTables, build_bundles


def _is_sparse(data) -> bool:
    try:
        import scipy.sparse as sp
        return sp.issparse(data)
    except ImportError:
        return False


def _csc_col(data, f: int):
    """(row_indices, values) of column ``f`` of a CSC matrix — the only
    sparse access pattern the data plane needs (reference sparse_bin.hpp
    iterates per-feature nonzeros the same way)."""
    start, end = data.indptr[f], data.indptr[f + 1]
    return data.indices[start:end], data.data[start:end]


def _reject_inf_feature(names, f: int, count: int) -> None:
    """±Inf feature values corrupt bin boundaries and flow silently into
    histogram sums; reject at construction, naming the column and its
    ``count`` of them. NaN stays legal — it is the missing-value
    representation (reference BinMapper::ValueToBin routes NaN through
    the NA bin)."""
    if count:
        log.fatal(
            "Feature '%s' (column %d) contains %d infinite value(s); "
            "replace them with NaN (missing) or clip to a finite range",
            names[f] if f < len(names) else str(f), f, count)


# the longest category table the row pass keeps for one feature; a
# categorical feature with a larger category is binned column by column
_ROW_PASS_MAX_CATEGORY = 1 << 16


def _row_pass_len(m: BinMapper) -> int:
    """How many bounds the row pass searches for mapper ``m`` (the last
    one clips: NaN's bin under MISSING_NAN is not searched), or the
    length of its category table; 0 where it keeps no row-pass form."""
    if m.bin_type == BIN_NUMERICAL:
        return m.num_bin - (1 if m.missing_type == MISSING_NAN else 0)
    top = max((c for c in m.categorical_2_bin if c >= 0), default=-1) + 1
    return max(top, 1) if top <= _ROW_PASS_MAX_CATEGORY else 0


class Metadata:
    """Per-row training metadata (reference: src/io/metadata.cpp,
    include/LightGBM/dataset.h:40-248): label, weights, query boundaries,
    init scores."""

    def __init__(self, num_data: int) -> None:
        self.num_data = num_data
        self.label: Optional[np.ndarray] = None
        self.weights: Optional[np.ndarray] = None
        self.init_score: Optional[np.ndarray] = None
        self.query_boundaries: Optional[np.ndarray] = None

    def set_label(self, label: Optional[np.ndarray]) -> None:
        if label is None:
            self.label = None
            return
        label = np.asarray(label, dtype=np.float32).reshape(-1)
        if len(label) != self.num_data:
            log.fatal("Length of label (%d) != num_data (%d)", len(label), self.num_data)
        bad = ~np.isfinite(label)
        if bad.any():
            # reference metadata.cpp refuses NaN labels at load; a NaN
            # here poisons every gradient silently
            log.fatal(
                "Label contains %d non-finite value(s) (NaN/Inf), first "
                "at row %d; clean the label column before constructing "
                "the Dataset", int(bad.sum()), int(np.flatnonzero(bad)[0]))
        self.label = label

    def set_weights(self, weights: Optional[np.ndarray]) -> None:
        if weights is None:
            self.weights = None
            return
        weights = np.asarray(weights, dtype=np.float32).reshape(-1)
        if len(weights) != self.num_data:
            log.fatal("Length of weights (%d) != num_data (%d)", len(weights), self.num_data)
        self.weights = weights

    def set_init_score(self, init_score: Optional[np.ndarray]) -> None:
        if init_score is None:
            self.init_score = None
            return
        init_score = np.asarray(init_score, dtype=np.float64).reshape(-1, order="F")
        if len(init_score) % self.num_data != 0:
            log.fatal("Length of init_score is not a multiple of num_data")
        bad = ~np.isfinite(init_score)
        if bad.any():
            log.fatal(
                "init_score contains %d non-finite value(s) (NaN/Inf), "
                "first at position %d; scores must be finite",
                int(bad.sum()), int(np.flatnonzero(bad)[0]))
        self.init_score = init_score

    def set_query(self, group: Optional[np.ndarray]) -> None:
        """``group`` is per-query sizes (like the reference's group field);
        converted to boundaries (reference metadata.cpp query_boundaries_)."""
        if group is None:
            self.query_boundaries = None
            return
        group = np.asarray(group, dtype=np.int64).reshape(-1)
        if group.sum() != self.num_data:
            log.fatal("Sum of query counts (%d) != num_data (%d)", int(group.sum()), self.num_data)
        self.query_boundaries = np.concatenate([[0], np.cumsum(group)]).astype(np.int32)

    @property
    def num_queries(self) -> int:
        return 0 if self.query_boundaries is None else len(self.query_boundaries) - 1


class BinnedDataset:
    """The constructed training dataset: packed bin codes + metadata.

    Equivalent of a fully-loaded reference ``Dataset`` (dataset.cpp:315
    Construct + FinishLoad): ``bins`` is [num_data, num_used_features] int,
    ``bin_mappers`` holds per-used-feature mappers, ``real_feature_index``
    maps used-feature -> original column (reference used_feature_map_ inverse).
    """

    def __init__(self) -> None:
        self.num_data: int = 0
        self.num_total_features: int = 0
        self.bins: Optional[np.ndarray] = None  # [N, G] group bin codes
        self.bin_mappers: List[BinMapper] = []
        self.real_feature_index: List[int] = []  # used idx -> original idx
        self.inner_feature_index: Dict[int, int] = {}  # original -> used or absent
        self.feature_names: List[str] = []
        self.metadata: Metadata = Metadata(0)
        self.max_bin: int = 255
        self.bundles: Optional[BundleTables] = None  # None == identity
        self._device_bins = None
        self._monotone_constraints: List[int] = []
        # construct-time row-occupancy statistics (ops/multival.py
        # OccupancyStats) driving the planar-vs-multival histogram
        # layout decision; None until a bin matrix exists
        self.occupancy = None

    # ------------------------------------------------------------------
    @property
    def num_features(self) -> int:
        return len(self.bin_mappers)

    @property
    def num_bins_per_feature(self) -> np.ndarray:
        return np.asarray([m.num_bin for m in self.bin_mappers], dtype=np.int32)

    @property
    def max_num_bin(self) -> int:
        return int(self.num_bins_per_feature.max()) if self.bin_mappers else 1

    def feature_offsets(self) -> np.ndarray:
        """Flattened per-feature bin offsets (for distributed histogram
        packing; reference Dataset group_bin_boundaries_ analogue)."""
        nb = self.num_bins_per_feature
        return np.concatenate([[0], np.cumsum(nb)]).astype(np.int32)

    def device_bins(self):
        """The packed bin matrix as a device array (uploaded once to HBM)."""
        import jax.numpy as jnp
        if self._device_bins is None:
            self._device_bins = jnp.asarray(self.bins)
        return self._device_bins

    # --- EFB views --------------------------------------------------------
    @property
    def efb_trivial(self) -> bool:
        return self.bundles is None or self.bundles.is_trivial

    @property
    def group_max_bins(self) -> int:
        """Max bin-code count over the physical bundle columns (== max
        feature num_bin when bundling is trivial)."""
        if self.efb_trivial:
            return self.max_num_bin
        return int(self.bundles.group_num_bins.max())

    def device_bundle_tables(self):
        """(group_of, offset_of, nslots_of, skip_of) device arrays, or
        None when bundling is trivial (consumers then index features
        directly — zero overhead on dense data)."""
        if self.efb_trivial:
            return None
        return self.bundles.device()

    def device_hist_tables(self):
        """Gather tables for bundle-hist → per-feature-hist conversion."""
        if self.efb_trivial:
            return None
        return self.bundles.hist_tables(
            [m.num_bin for m in self.bin_mappers], self.max_num_bin)

    def feature_bins(self) -> np.ndarray:
        """Decoded per-feature bin matrix [N, F_used] (host). Identity
        when bundling is trivial; otherwise materializes the dense view —
        used only by consumers that cannot work in bundle space
        (add_features_from, parallel-learner debundling)."""
        if self.efb_trivial:
            return self.bins
        bt = self.bundles
        f_used = len(self.bin_mappers)
        dtype = np.uint8 if all(m.num_bin <= 256 for m in self.bin_mappers) \
            else np.uint16
        out = np.empty((self.num_data, f_used), dtype=dtype)
        for f in range(f_used):
            codes = self.bins[:, bt.group_of[f]].astype(np.int32)
            rel = codes - bt.offset_of[f]
            inband = (rel >= 0) & (rel < bt.nslots_of[f])
            dec = rel + (rel >= bt.skip_of[f])
            out[:, f] = np.where(inband, dec, bt.skip_of[f]).astype(dtype)
        return out

    def debundle(self) -> None:
        """Replace the bundled bin matrix with the per-feature view
        (consumers that shard by feature — parallel learners — keep their
        simple layout; the reference supports EFB there via FeatureGroup
        indirection, which is a later-round TPU design)."""
        if self.efb_trivial:
            return
        self.bins = self.feature_bins()
        self.bundles = None
        self._device_bins = None
        self._measure_occupancy()  # stats follow the layout change

    # ------------------------------------------------------------------
    @staticmethod
    def _find_bin_mappers_local(sample_col_nonzeros, total_features: int,
                                sample_cnt: int, config: Config,
                                cat_set) -> List["BinMapper"]:
        """Single-machine per-feature bin finding
        (DatasetLoader::ConstructBinMappers, dataset_loader.cpp:527)."""
        mappers: List[BinMapper] = []
        for f in range(total_features):
            _, col = sample_col_nonzeros(f)
            nonzero = col[(np.abs(col) > K_ZERO_THRESHOLD) | np.isnan(col)]
            m = BinMapper()
            if config.max_bin_by_feature and f < len(config.max_bin_by_feature):
                mb = config.max_bin_by_feature[f]
            else:
                mb = config.max_bin
            m.find_bin(nonzero, sample_cnt, mb,
                       min_data_in_bin=config.min_data_in_bin,
                       min_split_data=config.min_data_in_leaf,
                       pre_filter=config.feature_pre_filter,
                       bin_type=(BIN_CATEGORICAL if f in cat_set
                                 else BIN_NUMERICAL),
                       use_missing=config.use_missing,
                       zero_as_missing=config.zero_as_missing)
            mappers.append(m)
        return mappers

    @classmethod
    def from_matrix(cls, data: np.ndarray, config: Config,
                    label: Optional[np.ndarray] = None,
                    weight: Optional[np.ndarray] = None,
                    group: Optional[np.ndarray] = None,
                    init_score: Optional[np.ndarray] = None,
                    feature_names: Optional[Sequence[str]] = None,
                    categorical_feature: Optional[Sequence[int]] = None,
                    reference: Optional["BinnedDataset"] = None) -> "BinnedDataset":
        """Construct from a raw row-major matrix.

        Mirrors LGBM_DatasetCreateFromMat -> DatasetLoader::ConstructFromSampleData
        (reference src/c_api.cpp, src/io/dataset_loader.cpp:527): sample rows,
        find bins per feature, then push all rows through the mappers.
        ``reference`` aligns bin mappers with a previously-constructed dataset
        (validation data; reference Dataset::CreateValid, dataset.cpp).
        """
        sparse_input = _is_sparse(data)
        data_csr = None
        if sparse_input:
            import scipy.sparse as sp
            # keep the CSR form (when that is what arrived) for the
            # row-sampling step below: re-deriving CSR from the CSC of
            # a multi-billion-nnz matrix is a second full sort + copy
            if sp.isspmatrix_csr(data):
                data_csr = data
            data = data.tocsc() if not sp.isspmatrix_csc(data) else data
        else:
            data = np.asarray(data)
            if data.ndim != 2:
                log.fatal("Data must be 2-dimensional")
        n, total_features = data.shape
        ds = cls()
        ds.num_data = n
        ds.num_total_features = total_features
        ds.metadata = Metadata(n)
        ds.metadata.set_label(label)
        ds.metadata.set_weights(weight)
        ds.metadata.set_query(group)
        ds.metadata.set_init_score(init_score)
        ds.max_bin = config.max_bin

        if feature_names is None:
            feature_names = [f"Column_{i}" for i in range(total_features)]
        ds.feature_names = list(feature_names)

        if reference is not None:
            # validation set: reuse the reference's mappers AND bundles
            # (scores are updated by bin-space traversal, which decodes
            # through the training set's bundle tables)
            ds.bin_mappers = reference.bin_mappers
            ds.real_feature_index = reference.real_feature_index
            ds.inner_feature_index = reference.inner_feature_index
            ds.feature_names = reference.feature_names
            ds.max_bin = reference.max_bin
            ds._monotone_constraints = reference._monotone_constraints
            ds.bundles = reference.bundles
            ds._apply_mappers(data, config.num_threads)
            return ds

        if categorical_feature is None:
            categorical_feature = _parse_categorical(config.categorical_feature,
                                                     ds.feature_names)
        cat_set = set(categorical_feature or [])

        # --- sampling for bin finding (dataset_loader.cpp:120-165) ---
        sample_cnt = min(config.bin_construct_sample_cnt, n)
        rng = np.random.RandomState(config.data_random_seed)
        get_manager().phase = "construct"
        with span("dataset/sample", stage="construct/sample"):
            if sample_cnt < n:
                sample_idx = np.sort(rng.choice(n, size=sample_cnt,
                                                replace=False))
                if sparse_input:
                    rows = data_csr if data_csr is not None else data.tocsr()
                    sample = rows[sample_idx].tocsc()
                else:
                    sample = data[sample_idx]
            else:
                sample = data
            if not sparse_input:
                sample = np.asarray(sample, dtype=np.float64)

        def sample_col_nonzeros(f):
            """(row_indices, values) of the sample column's stored
            entries — full column for dense input."""
            if sparse_input:
                idx, vals = _csc_col(sample, f)
                return idx, np.asarray(vals, dtype=np.float64)
            col = sample[:, f]
            return np.arange(sample_cnt), col

        # --- per-feature bin finding ---
        with span("dataset/find_bins", stage="construct/find_bins"):
            if config.num_machines > 1:
                # distributed construction protocol: per-rank owned-feature
                # binning + mapper allgather over the mesh (reference
                # dataset_loader.cpp:917-990). Single-controller mode bins
                # over the full in-process sample, so boundaries are
                # bit-identical to single-machine construction. Sparse
                # samples stay CSC end-to-end (round-5: the dense-only
                # restriction is gone — column slices come from the CSC
                # structure inside find_bins_for_features)
                from .distributed import distributed_find_bin_mappers
                mappers = distributed_find_bin_mappers(
                    sample if sparse_input
                    else np.asarray(sample, dtype=np.float64),
                    config, cat_set)
            else:
                mappers = cls._find_bin_mappers_local(
                    sample_col_nonzeros, total_features, sample_cnt, config,
                    cat_set)

        used = [f for f in range(total_features) if not mappers[f].is_trivial]
        if not used:
            log.warning("There are no meaningful features, as all feature values are constant.")
        ds.bin_mappers = [mappers[f] for f in used]
        ds.real_feature_index = used
        ds.inner_feature_index = {f: i for i, f in enumerate(used)}
        if config.monotone_constraints:
            ds._monotone_constraints = [
                config.monotone_constraints[f] if f < len(config.monotone_constraints) else 0
                for f in used]

        # --- EFB bundling decision over the sample (dataset.cpp:50-302) ---
        with span("dataset/bundle", stage="construct/bundle"):
            if config.enable_bundle and len(used) > 1:
                from .efb import bundle_eligible
                nonzero_rows: List[np.ndarray] = []
                bundle_ok: List[bool] = []
                empty = np.empty(0, dtype=np.int64)
                for i, f in enumerate(used):
                    m = ds.bin_mappers[i]
                    ok = bundle_eligible(m) and m.sparse_rate >= 0.5
                    bundle_ok.append(ok)
                    if not ok:
                        nonzero_rows.append(empty)
                        continue
                    idx, vals = sample_col_nonzeros(f)
                    b = m.values_to_bins(vals)
                    nonzero_rows.append(
                        np.asarray(idx)[b != m.most_freq_bin])
                ds.bundles = build_bundles(
                    nonzero_rows, ds.bin_mappers, sample_cnt, True,
                    bundle_ok=bundle_ok,
                    max_bundle_bins=config.efb_max_bundle_bins,
                    max_conflict_rate=config.efb_max_conflict_rate)
                if ds.bundles.is_trivial:
                    ds.bundles = None
        ds._apply_mappers(data, config.num_threads)
        return ds

    def _apply_mappers(self, data: np.ndarray, num_threads: int = 0) -> None:
        """Push every row through the mappers into the packed bin-code
        matrix: [N, F_used] per-feature codes when bundling is trivial,
        [N, num_groups] bundle codes otherwise (reference
        FeatureGroup::PushData / Bin::Push; sparse inputs touch only
        their stored entries — never densified)."""
        with span("dataset/bin_rows", stage="construct/bin_rows"):
            self.bins = self._bin_rows(data, num_threads)
        self.num_data = data.shape[0]
        with span("dataset/occupancy", stage="construct/occupancy"):
            self._measure_occupancy()

    def _bin_rows(self, data, num_threads: int = 0) -> np.ndarray:
        """The code matrix, one column a group (a feature when bundling
        is trivial). A dense float32 / float64 matrix goes through the
        native row pass (`_bin_rows_native`), every group it can bin;
        the rest, and sparse input, column by column. Both give the
        same codes byte for byte (tests/test_native.py)."""
        n = data.shape[0]
        sparse = _is_sparse(data)
        mappers = self.bin_mappers
        bt = self.bundles
        if bt is None or bt.is_trivial:
            groups = [[i] for i in range(len(mappers))]
            wide = any(m.num_bin > 256 for m in mappers)
        else:
            groups = bt.groups
            wide = int(bt.group_num_bins.max()) > 256
        dtype = np.uint16 if wide else np.uint8
        bins = np.empty((n, len(groups)), dtype=dtype)
        row_inf = self._bin_rows_native(data, groups, bins, num_threads)

        def col_bins(i: int):
            """(row_indices_or_None, codes) for used feature i; None row
            indices mean 'all rows, in order'."""
            f = self.real_feature_index[i]
            if sparse:
                idx, vals = _csc_col(data, f)
                vals = np.asarray(vals, dtype=np.float64)
                _reject_inf_feature(self.feature_names, f,
                                    int(np.isinf(vals).sum()))
                return idx, mappers[i].values_to_bins(vals)
            col = np.asarray(data[:, f], dtype=np.float64)
            _reject_inf_feature(self.feature_names, f,
                                int(np.isinf(col).sum()))
            return None, mappers[i].values_to_bins(col)

        # group by group in order, so that the first infinite column
        # named is the same whichever path binned it
        for g, members in enumerate(groups):
            if members[0] in row_inf:
                for i in members:
                    _reject_inf_feature(self.feature_names,
                                        self.real_feature_index[i],
                                        row_inf[i])
            elif len(members) == 1:
                i = members[0]
                idx, codes = col_bins(i)
                if idx is None:
                    bins[:, g] = codes.astype(dtype)
                else:
                    bins[:, g] = dtype(mappers[i].value_to_bin(0.0))
                    bins[idx, g] = codes.astype(dtype)
            else:
                # shared column: code 0 = every member at its
                # most-frequent bin; later members overwrite on the
                # (conflict-budgeted) overlapping rows
                code = np.zeros(n, dtype=dtype)
                for i in members:
                    idx, codes = col_bins(i)
                    mfb = bt.skip_of[i]
                    keep = codes != mfb
                    rows = np.flatnonzero(keep) if idx is None else idx[keep]
                    b = codes[keep]
                    slot = b - (b > mfb)
                    code[rows] = (bt.offset_of[i] + slot).astype(dtype)
                bins[:, g] = code
        reg = obs.active()
        if reg is not None:
            reg.inc("dataset.bin_rows_fused_cols", len(row_inf))
            reg.inc("dataset.bin_rows_column_cols",
                    len(mappers) - len(row_inf))
        return bins

    def _bin_rows_native(self, data, groups, bins: np.ndarray,
                         num_threads: int) -> Dict[int, int]:
        """Bin every group whose members all have a row-pass form into
        ``bins`` by ONE native pass over the rows of ``data``
        (native/binning.cpp lgbt_bin_rows): the rows read where they lie,
        once from memory, their codes written row-major. Returns {used
        feature: count of +-inf} for the features it binned; empty where
        the input or the library does not allow the pass."""
        if not self.bin_mappers or not native.row_pass_input(data) \
                or max(self.real_feature_index) >= data.shape[1]:
            return {}
        mappers = self.bin_mappers
        plan = [(g, i) for g, members in enumerate(groups)
                if all(_row_pass_len(mappers[i]) for i in members)
                for i in members]
        if not plan:
            return {}
        features = []
        for g, i in plan:
            m = mappers[i]
            members = groups[g]
            place = {}
            if len(members) > 1:
                place = dict(
                    mode=(native.FIRST_MEMBER if i == members[0]
                          else native.LATER_MEMBER),
                    skip=int(self.bundles.skip_of[i]),
                    offset=int(self.bundles.offset_of[i]))
            if m.bin_type == BIN_NUMERICAL:
                how = dict(bounds=m.bin_upper_bound[:_row_pass_len(m)],
                           nan_bin=(m.num_bin - 1
                                    if m.missing_type == MISSING_NAN else -1))
            else:
                table = np.zeros(_row_pass_len(m), dtype=np.int32)
                for cat, b in m.categorical_2_bin.items():
                    if cat >= 0:
                        table[cat] = b
                how = dict(table=table)
            features.append(native.RowPassFeature(
                src=self.real_feature_index[i], dst=g, **place, **how))
        inf, threads = native.bin_rows_native(data, features, bins,
                                              num_threads)
        log.info("Host binning: rows in one native pass (%d threads, %d of "
                 "%d columns)", threads, len(plan), len(mappers))
        return {i: int(c) for (_, i), c in zip(plan, inf)}

    def _measure_occupancy(self) -> None:
        """Record construct-time row-occupancy statistics (mean/max
        present codes per row, per-group density, sampled default
        codes) for the planar-vs-multival histogram layout decision —
        ops/histogram.py hist_layout(). Sampled and cheap; runs on
        every construction path (from_matrix, create_valid reference,
        load_binary) so the stats always match the current bin
        matrix."""
        self.occupancy = None
        if self.bins is None or self.bins.size == 0:
            return
        from ..ops.multival import measure_occupancy
        self.occupancy = measure_occupancy(self.bins)

    # ------------------------------------------------------------------
    def create_valid(self, data: np.ndarray, label=None, weight=None,
                     group=None, init_score=None) -> "BinnedDataset":
        ds = BinnedDataset.from_matrix(
            data, Config(), label=label, weight=weight, group=group,
            init_score=init_score, reference=self)
        return ds

    def monotone_constraint(self, inner_feature: int) -> int:
        if not self._monotone_constraints:
            return 0
        return self._monotone_constraints[inner_feature]

    def trace_signature(self) -> "tuple[str, bool]":
        """(digest, shareable) identity of everything dataset-derived
        that shapes a traced learner program.

        Bin boundary VALUES deliberately do not enter: traced programs
        operate on bin codes and route on bin-index thresholds, so two
        datasets with identical mapper *structure* (per-feature num_bin
        / missing_type / default_bin / bin_type), identical monotone
        constraints, and identical EFB bundle tables trace byte-
        identical programs — letting same-shaped learners share one
        compiled executable (compile/manager.py shared_entry).

        EFB table CONTENTS are hashed (not just shape) because learners
        close over the device copies; two different bundlings must not
        alias one program.

        On any failure the fallback is a per-instance uid: sharing is
        lost, correctness kept — callers should then register their
        entries with store=False so uid keys never pollute the on-disk
        AOT store."""
        if getattr(self, "_trace_sig", None) is None:
            import hashlib
            try:
                h = hashlib.sha256()
                for m in self.bin_mappers:
                    h.update(("%d,%d,%d,%d;" % (
                        m.num_bin, m.missing_type, m.default_bin,
                        m.bin_type)).encode())
                h.update(np.asarray(self._monotone_constraints or [],
                                    np.int32).tobytes())
                bt = self.bundles
                if bt is not None and not bt.is_trivial:
                    for a in (bt.group_of, bt.offset_of, bt.nslots_of,
                              bt.skip_of, bt.group_num_bins):
                        h.update(np.ascontiguousarray(a).tobytes())
                occ = self.occupancy
                if occ is not None:
                    # DERIVED discrete occupancy values only (never the
                    # raw float stats — jittery means must not fracture
                    # the AOT key space): the bucketed row capacity
                    # shapes the multival planes, the wide-sparse bool
                    # is the auto layout decision, and the sampled
                    # default codes are closed over by serial multival
                    # entries (ops/multival.py group tables)
                    from ..ops.multival import (
                        bucket_row_capacity, MULTIVAL_MIN_GROUPS,
                        MULTIVAL_MAX_OCCUPANCY)
                    wide = (occ.num_groups >= MULTIVAL_MIN_GROUPS
                            and occ.row_nnz_mean
                            <= MULTIVAL_MAX_OCCUPANCY * occ.num_groups)
                    h.update(("mv:%d,%d;" % (
                        bucket_row_capacity(occ.row_nnz_max),
                        int(wide))).encode())
                    h.update(np.ascontiguousarray(
                        occ.default_code).tobytes())
                self._trace_sig = ("ds-" + h.hexdigest()[:20], True)
            except Exception:
                self._trace_sig = ("uid-%x" % id(self), False)
        return self._trace_sig

    # --- binary cache (reference Dataset::SaveBinaryFile, dataset.cpp:890) ---
    def save_binary(self, filename: str) -> None:
        header = {
            "num_data": self.num_data,
            "num_total_features": self.num_total_features,
            "real_feature_index": self.real_feature_index,
            "feature_names": self.feature_names,
            "max_bin": self.max_bin,
            "monotone_constraints": self._monotone_constraints,
            "bin_mappers": [m.to_dict() for m in self.bin_mappers],
            "bundle_groups": None if self.efb_trivial else self.bundles.groups,
            "bins_dtype": str(self.bins.dtype),
            "has_label": self.metadata.label is not None,
            "has_weights": self.metadata.weights is not None,
            "has_query": self.metadata.query_boundaries is not None,
            "has_init_score": self.metadata.init_score is not None,
        }
        with open(filename, "wb") as fh:
            hdr = json.dumps(header).encode()
            fh.write(b"LGTPU1\n")
            fh.write(len(hdr).to_bytes(8, "little"))
            fh.write(hdr)
            fh.write(self.bins.tobytes())
            if self.metadata.label is not None:
                fh.write(self.metadata.label.astype(np.float32).tobytes())
            if self.metadata.weights is not None:
                fh.write(self.metadata.weights.astype(np.float32).tobytes())
            if self.metadata.query_boundaries is not None:
                qb = self.metadata.query_boundaries.astype(np.int32)
                fh.write(len(qb).to_bytes(8, "little"))
                fh.write(qb.tobytes())
            if self.metadata.init_score is not None:
                isc = self.metadata.init_score.astype(np.float64)
                fh.write(len(isc).to_bytes(8, "little"))
                fh.write(isc.tobytes())

    @classmethod
    def load_binary(cls, filename: str) -> "BinnedDataset":
        with open(filename, "rb") as fh:
            magic = fh.readline()
            if magic != b"LGTPU1\n":
                log.fatal("%s is not a lightgbm_tpu binary dataset file", filename)
            hdr_len = int.from_bytes(fh.read(8), "little")
            header = json.loads(fh.read(hdr_len).decode())
            ds = cls()
            ds.num_data = header["num_data"]
            ds.num_total_features = header["num_total_features"]
            ds.real_feature_index = list(header["real_feature_index"])
            ds.inner_feature_index = {f: i for i, f in enumerate(ds.real_feature_index)}
            ds.feature_names = list(header["feature_names"])
            ds.max_bin = header["max_bin"]
            ds._monotone_constraints = list(header["monotone_constraints"])
            ds.bin_mappers = [BinMapper.from_dict(d) for d in header["bin_mappers"]]
            groups = header.get("bundle_groups")
            if groups:
                ds.bundles = BundleTables(
                    [list(g) for g in groups],
                    [m.num_bin for m in ds.bin_mappers],
                    [m.most_freq_bin for m in ds.bin_mappers])
            dtype = np.dtype(header["bins_dtype"])
            n, f = ds.num_data, len(ds.bin_mappers) if not groups else len(groups)
            ds.bins = np.frombuffer(fh.read(n * f * dtype.itemsize), dtype=dtype).reshape(n, f).copy()
            ds.metadata = Metadata(n)
            if header["has_label"]:
                ds.metadata.label = np.frombuffer(fh.read(4 * n), dtype=np.float32).copy()
            if header["has_weights"]:
                ds.metadata.weights = np.frombuffer(fh.read(4 * n), dtype=np.float32).copy()
            if header["has_query"]:
                qn = int.from_bytes(fh.read(8), "little")
                ds.metadata.query_boundaries = np.frombuffer(fh.read(4 * qn), dtype=np.int32).copy()
            if header["has_init_score"]:
                sn = int.from_bytes(fh.read(8), "little")
                ds.metadata.init_score = np.frombuffer(fh.read(8 * sn), dtype=np.float64).copy()
        ds._measure_occupancy()
        return ds


def _parse_categorical(spec: Union[str, List[int], List[str], None],
                       feature_names: Sequence[str]) -> List[int]:
    """Resolve Config.categorical_feature (indices, names, or 'name:a,b' /
    '0,1,2' strings; reference config.h categorical_feature doc) to column
    indices."""
    if spec is None:
        return []
    if isinstance(spec, str):
        s = spec.strip()
        if not s:
            return []
        items: List[Any] = [x for x in (s[5:] if s.startswith("name:") else s).split(",") if x]
    else:
        items = list(spec)
    out: List[int] = []
    name_index = {nm: i for i, nm in enumerate(feature_names)}
    for it in items:
        if isinstance(it, str) and not it.lstrip("-").isdigit():
            if it in name_index:
                out.append(name_index[it])
            else:
                log.warning("Unknown categorical feature name %s, ignored", it)
        else:
            out.append(int(it))
    return out
