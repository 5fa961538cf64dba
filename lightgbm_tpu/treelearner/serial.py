"""Leaf-wise (best-first) tree grower.

TPU re-design of the reference SerialTreeLearner
(reference: src/treelearner/serial_tree_learner.cpp — Train loop at
:152-202: BeforeTrain → repeat {BeforeFindBestSplit → ConstructHistograms
→ FindBestSplitsFromHistograms (histogram subtraction for the larger
leaf at :396-404) → ArgMax over leaves → Split at :541}).

Architecture: the device executes three jitted kernels per split —
leaf-histogram (Pallas/scatter), vectorized split scan, and stable
partition — while the ~num_leaves-sized control loop stays on the host
(the reference tolerates a PCIe sync per leaf on its GPU path; the
host↔TPU latency budget here is the same shape). Kernels are
specialized on power-of-two leaf capacities so the jit cache stays
O(log N) and is reused across trees and iterations.

The histogram pool (reference feature_histogram.hpp:1061 HistogramPool)
becomes a per-leaf dict of device arrays; "smaller leaf first, larger by
subtraction" is preserved exactly.
"""
from __future__ import annotations

import functools
from typing import Dict, List, Optional

import numpy as np
import jax
import jax.numpy as jnp

from ..config import Config
from ..io.dataset import BinnedDataset
from ..io.binning import BIN_CATEGORICAL
from ..models.tree import Tree
from ..ops import histogram as H
from ..ops import quantize as Q
from ..ops import split as S
from ..obs import instrument_kernel, span as obs_span
from ..ops.partition import next_capacity, partition_leaf
from ..utils import log


class _Leaf:
    __slots__ = ("start", "count", "sum_g", "sum_h", "output", "depth",
                 "hist", "best", "cmin", "cmax")

    def __init__(self, start, count, sum_g, sum_h, output, depth,
                 hist=None, best=None, cmin=-np.inf, cmax=np.inf):
        self.start = start
        self.count = count
        self.sum_g = sum_g
        self.sum_h = sum_h
        self.output = output
        self.depth = depth
        self.hist = hist
        self.best = best
        self.cmin = cmin
        self.cmax = cmax


class SerialTreeGrower:
    """Grows one tree per call; owns the device-resident dataset view."""

    @property
    def bins(self):
        """Row-major bin matrix on device, uploaded LAZILY: the GBDT
        driver constructs this grower even when the fused path handles
        every iteration, and an eager upload strands the full [N, G]
        matrix in HBM (7.7 GB at the 13.2M x 581-bundle Allstate shape
        — the round-5 wide-sparse OOM)."""
        return self.dataset.device_bins()

    def __init__(self, dataset: BinnedDataset, config: Config) -> None:
        self.dataset = dataset
        self.config = config
        self.num_features = dataset.num_features
        mappers = dataset.bin_mappers
        self.max_num_bin = max((m.num_bin for m in mappers), default=2)
        self.any_categorical = any(m.bin_type == BIN_CATEGORICAL for m in mappers)

        monotone = [dataset.monotone_constraint(i) for i in range(self.num_features)]
        self.use_monotone = any(m != 0 for m in monotone)
        self._monotone_np = np.asarray(monotone, dtype=np.int32)
        self._mono_state = None  # per-tree, created in grow()
        penalty = list(config.feature_contri) + [1.0] * (self.num_features - len(config.feature_contri))
        # miss bin per feature for bin-space routing (NaN bin = last,
        # Zero mode = default bin; -1 = no routing). Mirrors
        # NumericalDecisionInner (tree.h:285): missing is routed by
        # default_left whenever the feature has a missing type, for any
        # num_bin; categorical routing is purely bitset membership.
        self.feature_miss_bin = np.asarray([
            -1 if m.bin_type == BIN_CATEGORICAL else
            (m.num_bin - 1 if m.missing_type == 2 else
             (m.default_bin if m.missing_type == 1 else -1))
            for m in mappers], dtype=np.int32)

        self.meta = S.FeatureMeta.build(
            num_bin=[m.num_bin for m in mappers],
            missing_type=[m.missing_type for m in mappers],
            default_bin=[m.default_bin for m in mappers],
            is_categorical=[m.bin_type == BIN_CATEGORICAL for m in mappers],
            monotone=monotone,
            penalty=[float(p) for p in penalty[:self.num_features]])
        self.split_cfg = S.SplitConfig(
            lambda_l1=config.lambda_l1, lambda_l2=config.lambda_l2,
            min_data_in_leaf=config.min_data_in_leaf,
            min_sum_hessian_in_leaf=config.min_sum_hessian_in_leaf,
            min_gain_to_split=config.min_gain_to_split,
            max_delta_step=config.max_delta_step,
            path_smooth=config.path_smooth,
            use_monotone=self.use_monotone,
            extra_trees=config.extra_trees,
            max_cat_threshold=config.max_cat_threshold,
            cat_l2=config.cat_l2, cat_smooth=config.cat_smooth,
            max_cat_to_onehot=config.max_cat_to_onehot,
            min_data_per_group=config.min_data_per_group)

        # EFB bundle views (None on dense/trivial datasets — all hist
        # and partition calls then take the direct per-feature path)
        self._efb_dev = dataset.device_bundle_tables()
        self._efb_hist = dataset.device_hist_tables()
        self.group_max_bin = dataset.group_max_bins

        self._col_rng = np.random.RandomState(config.feature_fraction_seed)
        self._extra_rng = np.random.RandomState(config.extra_seed)
        from ..compile import get_manager
        # jit entry points register as SHARED entries keyed by (config,
        # dataset trace signature): a second grower over a same-structure
        # dataset dispatches through the first grower's executables —
        # zero retraces, zero recompiles. The builders close over THIS
        # instance, which is safe precisely because the signature pins
        # every closed-over value (signature.py contract). When the
        # dataset cannot produce a shareable signature the entries fall
        # back to a per-instance uid and skip the on-disk store.
        self._shared_sig, self._sig_store = self._serial_signature()
        self._split_jit = instrument_kernel(
            get_manager().shared_entry(
                "serial/split_scan", self._shared_sig,
                lambda: jax.jit(self._split_packed),
                store=self._sig_store),
            "split", name="serial/split_scan")
        self._interaction_sets = _parse_interaction_constraints(
            config.interaction_constraints, dataset)
        self._forced_splits = _load_forced_splits(config.forcedsplits_filename)
        # CEGB state (reference cost_effective_gradient_boosting.hpp:27
        # IsEnable + the feature-used tracking consumed by DetlaGain :66)
        self._cegb_enabled = (
            config.cegb_tradeoff != 1.0 or config.cegb_penalty_split > 0.0
            or bool(config.cegb_penalty_feature_coupled)
            or bool(config.cegb_penalty_feature_lazy))
        self._cegb_coupled_used = np.zeros(self.num_features, dtype=bool)
        # histogram_pool_size (MB; <=0 unlimited; reference
        # feature_histogram.hpp:1061): when the per-leaf histogram set
        # would not fit, drop leaf histograms after their best-split
        # scan and recompute on demand (no subtraction)
        pool_mb = config.histogram_pool_size
        need = (config.num_leaves * self.num_features
                * self.max_num_bin * 2 * 4)
        self._keep_hists = pool_mb <= 0 or need <= pool_mb * 1024 * 1024
        if not self._keep_hists:
            log.info("histogram pool (%.0f MB) exceeds histogram_pool_size"
                     "=%.0f MB: recomputing leaf histograms on demand",
                     need / 1e6, pool_mb)
        self._cur_perm = None
        self._cur_grad = None
        self._cur_hess = None
        # quantized-gradient training (ops/quantize.py): per-tree scales
        # of the current iteration, None on the f32 path
        self._quant = bool(config.use_quantized_grad)
        self._mv_state = None  # lazy multival view (see _multival_state)
        self._qscales = None
        self._quant_tree_idx = 0
        self._quant_prefetch = Q.PrefetchedQuant()

    # ------------------------------------------------------------------
    def _split_packed(self, hist, sum_g, sum_h, num_data, parent_output,
                      cmin, cmax, feature_mask, rand_thresholds,
                      cegb_delta=None, gain_scale=None, qscales=None):
        if qscales is not None:
            # integer level-sums meet float arithmetic here and only
            # here (sum_g/sum_h are already dequantized leaf totals)
            hist = S.dequantize_hist(hist, qscales[0], qscales[1])
        res = S.best_split(hist, self.meta, self.split_cfg, sum_g, sum_h,
                           num_data, parent_output, cmin, cmax,
                           feature_mask=feature_mask,
                           rand_thresholds=rand_thresholds,
                           cegb_delta=cegb_delta, gain_scale=gain_scale,
                           any_categorical=self.any_categorical)
        f = res["best_feature"]
        vec = jnp.stack([
            res["best_gain"],
            res["left_sum_gradient"][f],
            res["left_sum_hessian"][f],
            res["left_output"][f],
            res["right_sum_gradient"][f],
            res["right_sum_hessian"][f],
            res["right_output"][f],
        ])
        # integer fields kept exact (counts overflow float32 at 2^24)
        ivec = jnp.stack([
            f, res["threshold"][f],
            res["default_left"][f].astype(jnp.int32),
            res["left_count"][f], res["right_count"][f],
            res["found"][f].astype(jnp.int32),
        ]).astype(jnp.int32)
        if self.any_categorical:
            cat = jnp.concatenate([
                jnp.stack([res["cat_family"][f].astype(jnp.int32),
                           res["cat_used_bin"][f].astype(jnp.int32)]),
                res["cat_sorted_order"][f].astype(jnp.int32)])
        else:
            cat = jnp.zeros(2, jnp.int32)
        return vec, ivec, cat

    def _serial_signature(self):
        """(sig, shareable) — everything that shapes this grower's traced
        programs besides per-call shapes: the config plus the dataset
        trace signature (mapper structure, monotone constraints, EFB
        table contents — io/dataset.py trace_signature). Unlike the
        fused grower, serial entries CLOSE OVER dataset tables, so the
        dataset identity must live in the signature, not the args."""
        from ..compile import config_signature
        ds_sig, shareable = self.dataset.trace_signature()
        return {
            "config": config_signature(self.config),
            "ds": ds_sig,
            "num_features": self.num_features,
            "max_num_bin": self.max_num_bin,
            "group_max_bin": self.group_max_bin,
            "any_categorical": self.any_categorical,
            "use_monotone": self.use_monotone,
            "split_cfg": self.split_cfg,
            "efb": self._efb_dev is not None,
            "efb_hist": self._efb_hist is not None,
        }, shareable

    def _multival_state(self):
        """Lazily built row-wise multi-value view of the dataset
        (ops/multival.py): (codes [n, K] device, total_bins, group
        tables). Only materialized when hist_method picked the multival
        layout for this dataset; like the other serial entries the
        tables are CLOSED OVER — the dataset identity in _shared_sig
        pins them."""
        if self._mv_state is None:
            from ..ops import multival as MV
            ds = self.dataset
            occ = ds.occupancy
            if ds.bundles is not None:
                gnb = ds.bundles.group_num_bins
            else:
                gnb = np.asarray([m.num_bin for m in ds.bin_mappers],
                                 np.int32)
            codes, lay = MV.build_rowwise_codes(ds.bins, gnb,
                                                occ.default_code)
            self._mv_state = (jnp.asarray(codes), lay.total_bins,
                              MV.group_tables(gnb, occ.default_code))
        return self._mv_state

    @functools.lru_cache(maxsize=64)
    def _hist_fn(self, capacity: int):
        B = self.max_num_bin
        Bg = self.group_max_bin
        efb_hist = self._efb_hist
        method = H.hist_method(self.config, self.dataset)

        if method == "multival_pallas":
            from ..ops import multival as MV
            codes_dev, total_bins, tables = self._multival_state()

            def fn(bins, perm, start, count, grad, hess):
                # ``bins`` ignored: the multival path reads the packed
                # present-code view instead of the [n, G] bin matrix
                flat = MV.leaf_histogram_multival(
                    codes_dev, perm, start, count, grad, hess,
                    capacity, total_bins, use_pallas=True)
                ghist = MV.group_hist_from_flat(flat, tables)
                if efb_hist is None:
                    return ghist
                from ..io.efb import per_feature_hist
                total = flat[-1]
                return per_feature_hist(ghist, efb_hist, total[0],
                                        total[1])
        else:
            def fn(bins, perm, start, count, grad, hess):
                if efb_hist is None:
                    return H.leaf_histogram(bins, perm, start, count,
                                            grad, hess, capacity, B,
                                            method=method)
                # bundle-space histogram over G << F columns, then gather
                # to per-feature space with FixHistogram mfb
                # reconstruction
                from ..io.efb import per_feature_hist
                ghist = H.leaf_histogram(bins, perm, start, count, grad,
                                         hess, capacity, Bg,
                                         method=method)
                total = ghist[0].sum(axis=0)  # every row in one code
                return per_feature_hist(ghist, efb_hist, total[0],
                                        total[1])
        from ..compile import get_manager
        sig = dict(self._shared_sig, capacity=capacity,
                   hist_method=method)
        return instrument_kernel(
            get_manager().shared_entry("serial/leaf_histogram", sig,
                                       lambda: jax.jit(fn),
                                       store=self._sig_store),
            "hist", name="serial/leaf_histogram")

    @functools.lru_cache(maxsize=64)
    def _partition_fn(self, capacity: int):
        efb = self._efb_dev
        from ..compile import get_manager

        def fn(bins, perm, start, count, feature, threshold, default_left,
               miss_bin, is_cat, cat_bitset):
            return partition_leaf(bins, perm, start, count, feature,
                                  threshold, default_left, miss_bin, is_cat,
                                  cat_bitset, capacity, efb=efb)
        sig = dict(self._shared_sig, capacity=capacity)
        entry = get_manager().shared_entry("serial/partition_leaf", sig,
                                           lambda: jax.jit(fn),
                                           store=self._sig_store)
        return instrument_kernel(entry, "partition",
                                 name="serial/partition_leaf")

    # ------------------------------------------------------------------
    def _feature_mask_tree(self) -> np.ndarray:
        """Per-tree feature_fraction sampling (reference
        col_sampler.hpp:20 ResetByTree)."""
        f = self.num_features
        mask = np.ones(f, dtype=bool)
        frac = self.config.feature_fraction
        if frac < 1.0:
            k = max(1, int(np.ceil(frac * f)))
            chosen = self._col_rng.choice(f, size=k, replace=False)
            mask[:] = False
            mask[chosen] = True
        return mask

    def _feature_mask_node(self, tree_mask: np.ndarray,
                           branch_features: Optional[set]) -> np.ndarray:
        """Per-node sampling + interaction constraints (reference
        col_sampler.hpp GetByNode)."""
        mask = tree_mask
        frac = self.config.feature_fraction_bynode
        if frac < 1.0:
            idx = np.flatnonzero(mask)
            k = max(1, int(np.ceil(frac * len(idx))))
            chosen = self._col_rng.choice(idx, size=k, replace=False)
            mask = np.zeros_like(mask)
            mask[chosen] = True
        if self._interaction_sets and branch_features is not None:
            allowed = np.zeros_like(mask)
            for s in self._interaction_sets:
                if branch_features <= s:
                    for fi in s:
                        if fi < len(allowed):
                            allowed[fi] = True
            mask = mask & allowed
        return mask

    def _cegb_delta(self, leaf: "_Leaf"):
        """Cost-Effective Gradient Boosting gain penalty per feature
        (reference cost_effective_gradient_boosting.hpp DetlaGain :66:
        tradeoff * (penalty_split * n_leaf + coupled penalty if the
        feature is unused so far + lazy penalty per not-yet-used data;
        lazy is approximated at leaf granularity here)."""
        if not self._cegb_enabled:
            return None
        cfg = self.config
        delta = np.full(self.num_features,
                        cfg.cegb_penalty_split * leaf.count, dtype=np.float64)
        coupled = cfg.cegb_penalty_feature_coupled
        lazy = cfg.cegb_penalty_feature_lazy
        for i, real in enumerate(self.dataset.real_feature_index):
            if coupled and real < len(coupled) and not self._cegb_coupled_used[i]:
                delta[i] += coupled[real]
            if lazy and real < len(lazy):
                delta[i] += lazy[real] * leaf.count
        return jnp.asarray(delta * cfg.cegb_tradeoff, jnp.float32)

    def _rand_thresholds(self) -> Optional[jax.Array]:
        if not self.config.extra_trees:
            return None
        nb = np.asarray([m.num_bin for m in self.dataset.bin_mappers])
        hi = np.maximum(nb - 2, 1)
        r = self._extra_rng.randint(0, 1 << 30, size=self.num_features) % hi
        return jnp.asarray(r.astype(np.int32))

    # ------------------------------------------------------------------
    def prefetch_quantize(self, grad: jax.Array, hess: jax.Array) -> None:
        """Dispatch the quantization pass for an upcoming grow() call
        NOW, up to two trees ahead of consumption (the double buffer in
        ops/quantize.py PrefetchedQuant). Key indices advance exactly
        as the inline path's would, so the stochastic-rounding draws
        are bit-identical; grow() falls back to the inline pass when
        its arguments don't match a slot. No-op on the f32 path."""
        if not self._quant or self._quant_prefetch.full:
            return
        cfg = self.config
        idx = self._quant_tree_idx + len(self._quant_prefetch)
        key = jax.random.fold_in(
            jax.random.PRNGKey(cfg.objective_seed ^ 0x51A7), idx)
        self._quant_prefetch.push(idx, grad, hess, Q.quantize_gradients(
            grad, hess, cfg.num_grad_quant_bins, key,
            cfg.stochastic_rounding))

    def grow(self, grad: jax.Array, hess: jax.Array, perm: jax.Array,
             num_data: int) -> Tree:
        """Train one tree (reference SerialTreeLearner::Train,
        serial_tree_learner.cpp:152-202).

        grad/hess: [N] device arrays (already bag-masked: zero outside
        the bag); perm: [N] permutation with the bag's rows in
        [0, num_data).
        """
        cfg = self.config
        tree = Tree(cfg.num_leaves, track_branch_features=bool(self._interaction_sets))
        tree_mask = self._feature_mask_tree()
        rand_thr = self._rand_thresholds()
        if self.use_monotone:
            from .monotone import MonotoneState
            self._mono_state = MonotoneState(
                cfg.monotone_constraints_method, cfg.num_leaves,
                self._monotone_np)

        raw_grad, raw_hess = grad, hess
        self._qscales = None
        if self._quant:
            # one quantization pass per tree; histograms, the pool, and
            # subtraction then run in exact int32 level space. The pass
            # itself usually dispatched ahead (prefetch_quantize) — the
            # inline fallback is bit-identical (same fold_in key)
            with obs_span("gradient quantization", phase="quantize"):
                Q.note_requantize(cfg.num_grad_quant_bins)
                pre = self._quant_prefetch.pop_match(
                    self._quant_tree_idx, grad, hess)
                if pre is None:
                    key = jax.random.fold_in(
                        jax.random.PRNGKey(cfg.objective_seed ^ 0x51A7),
                        self._quant_tree_idx)
                    pre = Q.quantize_gradients(
                        grad, hess, cfg.num_grad_quant_bins, key,
                        cfg.stochastic_rounding)
                self._quant_tree_idx += 1
                grad, hess, gs, hs = pre
                self._qscales = (gs, hs)

        self._cur_perm, self._cur_grad, self._cur_hess = perm, grad, hess
        root = _Leaf(0, num_data, 0.0, 0.0, 0.0, 0)
        cap = next_capacity(num_data)
        root.hist = self._hist_fn(cap)(self.bins, perm, 0, num_data, grad, hess)
        # root sums from the histogram (every row lands in exactly one bin
        # of feature 0), so out-of-bag rows never contribute — the
        # reference computes these in LeafSplits::Init over bag indices
        if self._quant:
            # leaf totals live in dequantized f32 units host-side; ONE
            # transfer for the two quant scales and both root sums
            # tpulint: sync-ok(per-tree root stats, single batched transfer)
            gsh, hsh, sg, sh = jax.device_get(
                (self._qscales[0], self._qscales[1],
                 jnp.sum(root.hist[0, :, 0]), jnp.sum(root.hist[0, :, 1])))
            self._qscales_host = (float(gsh), float(hsh))
            root.sum_g = float(sg) * self._qscales_host[0]
            root.sum_h = float(sh) * self._qscales_host[1]
        else:
            # tpulint: sync-ok(per-tree root stats, single batched transfer)
            sg, sh = jax.device_get((jnp.sum(root.hist[0, :, 0]),
                                     jnp.sum(root.hist[0, :, 1])))
            root.sum_g, root.sum_h = float(sg), float(sh)
        leaves: Dict[int, _Leaf] = {0: root}
        if self._forced_splits is not None:
            perm = self._apply_forced_splits(tree, leaves, perm, grad, hess)
        for leaf in leaves.values():
            leaf.best = self._compute_best(
                leaf, tree_mask, set() if self._interaction_sets else None,
                rand_thr)
            if not self._keep_hists:
                leaf.hist = None

        for _ in range(cfg.num_leaves - 1 - tree.num_nodes):
            # pick the globally-best leaf (reference ArgMax at :188)
            best_leaf, best_gain = -1, 0.0
            for lid, leaf in leaves.items():
                if leaf.best is None:
                    continue
                if cfg.max_depth > 0 and leaf.depth >= cfg.max_depth:
                    continue
                if leaf.best["gain"] > best_gain:
                    best_leaf, best_gain = lid, leaf.best["gain"]
            if best_leaf < 0:
                break
            perm = self._split_leaf(tree, leaves, best_leaf, perm, grad, hess,
                                    tree_mask, rand_thr)

        self.last_perm = perm
        if self._quant and cfg.quant_train_renew_leaf:
            self._renew_leaf_values(tree, leaves, perm, raw_grad, raw_hess)
        return tree

    def _renew_leaf_values(self, tree: Tree, leaves: Dict[int, _Leaf],
                           perm, grad, hess) -> None:
        """Refit leaf outputs from the EXACT f32 grad/hess sums after a
        quantized growth (reference quant_train_renew_leaf,
        gradient_discretizer RenewIntGradTreeOutput): the tree structure
        keeps the quantized decisions, the leaf values drop the
        level-rounding error. Window sums come from one device cumsum
        over the final leaf-ordered permutation; only per-leaf boundary
        prefix values transfer to the host."""
        items = [(lid, lf) for lid, lf in leaves.items() if lf.count > 0]
        if not items:
            return
        cg = jnp.cumsum(grad[perm])
        ch = jnp.cumsum(hess[perm])
        ends = jnp.asarray([lf.start + lf.count - 1 for _, lf in items],
                           jnp.int32)
        los = np.asarray([lf.start - 1 for _, lf in items])
        lo_idx = jnp.asarray(np.maximum(los, 0), jnp.int32)
        # tpulint: sync-ok(per-tree leaf renewal, already one batched transfer)
        ge, he, gl, hl = jax.device_get(
            (cg[ends], ch[ends], cg[lo_idx], ch[lo_idx]))
        has_lo = los >= 0
        sum_g = np.asarray(ge, np.float64) - np.where(has_lo, gl, 0.0)
        sum_h = np.asarray(he, np.float64) - np.where(has_lo, hl, 0.0)
        cfg = self.config
        for (lid, lf), g, h in zip(items, sum_g, sum_h):
            if cfg.lambda_l1 > 0:
                g = np.sign(g) * max(abs(g) - cfg.lambda_l1, 0.0)
            out = -g / (h + cfg.lambda_l2 + S.K_EPSILON)
            if cfg.max_delta_step > 0:
                out = float(np.clip(out, -cfg.max_delta_step,
                                    cfg.max_delta_step))
            if self.use_monotone:
                out = float(np.clip(out, lf.cmin, lf.cmax))
            tree.leaf_value[lid] = float(out)

    # ------------------------------------------------------------------
    def _compute_best(self, leaf: _Leaf, tree_mask: np.ndarray,
                      branch_features: Optional[set],
                      rand_thr) -> Optional[dict]:
        if leaf.count < 2 * self.config.min_data_in_leaf \
                or leaf.sum_h < 2 * self.config.min_sum_hessian_in_leaf:
            return None
        drop_after = False
        if leaf.hist is None:
            # pool-capped mode: recompute this leaf's histogram from its
            # still-valid permutation window (reference HistogramPool
            # miss -> reconstruct)
            cap = next_capacity(leaf.count)
            leaf.hist = self._hist_fn(cap)(
                self.bins, self._cur_perm, jnp.int32(leaf.start),
                jnp.int32(leaf.count), self._cur_grad, self._cur_hess)
            drop_after = True
        mask = self._feature_mask_node(tree_mask, branch_features)
        cegb = self._cegb_delta(leaf)
        scale = None
        if self.use_monotone and self.config.monotone_penalty > 0:
            from .monotone import monotone_penalty_factor
            fac = monotone_penalty_factor(leaf.depth,
                                          self.config.monotone_penalty)
            scale = jnp.asarray(
                np.where(self._monotone_np != 0, fac, 1.0), jnp.float32)
        args = (
            leaf.hist, jnp.float32(leaf.sum_g), jnp.float32(leaf.sum_h),
            jnp.int32(leaf.count), jnp.float32(leaf.output),
            jnp.float32(leaf.cmin), jnp.float32(leaf.cmax),
            jnp.asarray(mask), rand_thr if rand_thr is not None
            else jnp.zeros(self.num_features, jnp.int32), cegb, scale)
        if self._qscales is not None:
            vec, ivec, cat = self._split_jit(*args, self._qscales)
        else:
            vec, ivec, cat = self._split_jit(*args)
        # per-leaf best-split readback: ONE transfer for the packed
        # split vector, its int lanes, and the categorical block
        # tpulint: sync-ok(per-leaf split readback, single batched transfer)
        vec, ivec, cat = jax.device_get((vec, ivec, cat))
        v = np.asarray(vec, dtype=np.float64)
        iv = np.asarray(ivec, dtype=np.int64)
        if drop_after:
            leaf.hist = None
        if not iv[5] or not np.isfinite(v[0]) or v[0] <= 0.0:
            return None
        best = {
            "feature": int(iv[0]), "gain": float(v[0]), "threshold": int(iv[1]),
            "default_left": bool(iv[2]), "left_sum_gradient": float(v[1]),
            "left_sum_hessian": float(v[2]), "left_count": int(iv[3]),
            "left_output": float(v[3]), "right_sum_gradient": float(v[4]),
            "right_sum_hessian": float(v[5]), "right_count": int(iv[4]),
            "right_output": float(v[6]),
        }
        if self.any_categorical:
            c = np.asarray(cat)
            best["cat_family"] = int(c[0])
            best["cat_used_bin"] = int(c[1])
            best["cat_sorted_order"] = c[2:]
        return best

    def _split_leaf(self, tree: Tree, leaves: Dict[int, _Leaf], lid: int,
                    perm, grad, hess, tree_mask, rand_thr) -> None:
        """Apply the stored best split (reference SplitInner,
        serial_tree_learner.cpp:541-660)."""
        leaf = leaves[lid]
        best = leaf.best
        fi = best["feature"]
        mapper = self.dataset.bin_mappers[fi]
        real_feature = self.dataset.real_feature_index[fi]
        is_cat = mapper.bin_type == BIN_CATEGORICAL
        mono = self.dataset.monotone_constraint(fi)
        if self._mono_state is not None:
            self._mono_state.before_split(tree, lid, mono)

        if is_cat:
            bin_set = self._cat_bins(best)
            bitset_bins = np.zeros((self.max_num_bin + 31) // 32, dtype=np.uint32)
            for b in bin_set:
                bitset_bins[b // 32] |= np.uint32(1 << (b % 32))
            cat_vals = sorted(mapper.bin_2_categorical[b] for b in bin_set
                              if mapper.bin_2_categorical[b] >= 0)
            right_leaf = tree.split_categorical(
                lid, fi, real_feature, sorted(bin_set), cat_vals,
                best["left_output"], best["right_output"],
                best["left_count"], best["right_count"],
                best["left_sum_hessian"], best["right_sum_hessian"],
                best["gain"], mapper.missing_type)
            cat_bitset_dev = jnp.asarray(bitset_bins)
            thr, dl, mb = 0, False, -1
        else:
            threshold_real = mapper.bin_to_value(best["threshold"])
            right_leaf = tree.split(
                lid, fi, real_feature, best["threshold"], threshold_real,
                best["left_output"], best["right_output"],
                best["left_count"], best["right_count"],
                best["left_sum_hessian"], best["right_sum_hessian"],
                best["gain"], mapper.missing_type, best["default_left"])
            cat_bitset_dev = jnp.zeros(1, jnp.uint32)
            thr, dl, mb = best["threshold"], best["default_left"], \
                int(self.feature_miss_bin[fi])

        cap = next_capacity(leaf.count)
        new_perm, left_count = self._partition_fn(cap)(
            self.bins, perm, jnp.int32(leaf.start), jnp.int32(leaf.count),
            jnp.int32(fi), jnp.int32(thr), bool(dl), jnp.int32(mb),
            bool(is_cat), cat_bitset_dev)
        # tpulint: sync-ok(partition count steers the host grow loop)
        lc = int(left_count)
        rc = leaf.count - lc

        # monotone constraint propagation (reference
        # monotone_constraints.hpp Basic/IntermediateLeafConstraints)
        lcmin, lcmax, rcmin, rcmax = leaf.cmin, leaf.cmax, leaf.cmin, leaf.cmax
        updated_leaves: List[int] = []
        if self._mono_state is not None:
            ms = self._mono_state
            updated_leaves = ms.update(
                tree, lid, right_leaf, mono, not is_cat,
                best["left_output"], best["right_output"], fi,
                best["threshold"],
                lambda l: l in leaves and leaves[l].best is not None)
            lcmin, lcmax = ms.cmin[lid], ms.cmax[lid]
            rcmin, rcmax = ms.cmin[right_leaf], ms.cmax[right_leaf]

        left = _Leaf(leaf.start, lc, best["left_sum_gradient"],
                     best["left_sum_hessian"], best["left_output"],
                     leaf.depth + 1, cmin=lcmin, cmax=lcmax)
        right = _Leaf(leaf.start + lc, rc, best["right_sum_gradient"],
                      best["right_sum_hessian"], best["right_output"],
                      leaf.depth + 1, cmin=rcmin, cmax=rcmax)

        # histogram: smaller child directly, larger by subtraction
        # (reference serial_tree_learner.cpp:396-404); pool-capped mode
        # computes both directly and keeps nothing
        self._cur_perm = new_perm
        smaller, larger = (left, right) if lc <= rc else (right, left)
        scap = next_capacity(max(smaller.count, 1))
        smaller.hist = self._hist_fn(scap)(
            self.bins, new_perm, jnp.int32(smaller.start),
            jnp.int32(smaller.count), grad, hess)
        if self._keep_hists and leaf.hist is not None:
            larger.hist = leaf.hist - smaller.hist
        else:
            lcap = next_capacity(max(larger.count, 1))
            larger.hist = self._hist_fn(lcap)(
                self.bins, new_perm, jnp.int32(larger.start),
                jnp.int32(larger.count), grad, hess)
        leaf.hist = None

        branches = None
        if self._interaction_sets:
            # branch features are tracked as real ids; constraints are in
            # inner-feature space
            branches = {self.dataset.inner_feature_index[f]
                        for f in tree.branch_features[lid]
                        if f in self.dataset.inner_feature_index}
        left.best = self._compute_best(left, tree_mask, branches, rand_thr)
        right.best = self._compute_best(right, tree_mask, branches, rand_thr)
        if not self._keep_hists:
            left.hist = None
            right.hist = None

        leaves[lid] = left
        leaves[right_leaf] = right
        # intermediate monotone mode: leaves whose bounds tightened must
        # re-search their best split (reference serial_tree_learner.cpp
        # :650-658 consuming leaves_need_update)
        for ul in updated_leaves:
            if ul in (lid, right_leaf):
                continue
            u = leaves[ul]
            u.cmin = self._mono_state.cmin[ul]
            u.cmax = self._mono_state.cmax[ul]
            ub = None
            if self._interaction_sets:
                ub = {self.dataset.inner_feature_index[f]
                      for f in tree.branch_features[ul]
                      if f in self.dataset.inner_feature_index}
            u.best = self._compute_best(u, tree_mask, ub, rand_thr)
        if self._cegb_enabled:
            self._cegb_coupled_used[fi] = True
        return new_perm

    def _apply_forced_splits(self, tree: Tree, leaves: Dict[int, _Leaf],
                             perm, grad, hess):
        """Apply user-forced splits BFS-wise before the best-first loop
        (reference SerialTreeLearner::ForceSplits,
        serial_tree_learner.cpp:427; stats at a fixed threshold as in
        GatherInfoForThreshold, feature_histogram.hpp:515)."""
        from ..ops.split import K_EPSILON
        cfg = self.config
        q = [(self._forced_splits, 0)]
        while q and tree.num_leaves < cfg.num_leaves:
            node, lid = q.pop(0)
            real_f = node.get("feature")
            if real_f is None:
                continue
            inner = self.dataset.inner_feature_index.get(int(real_f))
            if inner is None:
                log.warning("Forced split on unused feature %s ignored", real_f)
                continue
            leaf = leaves[lid]
            mapper = self.dataset.bin_mappers[inner]
            thr_bin = int(mapper.value_to_bin(float(node["threshold"])))
            thr_bin = max(0, min(thr_bin, mapper.num_bin - 2))
            if leaf.hist is None:  # pool-capped mode dropped it
                cap = next_capacity(max(leaf.count, 1))
                leaf.hist = self._hist_fn(cap)(
                    self.bins, perm, jnp.int32(leaf.start),
                    jnp.int32(leaf.count), grad, hess)
            # tpulint: sync-ok(forced-splits path, config-gated and rare)
            hist = np.asarray(leaf.hist[inner], dtype=np.float64)  # [B, 2]
            if self._quant:
                # level-sums → f32 units to match leaf.sum_g/sum_h
                hist = hist * np.asarray(self._qscales_host, np.float64)
            miss = int(self.feature_miss_bin[inner])
            sel = np.arange(hist.shape[0]) <= thr_bin
            if miss >= 0:
                sel = sel & (np.arange(hist.shape[0]) != miss)
            lg = float(hist[sel, 0].sum())
            lh = float(hist[sel, 1].sum()) + K_EPSILON
            rg = leaf.sum_g - lg
            rh = leaf.sum_h + 2 * K_EPSILON - lh
            cnt_factor = leaf.count / (leaf.sum_h + 2 * K_EPSILON)
            lcnt = int(np.floor(hist[sel, 1].sum() * cnt_factor + 0.5))
            l1, l2 = cfg.lambda_l1, cfg.lambda_l2

            def out(g, h):
                s = np.sign(g) * max(0.0, abs(g) - l1) if l1 > 0 else g
                return -s / (h + l2)

            forced_best = {
                "feature": inner, "gain": 0.0, "threshold": thr_bin,
                "default_left": False,
                "left_sum_gradient": lg, "left_sum_hessian": lh - K_EPSILON,
                "left_count": lcnt, "left_output": out(lg, lh),
                "right_sum_gradient": rg, "right_sum_hessian": rh - K_EPSILON,
                "right_count": leaf.count - lcnt, "right_output": out(rg, rh),
            }
            leaf.best = forced_best
            n_before = tree.num_leaves
            perm = self._split_leaf(tree, leaves, lid, perm, grad, hess,
                                    np.ones(self.num_features, dtype=bool),
                                    None)
            right_leaf = n_before  # new leaf id assigned by Tree.split
            if "left" in node and isinstance(node["left"], dict):
                q.append((node["left"], lid))
            if "right" in node and isinstance(node["right"], dict):
                q.append((node["right"], right_leaf))
        return perm

    def _cat_bins(self, best: dict) -> List[int]:
        """Materialize the left-side category bin set from the scan's
        (family, position, sorted order) description."""
        fam = best["cat_family"]
        pos = best["threshold"]
        if fam == 0:
            return [pos]
        order = best["cat_sorted_order"]
        used = best["cat_used_bin"]
        if fam == 1:
            return [int(order[i]) for i in range(pos + 1)]
        return [int(order[used - 1 - i]) for i in range(pos + 1)]


def _load_forced_splits(filename: str):
    """Parse forcedsplits_filename JSON (reference serial_tree_learner
    constructor, serial_tree_learner.cpp:36-44)."""
    if not filename:
        return None
    import json as _json
    try:
        with open(filename) as fh:
            return _json.load(fh)
    except Exception as e:
        log.warning("Cannot load forced splits from %s: %s", filename, e)
        return None


def _parse_interaction_constraints(spec, dataset: BinnedDataset):
    """interaction_constraints -> list of allowed inner-feature-id sets
    (reference config.h interaction_constraints + col_sampler filtering)."""
    if not spec:
        return []
    groups = spec
    if isinstance(spec, str):
        import json as _json
        try:
            groups = _json.loads(spec.replace("(", "[").replace(")", "]"))
        except Exception:
            log.warning("Cannot parse interaction_constraints %r", spec)
            return []
    out = []
    for g in groups:
        inner = set()
        for f in g:
            i = dataset.inner_feature_index.get(int(f))
            if i is not None:
                inner.add(i)
        out.append(inner)
    return out
