"""Multi-chip parallel tree learners over a JAX device mesh.

TPU re-design of the reference's distributed tree learners
(reference: src/treelearner/data_parallel_tree_learner.cpp — local
histograms + Network::ReduceScatter at :169 + SyncUpGlobalBestSplit
:240; feature_parallel_tree_learner.cpp — feature shards, all data on
every machine, allreduce-max of SplitInfo; voting_parallel_tree_learner
.cpp — PV-Tree top-k voting then selective histogram reduction).

The socket/MPI collective stack (src/network/) disappears entirely: rows
are sharded over a 1-D `jax.sharding.Mesh` axis ("data"), per-shard
histograms are summed with `jax.lax.psum` (or `psum_scatter` for the
feature-sharded variant) inside `shard_map`, and the split decision is
computed replicated — the reference's Allreduce-max of packed SplitInfo
(parallel_tree_learner.h:190-213) becomes an ordinary argmax on the
already-global histogram, which is bitwise-identical on every shard.

Host control flow is identical to the serial grower; only the three
device kernels change:
- leaf histogram: shard-local gather + psum           [cross-chip: ICI]
- best split: replicated scan over global histograms  [no comm]
- partition: shard-local, per-shard (start, count)    [no comm]

Voting-parallel reduces ICI volume by only reducing histograms of the
2k vote-winning features; feature-parallel replicates rows and shards
the scan. Both reuse this class's machinery.
"""
from __future__ import annotations

import functools
from typing import Dict, Optional

import numpy as np
import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..config import Config
from ..io.dataset import BinnedDataset
from ..models.tree import Tree
from ..network import collective_span
from ..obs import instrument_kernel, span
from ..ops import histogram as H
from ..ops import quantize as Q
from ..ops import split as S
from ..ops.partition import next_capacity
from ..ops.partition import _decision_go_left
from ..utils import log
from .serial import SerialTreeGrower, _Leaf
from .fused import FusedSerialGrower


def shard_bag_permutation(perm, bag_cnt: int, num_shards: int,
                          rows_per_shard: int):
    """Global bag permutation -> per-shard LOCAL permutations (bag rows
    first, in order) + per-shard bag counts — the reference's
    SetBaggingData semantics applied to each machine's own row shard.
    Shard d owns global rows [d*rows_per_shard, (d+1)*rows_per_shard)."""
    D, sr = num_shards, rows_per_shard
    mask = np.zeros(D * sr, dtype=bool)
    mask[np.asarray(perm[:bag_cnt])] = True
    perm_np = np.empty((D, sr), np.int32)
    counts = np.empty(D, np.int32)
    m2 = mask.reshape(D, sr)
    for d in range(D):
        bag_local = np.flatnonzero(m2[d]).astype(np.int32)
        oob_local = np.flatnonzero(~m2[d]).astype(np.int32)
        perm_np[d] = np.concatenate([bag_local, oob_local])
        counts[d] = len(bag_local)
    return perm_np, counts


def build_mesh(config: Config) -> Mesh:
    """Mesh from tpu_mesh_shape (defaults to all devices on one axis)."""
    devices = np.asarray(jax.devices())
    if config.tpu_mesh_shape:
        shape = tuple(config.tpu_mesh_shape)
        n = int(np.prod(shape))
        if n > len(devices):
            log.fatal("tpu_mesh_shape %s needs %d devices, have %d",
                      shape, n, len(devices))
        devices = devices[:n].reshape(shape)
        axes = tuple(f"axis{i}" for i in range(len(shape) - 1)) + ("data",) \
            if len(shape) > 1 else ("data",)
        return Mesh(devices, axes)
    return Mesh(devices, ("data",))


class DataParallelTreeGrower(SerialTreeGrower):
    """Row-sharded learner (reference data_parallel_tree_learner.cpp).

    The dataset's bin matrix is laid out [D, N/D, F] (one leading shard
    axis), per-shard permutations are [D, cap_shard], and every leaf
    tracks per-shard (start, count) vectors host-side. Histogram psum
    rides ICI; everything else is shard-local.
    """

    supports_hist_subtraction = True

    def __init__(self, dataset: BinnedDataset, config: Config,
                 mesh: Optional[Mesh] = None) -> None:
        super().__init__(dataset, config)
        self.mesh = mesh if mesh is not None else build_mesh(config)
        self.num_shards = self.mesh.shape["data"]
        d = self.num_shards
        n = dataset.num_data
        self.rows_per_shard = (n + d - 1) // d
        pad = self.rows_per_shard * d - n
        bins_np = np.asarray(dataset.bins)
        if pad:
            bins_np = np.pad(bins_np, ((0, pad), (0, 0)), mode="edge")
        self._shard_valid_rows = np.full(d, self.rows_per_shard, np.int32)
        if pad:
            self._shard_valid_rows[-1] -= pad
        sharded = bins_np.reshape(d, self.rows_per_shard, -1)
        self.bins_sharded = jax.device_put(
            sharded, NamedSharding(self.mesh, P("data", None, None)))
        self._spec_rows = NamedSharding(self.mesh, P("data", None))

    # -- sharded kernels ------------------------------------------------
    # the voting override's local vote scan needs the per-tree
    # dequantization scales as traced args; this learner's psum does not
    _hist_takes_scales = False

    @functools.lru_cache(maxsize=64)
    def _hist_fn_sharded(self, capacity: int, packed: bool = False):
        B = self.max_num_bin
        Bg = self.group_max_bin
        efb_hist = self._efb_hist
        mesh = self.mesh
        # no dataset handle: the host-loop parallel learners always take
        # the planar/radix kernels (the multival layout is a serial- and
        # fused-learner path; see ops/histogram.py hist_method)
        method = H.hist_method(self.config)

        @jax.jit
        @functools.partial(
            shard_map, mesh=mesh, check_vma=False,
            in_specs=(P("data", None, None), P("data", None), P("data"),
                      P("data"), P("data", None), P("data", None)),
            out_specs=P())
        def fn(bins, perm, start, count, grad, hess):
            # leading length-1 shard axis inside the body
            h = H.leaf_histogram(bins[0], perm[0], start[0], count[0],
                                 grad[0], hess[0], capacity,
                                 Bg if efb_hist is not None else B,
                                 method=method)
            # ReduceScatter+Allgather of the reference (:169) collapses
            # to one ICI all-reduce; feature-sharded scan is a later
            # optimization once profiling justifies psum_scatter
            if packed:
                # quantized path, small leaf: both int32 level-sum
                # lanes of every cell fit 16 bits (Q.packed_rows_ok
                # checked host-side), so one packed [*, B] word psum
                # moves HALF the bytes of the [*, B, 2] reduction —
                # the integer-collective saving of the quantized
                # training paper
                hist = Q.packed_hist_to_pairs(
                    jax.lax.psum(Q.pairs_to_packed_hist(h), "data"))
            else:
                hist = jax.lax.psum(h, "data")
            # exact global leaf sums (root sums in the reference come
            # from an Allreduce of (count, Σg, Σh) tuples, :126-152);
            # int32 level sums under quantized training (host rescales)
            sg = jax.lax.psum(jnp.sum(h[0, :, 0]), "data")
            sh = jax.lax.psum(jnp.sum(h[0, :, 1]), "data")
            if efb_hist is not None:
                # EFB bundles stay sharded (round-4: no more debundling
                # under parallel learners): the bundle-space histogram
                # is psum'd, then gathered to per-feature space with the
                # mfb FixHistogram reconstruction — which needs GLOBAL
                # totals, hence after the psum (dtype-preserving, so the
                # quantized int32 reconstruction stays exact)
                from ..io.efb import per_feature_hist
                total = hist[0].sum(axis=0)
                hist = per_feature_hist(hist, efb_hist, total[0], total[1])
            return hist, sg, sh
        # the psum moves one [F, B, 2] histogram per call (f32, or int32
        # level-sums under quantized training; [F, B] packed words when
        # the leaf is small enough)
        psum_bytes = self.num_features * B * (2 if packed else 4) * 2
        from ..compile import get_manager
        return instrument_kernel(
            get_manager().jit_entry(
                f"data_parallel/leaf_histogram_c{capacity}"
                + ("_packed" if packed else ""), fn),
            "hist", name="data_parallel/leaf_histogram",
            collective=("hist_psum", psum_bytes, "data"))

    @functools.lru_cache(maxsize=64)
    def _partition_fn_sharded(self, capacity: int):
        mesh = self.mesh
        efb = self._efb_dev

        @jax.jit
        @functools.partial(
            shard_map, mesh=mesh, check_vma=False,
            in_specs=(P("data", None, None), P("data", None), P("data"),
                      P("data"), P(), P(), P(), P(), P(), P()),
            out_specs=(P("data", None), P("data")))
        def fn(bins, perm, start, count, feature, threshold, default_left,
               miss_bin, is_cat, cat_bitset):
            from ..ops.partition import partition_leaf
            new_perm, lc = partition_leaf(
                bins[0], perm[0], start[0], count[0], feature, threshold,
                default_left, miss_bin, is_cat, cat_bitset, capacity,
                efb=efb)
            return new_perm[None], lc[None]
        from ..compile import get_manager
        return instrument_kernel(
            get_manager().jit_entry(
                f"data_parallel/partition_leaf_c{capacity}", fn),
            "partition", name="data_parallel/partition_leaf")

    def _hist_call(self, cap: int, total_count: int, *args):
        """Histogram + psum at the right integer width: under quantized
        training, leaves whose GLOBAL row count keeps every packed
        16-bit lane sum exact ride the halved packed-word collective;
        larger leaves escalate to the unpacked [F, B, 2] int32 psum
        (the per-leaf hist-bits escalation of the reference's
        gradient_discretizer)."""
        packed = False
        if self._qscales is not None:
            from ..obs import active as obs_active
            packed = Q.packed_rows_ok(int(total_count),
                                      self.config.num_grad_quant_bins)
            reg = obs_active()
            if reg is not None:
                if packed:
                    reg.inc("hist.quant_packed_bytes",
                            self.num_features * self.max_num_bin * 4)
                else:
                    reg.inc("hist.quant_overflow_escalations")
        fn = self._hist_fn_sharded(cap, packed)
        if self._qscales is not None and self._hist_takes_scales:
            return fn(*args, *self._qscales)
        return fn(*args)

    # -- grower ---------------------------------------------------------
    def grow(self, grad: jax.Array, hess: jax.Array, perm: jax.Array,
             num_data: int) -> Tree:
        cfg = self.config
        d = self.num_shards
        rps = self.rows_per_shard
        if self._forced_splits is not None:
            log.warning("forcedsplits_filename is not supported by the "
                        "parallel tree learners yet; ignoring")
        # shard-local views of grad/hess/perm. Bagging: each shard's
        # local permutation lists its in-bag rows first, so leaf windows
        # cover exactly the bag (mirrors SetBaggingData on the reference
        # learners); out-of-bag grads are additionally zeroed.
        grad_np = np.asarray(grad)
        hess_np = np.asarray(hess)
        pad = rps * d - len(grad_np)
        if pad:
            grad_np = np.pad(grad_np, (0, pad))
            hess_np = np.pad(hess_np, (0, pad))
        counts0 = self._shard_valid_rows.copy()
        perm_np = np.broadcast_to(np.arange(rps, dtype=np.int32)[None],
                                  (d, rps)).copy()
        if num_data < self.dataset.num_data:
            mask = np.zeros(rps * d, dtype=bool)
            mask[np.asarray(perm[:num_data])] = True
            grad_np = np.where(mask, grad_np, 0.0)
            hess_np = np.where(mask, hess_np, 0.0)
            perm_np, counts0 = shard_bag_permutation(perm, num_data, d, rps)
        self._qscales = None
        raw_g_sh = raw_h_sh = None
        if self._quant:
            # one quantization pass per tree (bag-masked raw grads in,
            # int32 levels out); every sharded histogram and its psum
            # then run in exact level space, and the host keeps leaf
            # sums in dequantized f32 units
            Q.note_requantize(cfg.num_grad_quant_bins)
            key = jax.random.fold_in(
                jax.random.PRNGKey(cfg.objective_seed ^ 0x51A7),
                self._quant_tree_idx)
            self._quant_tree_idx += 1
            qg, qh, gs, hs = Q.quantize_gradients(
                jnp.asarray(grad_np), jnp.asarray(hess_np),
                cfg.num_grad_quant_bins, key, cfg.stochastic_rounding)
            self._qscales = (gs, hs)
            # tpulint: sync-ok(per-tree quant scales, single batched transfer)
            gsh, hsh = jax.device_get((gs, hs))
            self._qscales_host = (float(gsh), float(hsh))
            if cfg.quant_train_renew_leaf:
                raw_g_sh = jax.device_put(
                    jnp.asarray(grad_np.reshape(d, rps)), self._spec_rows)
                raw_h_sh = jax.device_put(
                    jnp.asarray(hess_np.reshape(d, rps)), self._spec_rows)
            g_sh = jax.device_put(qg.reshape(d, rps), self._spec_rows)
            h_sh = jax.device_put(qh.reshape(d, rps), self._spec_rows)
        else:
            g_sh = jax.device_put(jnp.asarray(grad_np.reshape(d, rps)), self._spec_rows)
            h_sh = jax.device_put(jnp.asarray(hess_np.reshape(d, rps)), self._spec_rows)
        perm_sh = jax.device_put(jnp.asarray(perm_np), self._spec_rows)

        tree = Tree(cfg.num_leaves,
                    track_branch_features=bool(self._interaction_sets))
        tree_mask = self._feature_mask_tree()
        rand_thr = self._rand_thresholds()

        starts0 = np.zeros(d, dtype=np.int32)
        cap = next_capacity(int(counts0.max()))
        hist, sg, sh = self._hist_call(
            cap, int(counts0.sum()),
            self.bins_sharded, perm_sh, jnp.asarray(starts0),
            jnp.asarray(counts0), g_sh, h_sh)
        # tpulint: sync-ok(per-tree root stats, single batched transfer)
        sg, sh = map(float, jax.device_get((sg, sh)))
        if self._qscales is not None:
            # int32 level sums -> dequantized f32 leaf totals
            sg *= self._qscales_host[0]
            sh *= self._qscales_host[1]
        root = _Leaf(starts0, counts0, sg, sh, 0.0, 0)
        root.hist = hist
        root.best = self._compute_best_dp(root, tree_mask,
                                          set() if self._interaction_sets else None,
                                          rand_thr)
        leaves: Dict[int, _Leaf] = {0: root}

        for _ in range(cfg.num_leaves - 1):
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
            perm_sh = self._split_leaf_dp(tree, leaves, best_leaf, perm_sh,
                                          g_sh, h_sh, tree_mask, rand_thr)
        self.last_perm = perm_sh
        if self._quant and cfg.quant_train_renew_leaf:
            self._renew_leaf_values_dp(tree, leaves, perm_sh,
                                       raw_g_sh, raw_h_sh)
        return tree

    def _renew_leaf_values_dp(self, tree: Tree, leaves: Dict[int, _Leaf],
                              perm_sh, g_sh, h_sh) -> None:
        """Sharded mirror of SerialTreeGrower._renew_leaf_values: leaf
        outputs refit from the EXACT f32 grad/hess sums after quantized
        growth. One leaf-ordered cumsum per shard; only the [L, D]
        window-boundary prefix values transfer to the host, where the
        cross-shard sums and the output formula run in f64."""
        items = [(lid, lf) for lid, lf in leaves.items()
                 if int(np.sum(lf.count)) > 0]
        if not items:
            return
        cg = jnp.cumsum(jnp.take_along_axis(g_sh, perm_sh, axis=1), axis=1)
        ch = jnp.cumsum(jnp.take_along_axis(h_sh, perm_sh, axis=1), axis=1)
        starts = np.asarray([lf.start for _, lf in items])      # [L, D]
        counts = np.asarray([lf.count for _, lf in items])      # [L, D]
        ends = starts + counts - 1
        los = starts - 1
        dd = jnp.arange(self.num_shards, dtype=jnp.int32)[None, :]
        e_idx = jnp.asarray(np.maximum(ends, 0), jnp.int32)
        lo_idx = jnp.asarray(np.maximum(los, 0), jnp.int32)
        # tpulint: sync-ok(per-tree leaf renewal, already one batched transfer)
        ge, he, gl, hl = jax.device_get(
            (cg[dd, e_idx], ch[dd, e_idx], cg[dd, lo_idx], ch[dd, lo_idx]))
        has = counts > 0
        has_lo = los >= 0
        sum_g = np.sum(np.where(
            has, np.asarray(ge, np.float64) - np.where(has_lo, gl, 0.0),
            0.0), axis=1)
        sum_h = np.sum(np.where(
            has, np.asarray(he, np.float64) - np.where(has_lo, hl, 0.0),
            0.0), axis=1)
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

    def _compute_best_dp(self, leaf: _Leaf, tree_mask, branch_features,
                         rand_thr):
        total = int(np.sum(leaf.count))
        if total < 2 * self.config.min_data_in_leaf \
                or leaf.sum_h < 2 * self.config.min_sum_hessian_in_leaf:
            return None
        fake = _Leaf(0, total, leaf.sum_g, leaf.sum_h, leaf.output, leaf.depth,
                     hist=leaf.hist, cmin=leaf.cmin, cmax=leaf.cmax)
        return super()._compute_best(fake, tree_mask, branch_features, rand_thr)

    def _split_leaf_dp(self, tree: Tree, leaves: Dict[int, _Leaf], lid: int,
                       perm_sh, g_sh, h_sh, tree_mask, rand_thr):
        from ..io.binning import BIN_CATEGORICAL
        leaf = leaves[lid]
        best = leaf.best
        fi = best["feature"]
        mapper = self.dataset.bin_mappers[fi]
        real_feature = self.dataset.real_feature_index[fi]
        is_cat = mapper.bin_type == BIN_CATEGORICAL

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

        cap = next_capacity(int(np.max(leaf.count)))
        new_perm, left_counts = self._partition_fn_sharded(cap)(
            self.bins_sharded, perm_sh, jnp.asarray(leaf.start),
            jnp.asarray(leaf.count), jnp.int32(fi), jnp.int32(thr),
            bool(dl), jnp.int32(mb), bool(is_cat), cat_bitset_dev)
        # tpulint: sync-ok(per-shard partition counts steer the host loop)
        lc = np.asarray(left_counts, dtype=np.int32)
        rc = leaf.count - lc

        lcmin, lcmax, rcmin, rcmax = leaf.cmin, leaf.cmax, leaf.cmin, leaf.cmax
        if self.use_monotone:
            mono = self.dataset.monotone_constraint(fi)
            if mono != 0:
                mid = (best["left_output"] + best["right_output"]) / 2.0
                if mono > 0:
                    lcmax, rcmin = min(lcmax, mid), max(rcmin, mid)
                else:
                    lcmin, rcmax = max(lcmin, mid), min(rcmax, mid)

        left = _Leaf(leaf.start.copy(), lc, best["left_sum_gradient"],
                     best["left_sum_hessian"], best["left_output"],
                     leaf.depth + 1, cmin=lcmin, cmax=lcmax)
        right = _Leaf(leaf.start + lc, rc, best["right_sum_gradient"],
                      best["right_sum_hessian"], best["right_output"],
                      leaf.depth + 1, cmin=rcmin, cmax=rcmax)

        lt, rt = int(lc.sum()), int(rc.sum())
        smaller, larger = (left, right) if lt <= rt else (right, left)
        scap = next_capacity(max(int(np.max(smaller.count)), 1))
        smaller.hist, _, _ = self._hist_call(
            scap, min(lt, rt),
            self.bins_sharded, new_perm, jnp.asarray(smaller.start),
            jnp.asarray(smaller.count), g_sh, h_sh)
        if self.supports_hist_subtraction:
            # exact in int32 level space under quantized training
            larger.hist = leaf.hist - smaller.hist
        else:
            # voting mode: each reduction round selects its own feature
            # subset, so parent/child histograms are not subtractable —
            # compute the larger child directly (its own vote round)
            lcap = next_capacity(max(int(np.max(larger.count)), 1))
            larger.hist, _, _ = self._hist_call(
                lcap, max(lt, rt),
                self.bins_sharded, new_perm, jnp.asarray(larger.start),
                jnp.asarray(larger.count), g_sh, h_sh)
        leaf.hist = None

        branches = None
        if self._interaction_sets:
            branches = {self.dataset.inner_feature_index[f]
                        for f in tree.branch_features[lid]
                        if f in self.dataset.inner_feature_index}
        left.best = self._compute_best_dp(left, tree_mask, branches, rand_thr)
        right.best = self._compute_best_dp(right, tree_mask, branches, rand_thr)
        leaves[lid] = left
        leaves[right_leaf] = right
        return new_perm


class VotingParallelTreeGrower(DataParallelTreeGrower):
    """PV-Tree voting (reference voting_parallel_tree_learner.cpp): each
    shard votes its local top-k features; only features with enough
    votes get their histograms globally reduced.

    With psum already reducing the full histogram in one ICI op, voting
    is expressed as a feature mask applied before the reduction: the
    local top-k is computed from shard-local scans, the vote tally is a
    psum of one-hot feature votes (tiny), and the big histogram psum is
    masked to the ≤2k selected features — the same traffic shape as
    CopyLocalHistogram (:185) + ReduceScatter (:343). Because each
    reduction round selects its own features, parent/child histograms
    are NOT subtractable (supports_hist_subtraction = False).
    """

    supports_hist_subtraction = False
    # the local vote scan evaluates real f32 gains, so the quantized
    # path must pass the per-tree scales into the sharded program
    _hist_takes_scales = True

    @functools.lru_cache(maxsize=64)
    def _hist_fn_sharded(self, capacity: int, packed: bool = False):
        B = self.max_num_bin
        Bg = self.group_max_bin
        efb_hist = self._efb_hist
        mesh = self.mesh
        top_k = self.config.top_k
        meta = self.meta
        cfg = self.split_cfg
        method = H.hist_method(self.config)
        quant = self._quant
        row_specs = (P("data", None, None), P("data", None), P("data"),
                     P("data"), P("data", None), P("data", None))
        in_specs = row_specs + ((P(), P()) if quant else ())

        def reduce_hist(h):
            # the big collective: packed [*, B] words (half bytes) when
            # the leaf's global count keeps 16-bit lane sums exact,
            # else the plain [*, B, 2] (f32, or int32 level) psum
            if packed:
                return Q.packed_hist_to_pairs(
                    jax.lax.psum(Q.pairs_to_packed_hist(h), "data"))
            return jax.lax.psum(h, "data")

        def body(bins, perm, start, count, grad, hess, gs=None, hs=None):
            h = H.leaf_histogram(bins[0], perm[0], start[0], count[0],
                                 grad[0], hess[0], capacity,
                                 Bg if efb_hist is not None else B,
                                 method=method)
            if efb_hist is not None:
                # voting scans LOCAL per-feature histograms; the mfb
                # reconstruction is linear in the group histogram, so
                # reconstructing per shard and psum'ing selected
                # features afterwards equals the global reconstruction
                from ..io.efb import per_feature_hist
                tot = h[0].sum(axis=0)
                h = per_feature_hist(h, efb_hist, tot[0], tot[1])
            # local scan for voting (min_data divided by #machines,
            # reference :62-64)
            local_cfg = S.SplitConfig(
                lambda_l1=cfg.lambda_l1, lambda_l2=cfg.lambda_l2,
                min_data_in_leaf=max(1, cfg.min_data_in_leaf // mesh.shape["data"]),
                min_sum_hessian_in_leaf=cfg.min_sum_hessian_in_leaf / mesh.shape["data"],
                min_gain_to_split=cfg.min_gain_to_split,
                max_delta_step=cfg.max_delta_step, path_smooth=cfg.path_smooth)
            sg = jnp.sum(h[0, :, 0])
            sh_ = jnp.sum(h[0, :, 1])
            if quant:
                # the vote scan runs on the dequantized LOCAL histogram
                # (gains are regularized, so level-space scans would
                # mix units); the collectives below stay integer
                h_scan = S.dequantize_hist(h, gs, hs)
                sg_scan = sg.astype(jnp.float32) * gs
                sh_scan = sh_.astype(jnp.float32) * hs
            else:
                h_scan, sg_scan, sh_scan = h, sg, sh_
            res = S.numerical_split_scan(h_scan, meta, local_cfg, sg_scan,
                                         sh_scan, count[0], 0.0,
                                         -jnp.inf, jnp.inf)
            gains = jnp.where(jnp.isfinite(res["gain"]), res["gain"], -jnp.inf)
            f_total = gains.shape[0]
            k = min(top_k, f_total)
            _, top_idx = jax.lax.top_k(gains, k)
            votes = jnp.zeros(f_total, jnp.int32).at[top_idx].add(1)
            votes = jax.lax.psum(votes, "data")        # tiny: [F] int32
            # global candidates: top 2k features by votes (GlobalVoting,
            # reference :152-183)
            k2 = min(2 * top_k, f_total)
            sg_true = jax.lax.psum(sg, "data")
            sh_true = jax.lax.psum(sh_, "data")
            if k2 >= f_total:
                return reduce_hist(h), sg_true, sh_true
            # the vote tally is replicated after its psum, so every
            # shard computes the SAME selected set; only the selected
            # features' histogram slab rides ICI — [2k, B, 2] instead of
            # [F, B, 2], the PV-Tree saving (CopyLocalHistogram :185 +
            # ReduceScatter of selected buffers :343)
            _, selected = jax.lax.top_k(votes, k2)
            h_sel = reduce_hist(h[selected])           # [2k, B, 2]
            hist_global = jnp.zeros_like(h).at[selected].set(h_sel)
            # non-selected features keep zero histograms; the replicated
            # scan will simply not pick them
            return hist_global, sg_true, sh_true

        if quant:
            def fn_args(bins, perm, start, count, grad, hess, gs, hs):
                return body(bins, perm, start, count, grad, hess, gs, hs)
        else:
            def fn_args(bins, perm, start, count, grad, hess):
                return body(bins, perm, start, count, grad, hess)
        fn = jax.jit(functools.partial(
            shard_map, mesh=mesh, check_vma=False,
            in_specs=in_specs, out_specs=P())(fn_args))
        # ICI traffic per call: the [F] vote tally + the selected
        # [<=2k, B, 2] histogram slab (full [F, B, 2] when 2k >= F;
        # halved when packed)
        k2_est = min(2 * top_k, self.num_features)
        from ..compile import get_manager
        return instrument_kernel(
            get_manager().jit_entry(
                f"voting_parallel/leaf_histogram_c{capacity}"
                + ("_packed" if packed else ""), fn),
            "hist", name="voting_parallel/leaf_histogram",
            collective=("voting_psum",
                        self.num_features * 4
                        + k2_est * B * (1 if packed else 2) * 4,
                        "data"))


class FeatureParallelTreeGrower(SerialTreeGrower):
    """Feature-sharded learner (reference
    feature_parallel_tree_learner.cpp): every chip holds all rows; each
    evaluates splits for its feature shard; best split = argmax over the
    feature axis — realized by sharding the histogram scan over the mesh
    with jit-with-sharding (XLA inserts the tiny allreduce-max for the
    final argmax; no histogram traffic at all, like the reference which
    only syncs SplitInfo)."""

    def __init__(self, dataset: BinnedDataset, config: Config,
                 mesh: Optional[Mesh] = None) -> None:
        super().__init__(dataset, config)
        self.mesh = mesh if mesh is not None else build_mesh(config)
        # shard the histogram scan over features: hist [F, B, 2] with F
        # sharded. The per-feature scans are independent, so simply
        # constraining the sharding of the hist input distributes the
        # scan; everything else (gather, partition) is replicated.
        self._hist_sharding = NamedSharding(self.mesh, P("data", None, None))

    def _split_packed(self, hist, *args):
        hist = jax.lax.with_sharding_constraint(hist, self._hist_sharding)
        return super()._split_packed(hist, *args)


class FusedDataParallelGrower(FusedSerialGrower):
    """Fused single-dispatch iterations under `shard_map` — the
    data-parallel learner for the persistent training path.

    Reference analogue: data_parallel_tree_learner.cpp, but instead of
    a ReduceScatter of histogram buffers per LEAF over sockets
    (:169), the whole `lax.while_loop` tree build runs per shard with
    one `psum` of the smaller child's histogram (and of the split
    counts) per split riding ICI. Rows are sharded contiguously over
    the 1-D "data" mesh axis; each shard partitions only its own rows
    and carries its own leaf windows, while split decisions are made
    on the psum'd (global) histograms — bitwise identical on every
    shard, so the resulting tree is replicated by construction (the
    reference's SyncUpGlobalBestSplit, :240, becomes a no-op).
    """

    is_multichip = True

    def __init__(self, dataset: BinnedDataset, config: Config,
                 objective=None, mesh: Optional[Mesh] = None) -> None:
        self.mesh = mesh if mesh is not None else build_mesh(config)
        self.num_shards = int(self.mesh.shape["data"])
        self.global_rows = dataset.num_data
        shard_rows = -(-dataset.num_data // self.num_shards)
        super().__init__(dataset, config, objective,
                         num_rows_override=shard_rows)
        self.shard_rows = shard_rows
        self.psum_axis = "data"
        n = self.global_rows
        counts = [max(0, min(n - d * shard_rows, shard_rows))
                  for d in range(self.num_shards)]
        self._n_per_shard = jax.device_put(
            jnp.asarray(counts, jnp.int32),
            NamedSharding(self.mesh, P("data")))
        self._iter_mc_entry = None
        self._iter_mc_jit = None
        self._grow_mc_tree_jit = None
        # per-tree ICI estimate for the host-side collective accounting
        # (benchmarks/harness/work_dp.py's formula at a full tree): the
        # root's and each split's smaller child's [F, B, 2] f32
        # histogram plus its i32 count, which a ring allreduce over D
        # chips carries 2 (D - 1) / D times over every chip's links
        D = self.num_shards
        self._tree_psum_bytes = (
            self.num_leaves
            * (self.num_features * self.max_num_bin * 2 * 4 + 4)
            * 2 * (D - 1) // D)

    def _mc_signature(self):
        """(sig, shareable) for the top-level shard_map entries. The
        per-shard fused grower skips manager registration (its programs
        mutate post-init), but THESE entries are built after that
        mutation settles, so two MC growers with equal signatures trace
        identical sharded programs and can share one executable. The
        bodies close over dataset-derived tables, so the dataset trace
        signature joins the fused compile signature, as on the serial
        path."""
        ds_sig, shareable = self.dataset.trace_signature()
        sig = self._compile_signature()
        sig["ds"] = ds_sig
        sig["mesh"] = (self.num_shards, self.shard_rows, self.global_rows)
        return sig, shareable

    # -- sharded state construction ------------------------------------
    def _pack_codes_per_device(self, sharding, shape):
        """([(device, shard)], [shard's [code_planes, R] codes on its
        device]) for a lane-sharded array of ``shape``: every shard is
        packed on the HOST from its slice of the bin matrix and uploaded
        to the device that owns it in its final form, so no device ever
        holds more than its own share and no device program is built."""
        from ..ops import plane
        sr, Ly = self.shard_rows, self.layout
        bins = np.asarray(self.dataset.bins)
        owned = [(dev, idx[1].start // Ly.num_lanes) for dev, idx in
                 sharding.addressable_devices_indices_map(shape).items()]
        # uploads are asynchronous: shard d+1 is packed while shard d is
        # on its way, and the stage is closed by the one block
        with span("fused/pack_codes", stage="state/pack_codes"):
            packed = [plane.build_codes_planes(bins[d * sr:(d + 1) * sr],
                                               Ly, device=dev)
                      for dev, d in owned]
            # tpulint: sync-ok(set-up, once per state build, after every device's upload is enqueued)
            jax.block_until_ready(packed)
        return owned, packed

    def init_persistent_state(self, score_vec) -> jax.Array:
        """[P, D * num_lanes] planar state, lanes sharded over "data".
        Every shard is packed ON the device that owns it from host
        slices, so no device ever holds more than its own share (the
        global state assembled on the default device first is 4x one
        chip's share on a four-chip host)."""
        assert self.persistent_capable
        from ..ops import plane
        D, sr, Ly = self.num_shards, self.shard_rows, self.layout
        aux_label, aux_weight = self.objective.persistent_aux()
        n = self.global_rows
        # one device->host copy each, sliced per shard below
        label, score, weight = (
            None if v is None else np.asarray(v, np.float32)
            for v in (aux_label, score_vec, aux_weight))

        def host_rows(v, d):
            """Shard d's slice of a global [n] host vector; build_data
            zero-pads it to the lane count."""
            return jnp.asarray(v[d * sr:(d + 1) * sr])

        def build_shard(d, cp):
            # pad rows alias row id n -> dropped by the sync scatter
            rowid = np.minimum(np.arange(d * sr, (d + 1) * sr), n)
            rowid = np.pad(rowid, (0, Ly.num_lanes - sr),
                           constant_values=n).astype(np.int32)
            zero = jnp.zeros(sr, jnp.float32)
            return plane.build_data(
                Ly, cp, zero, zero, rowid=jnp.asarray(rowid),
                label=host_rows(label, d), score=host_rows(score, d),
                weight=None if weight is None else host_rows(weight, d))

        shape = (Ly.num_planes, D * Ly.num_lanes)
        sharding = NamedSharding(self.mesh, P(None, "data"))
        owned, packed = self._pack_codes_per_device(sharding, shape)
        shards = []
        with span("fused/build_data", stage="state/build_data"):
            for (dev, d), cp in zip(owned, packed):
                with jax.default_device(dev):
                    shards.append(build_shard(d, cp))
        return jax.make_array_from_single_device_arrays(
            shape, sharding, shards)

    # -- sharded iteration ---------------------------------------------
    # NOTE on quantized training: the in-graph per-split child-histogram
    # psum stays at the unpacked [F, B, 2] int32 width — leaf counts are
    # TRACED inside the while_loop, so the packed/unpacked choice cannot
    # branch per leaf the way the host-loop learner's _hist_call does.
    # The quantization scales pmax across shards before packing (see
    # FusedSerialGrower._train_iter), so the int32 sums stay coherent.
    def train_iter_persistent(self, data, shrinkage, bias):
        quant = self._quant
        if self._iter_mc_jit is None:
            # the shard's body carries the serial entry's name, so the
            # XLA module (and every op's scope path) reads
            # `_entry_train_iter` on one chip and on four
            if quant:
                def _entry_train_iter(data_l, nvalid_l, mask_, shr, b, key):
                    return self._train_iter(data_l, mask_, shr, b,
                                            n_valid=nvalid_l[0], key=key)
                in_specs = (P(None, "data"), P("data"), P(), P(), P(), P())
            else:
                def _entry_train_iter(data_l, nvalid_l, mask_, shr, b):
                    return self._train_iter(data_l, mask_, shr, b,
                                            n_valid=nvalid_l[0])
                in_specs = (P(None, "data"), P("data"), P(), P(), P())
            f = functools.partial(
                shard_map, mesh=self.mesh, check_vma=False,
                in_specs=in_specs,
                out_specs=(P(None, "data"), P()))(_entry_train_iter)
            from ..compile import get_manager
            sig, ok = self._mc_signature()
            self._iter_mc_entry = get_manager().shared_entry(
                "mc/train_iter", sig,
                lambda: jax.jit(f, donate_argnums=0),  # tpulint: jit-ok(inside a shared_entry builder; the manager dispatches this jit)
                donate_argnums=(0,), store=ok, profiled=True)
            # the span of the serial dispatch: one name for one role
            self._iter_mc_jit = instrument_kernel(
                self._iter_mc_entry, "fused", name="fused/train_iter")
        args = (data, self._n_per_shard, self.feature_masks_for_tree(),
                jnp.float32(shrinkage), jnp.float32(bias))
        if quant:
            args = args + (self._next_quant_key(),)
        with collective_span("fused_iter_psum", self._tree_psum_bytes,
                             axis="data"):
            return self._iter_mc_jit(*args)

    def _sync_scores(self, data):
        """[n] f32 raw scores in row order: every shard scatters its own
        rows into its own [shard_rows] slice, and one all-gather lines
        the slices up (shard d owns rows [d*sr, (d+1)*sr)), where an
        [n]-sized scatter and psum on every chip moved 2x the bytes and
        held two [n] arrays a chip. The collective sits under a scope of
        its own segment so that a profile keeps a host consumer's sync
        apart from the iteration's allreduces."""
        from ..ops import plane
        Ly = self.layout
        n, sr = self.global_rows, self.shard_rows

        def body(data_l):
            with jax.named_scope("lgbm.score_sync"):
                rowids = data_l[Ly.rowid]
                local = rowids - jax.lax.axis_index("data") * sr
                # pad lanes carry row id n: out of every shard's slice
                local = jnp.where(rowids >= n, sr, local)
                out = jnp.zeros(sr, jnp.float32).at[local].set(
                    plane.get_f32(data_l, Ly.score), mode="drop",
                    unique_indices=True)
                with jax.named_scope("lgbm.allreduce"):
                    return jax.lax.all_gather(out, "data", tiled=True)[:n]

        with collective_span("scores_allgather", n * 4, axis="data"):
            return functools.partial(
                shard_map, mesh=self.mesh, check_vma=False,
                in_specs=(P(None, "data"),), out_specs=P())(body)(data)

    # -- sharded per-tree path (bagging / multiclass / custom fobj) -----
    def _codes_planes_sharded(self):
        """[code_planes, D * num_lanes] resident planar codes, lanes
        sharded over "data" (same ownership as the persistent state:
        shard d owns rows [d*sr, (d+1)*sr))."""
        if getattr(self, "_cp_sh", None) is None:
            Ly = self.layout
            shape = (Ly.code_planes, self.num_shards * Ly.num_lanes)
            sharding = NamedSharding(self.mesh, P(None, "data"))
            _, packed = self._pack_codes_per_device(sharding, shape)
            self._cp_sh = jax.make_array_from_single_device_arrays(
                shape, sharding, packed)
        return self._cp_sh

    def _grow_mc_jit_build(self):
        rows = P("data", None)

        def grow(cp, in_bag, cnt, g, h, mask):
            def body(cp_l, flag_l, cnt_l, g_l, h_l, mask_):
                # the serial per-tree program on the shard's own rows:
                # local planes, the shard's slice of the bag flag (its
                # own compaction pass counts its bag), psum'd histograms
                ta, leaf = self._grow_tree(
                    cp_l, g_l[0], h_l[0],
                    None if flag_l is None else flag_l[0], cnt_l[0], mask_)
                return ta, leaf[:self.shard_rows][None]

            # in_bag None (no row left out) is an empty subtree: its
            # spec applies to nothing
            return functools.partial(
                shard_map, mesh=self.mesh, check_vma=False,
                in_specs=(P(None, "data"), rows, P("data"), rows, rows, P()),
                out_specs=(P(), rows))(body)(cp, in_bag, cnt, g, h, mask)

        from ..compile import get_manager
        sig, ok = self._mc_signature()
        return get_manager().shared_entry(
            "mc/grow_tree", sig,
            lambda: jax.jit(grow),  # tpulint: jit-ok(inside a shared_entry builder; the manager dispatches this jit)
            store=ok)

    def grow_device(self, grad, hess, in_bag=None,
                    compute_score_update=True):
        """Sharded per-tree growth (reference
        data_parallel_tree_learner.cpp covers every config through one
        network layer; here every config runs the same while_loop
        program per shard with psum'd histograms). ``in_bag`` as the
        serial grower's: every shard gets its rows' slice of the flag,
        with no host pass over the bag."""
        D, sr, n = self.num_shards, self.shard_rows, self.global_rows
        spec_rows = NamedSharding(self.mesh, P("data", None))

        def over_shards(v):
            """[n] -> [D, sr] over the mesh, zero (False) in the pad."""
            v = jnp.pad(v, (0, D * sr - v.shape[0]))
            return jax.device_put(v.reshape(D, sr), spec_rows)

        if self._grow_mc_tree_jit is None:
            # the span of the serial per-tree dispatch
            self._grow_mc_tree_jit = instrument_kernel(
                self._grow_mc_jit_build(), "fused", name="fused/grow_tree")
        self._count_tree_layout(in_bag)
        with collective_span("fused_tree_psum", self._tree_psum_bytes,
                             axis="data"):
            ta, leaf = self._grow_mc_tree_jit(
                self._codes_planes_sharded(),
                None if in_bag is None else over_shards(in_bag),
                self._n_per_shard,
                over_shards(jnp.asarray(grad, jnp.float32)),
                over_shards(jnp.asarray(hess, jnp.float32)),
                self.feature_masks_for_tree())
        leaf_of_row = leaf.reshape(-1)[:n] if compute_score_update else None
        return ta, leaf_of_row


def create_parallel_learner(kind: str, dataset: BinnedDataset,
                            config: Config, mesh: Optional[Mesh] = None):
    """reference TreeLearner::CreateTreeLearner (tree_learner.h:99)."""
    if kind == "data":
        return DataParallelTreeGrower(dataset, config, mesh)
    if kind == "voting":
        return VotingParallelTreeGrower(dataset, config, mesh)
    if kind == "feature":
        return FeatureParallelTreeGrower(dataset, config, mesh)
    log.fatal("Unknown parallel tree learner %s", kind)
