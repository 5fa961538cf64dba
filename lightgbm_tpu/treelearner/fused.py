"""Fully on-device leaf-wise tree growth — one dispatch per iteration.

This is the TPU-critical redesign of the training hot path. The
reference's per-split control flow (serial_tree_learner.cpp:152-202)
costs it nothing on CPU, and its GPU learner tolerates a PCIe sync per
leaf (gpu_tree_learner.cpp). Here the num_leaves-1 split steps of a
tree run inside one compiled program, with no host round trip between
them:

- The whole split loop is a `lax.while_loop`; per-leaf state (ranges,
  sums, outputs, best-split records, the histogram pool) lives in
  fixed-size [num_leaves] device arrays — the HistogramPool
  (feature_histogram.hpp:1061) becomes a dense [L, F, B, 2] pool.
- Training rows live in the PLANAR [P, R] int32 layout of ops/plane.py
  (bin-code byte planes + grad/hess/label/score/row-id planes,
  lane-major). DataPartition::Split (data_partition.hpp:72) is the
  Pallas carry-stream kernel: in-register block compaction + aligned
  DMA writes — no per-row gather/scatter/sort anywhere in the loop,
  which removed the ~37-140 ns/row access tolls that dominated every
  row-major formulation (docs/PERF_NOTES.md).
- Leaf histograms use `lax.switch` over capacity buckets; the smaller
  child is histogrammed at its own bucket, the larger child is
  histogram subtraction, as in the reference (:396-404).
- In the persistent mode (no bagging, pointwise objective, one tree
  per iteration) the score/label/row-id ride inside the planar state
  ACROSS iterations in leaf-permuted order: gradients, tree growth,
  and the score update all happen in one program with zero [N]-sized
  scatters; scores are scattered back to row order only when a host
  consumer asks (GBDT.get_training_score).

Coverage: numerical AND categorical features (one-vs-rest + sorted
many-vs-many with the left-set bitset materialized on device and
routed through the partition kernel's prefetched scalars), serial and
sharded-data-parallel learners, any objective without leaf renewal,
bagging via a per-row bag flag (_compact_bag), per-tree feature_fraction,
max_depth, basic monotone constraints, L1/L2/max_delta_step/path
smoothing, forced splits (BFS phase before the best-first loop) and
feature_fraction_bynode (per-scan-event masks). Interaction
constraints, extra_trees, CEGB and renew-tree-output objectives fall
back to the host-loop grower (treelearner/serial.py) — every rejection
is named by fused_reject_reason and warned about loudly.
"""
from __future__ import annotations

import contextlib
from typing import Dict, NamedTuple, Optional

import numpy as np
import jax
import jax.numpy as jnp

from ..config import Config
from ..io.dataset import BinnedDataset
from ..io.binning import BIN_CATEGORICAL
from ..models.tree import K_CATEGORICAL_MASK, K_DEFAULT_LEFT_MASK, Tree
from ..obs import instrument_kernel, span
from ..ops import histogram as H
from ..ops import plane
from ..ops import quantize as Q
from ..ops import split as S
from ..utils import log

NEG_INF = jnp.float32(-jnp.inf)
# growth factor of the REF path's capacity ladder: a factor-8 ladder
# compiled a third faster but ran 12 % slower in skipped-step overhead
# (docs/PERF_NOTES.md)
LADDER_FACTOR = 4


def bag_active(config: Config) -> bool:
    """Whether row sampling re-permutes rows away from score order —
    shared by fused_reject_reason and the grower's
    _score_from_partition so the two can never disagree (a renew
    objective accepted here but non-persistent there would silently
    skip its leaf refit)."""
    return ((config.bagging_freq > 0
             and (config.bagging_fraction < 1.0
                  or config.pos_bagging_fraction < 1.0
                  or config.neg_bagging_fraction < 1.0))
            or config.boosting in ("goss", "rf"))


def fused_reject_reason(config: Config, dataset: BinnedDataset,
                        objective) -> Optional[str]:
    """Why a config cannot run the fused single-dispatch path (None =
    eligible). Every remaining rejection names the responsible option so
    the driver can warn LOUDLY about the ~10x host-loop perf cliff."""
    if not config.tpu_fused:
        return "tpu_fused=false"
    if config.tree_learner != "serial":
        return f"tree_learner={config.tree_learner}"
    if max((m.num_bin for m in dataset.bin_mappers
            if m.bin_type == BIN_CATEGORICAL), default=0) > 256:
        # categorical routing carries an 8-word (256-bin) bitset through
        # the partition kernel's prefetched scalars
        return "a categorical feature with > 256 bins (max_bin)"
    if config.forcedsplits_filename:
        # the forced phase reads parent histograms from the pool
        pool_mb = config.histogram_pool_size
        need = (max(config.num_leaves, 2) * dataset.num_features
                * max((m.num_bin for m in dataset.bin_mappers), default=2)
                * 2 * 4)
        if not (pool_mb <= 0 or need <= pool_mb * 1024 * 1024):
            return ("forcedsplits_filename with a histogram_pool_size "
                    "too small for the dense pool")
    if config.interaction_constraints:
        return "interaction_constraints"
    if config.extra_trees:
        return "extra_trees"
    if (config.cegb_tradeoff != 1.0 or config.cegb_penalty_split > 0
            or config.cegb_penalty_feature_coupled
            or config.cegb_penalty_feature_lazy):
        return "cegb_* (cost-effective gradient boosting)"
    if config.monotone_constraints and (
            config.monotone_constraints_method != "basic"
            or config.monotone_penalty > 0):
        # intermediate mode re-searches arbitrary leaves after a split —
        # host-loop territory (treelearner/monotone.py)
        return ("monotone_constraints_method=intermediate or "
                "monotone_penalty > 0")
    if config.use_quantized_grad:
        # the quantized pass rounds persistent_grads in-program and
        # renews leaf values from the raw f32 score/label planes — both
        # live only on the persistent path. Per-tree fused configs
        # (bagging/GOSS/RF/DART, multi-class) take the host-loop serial
        # learner, which quantizes per tree on its own.
        persist = (objective is not None
                   and getattr(objective, "persistent_aux", None) is not None
                   and objective.persistent_aux() is not None
                   and objective.num_tree_per_iteration == 1)
        if not persist or config.boosting != "gbdt" or bag_active(config):
            return ("use_quantized_grad outside the persistent path "
                    "(bagging/GOSS/RF/DART or a non-pointwise objective)")
    if objective is not None and objective.is_renew_tree_output:
        # the leaf refit runs in-program via _renew_leaf_outputs, which
        # needs the persistent path's label/score planes — reject
        # configs that would take the per-tree fused path instead
        # (bagging/GOSS/RF/DART re-permute rows away from score order)
        if (objective.persistent_renew_spec() is None
                or config.boosting != "gbdt" or bag_active(config)):
            return (f"objective={objective.name} (renew-tree-output leaf "
                    "refit outside the persistent path)")
    if dataset.num_features == 0:
        return "dataset has no usable features"
    return None


def fused_supported(config: Config, dataset: BinnedDataset,
                    objective) -> bool:
    """Static eligibility check for the fused path."""
    return fused_reject_reason(config, dataset, objective) is None


class FusedTreeState(NamedTuple):
    """Loop-carried device state; [L] = num_leaves slots."""
    data: jax.Array            # [P, R] planar training rows
    n_leaves: jax.Array        # scalar i32
    leaf_start: jax.Array      # [L] shard-local window starts
    leaf_count: jax.Array      # [L] shard-local window lengths
    leaf_count_g: jax.Array    # [L] GLOBAL row counts (== local 1-chip)
    leaf_sum_g: jax.Array      # [L]
    leaf_sum_h: jax.Array      # [L]
    leaf_output: jax.Array     # [L]
    leaf_depth: jax.Array      # [L]
    leaf_parent: jax.Array     # [L]
    leaf_cmin: jax.Array       # [L] monotone lower bound
    leaf_cmax: jax.Array       # [L]
    # per-leaf best split record
    best_gain: jax.Array       # [L] (-inf = unsplittable)
    best_feature: jax.Array    # [L]
    best_thr: jax.Array        # [L]
    best_dl: jax.Array         # [L] bool
    best_lg: jax.Array         # [L]
    best_lh: jax.Array         # [L]
    best_lcnt: jax.Array       # [L]
    best_lout: jax.Array       # [L]
    best_rg: jax.Array         # [L]
    best_rh: jax.Array         # [L]
    best_rcnt: jax.Array       # [L]
    best_rout: jax.Array       # [L]
    best_cat: jax.Array        # [L] bool — categorical split
    best_bits: jax.Array       # [L, 8] left-category bin bitset
    hist_pool: jax.Array       # [L, F, B, 2]
    # tree under construction (internal nodes [L-1])
    t_feature: jax.Array
    t_thr: jax.Array
    t_dl: jax.Array
    t_left: jax.Array
    t_right: jax.Array
    t_gain: jax.Array
    t_ivalue: jax.Array
    t_iweight: jax.Array
    t_icount: jax.Array
    t_cat: jax.Array           # [L-1] bool
    t_bits: jax.Array          # [L-1, 8]


class FusedSerialGrower:
    """Builds and owns the single-dispatch training-iteration program."""

    is_multichip = False
    # the code planes are packed on the host (plane.pack_codes_host) and
    # uploaded in their final form; execution_plan() says so
    codes_pack = "host"

    def __init__(self, dataset: BinnedDataset, config: Config,
                 objective=None, num_rows_override=None,
                 num_rows_bucket=None) -> None:
        self.dataset = dataset
        self._num_rows_override = num_rows_override
        self.config = config
        self.objective = objective
        # HBM budgeting: the row-major bin matrix never goes to the
        # device (13.2M x 500 groups = 6.6 GB; at G < 128 the TPU pads
        # every row to a 128-lane tile): the code planes are packed on
        # the host (plane.pack_codes_host) and uploaded in their final
        # form. Row sampling compacts and traverses the resident code
        # planes instead (_grow_tree)
        self.num_features = dataset.num_features
        mappers = dataset.bin_mappers
        self.max_num_bin = max((m.num_bin for m in mappers), default=2)
        self.num_leaves = max(config.num_leaves, 2)
        monotone = [dataset.monotone_constraint(i)
                    for i in range(self.num_features)]
        self.use_monotone = any(m != 0 for m in monotone)
        self.any_categorical = any(m.bin_type == BIN_CATEGORICAL
                                   for m in mappers)
        penalty = list(config.feature_contri) + \
            [1.0] * (self.num_features - len(config.feature_contri))
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
            max_cat_threshold=config.max_cat_threshold,
            cat_l2=config.cat_l2, cat_smooth=config.cat_smooth,
            max_cat_to_onehot=config.max_cat_to_onehot,
            min_data_per_group=config.min_data_per_group)
        self.feature_miss_bin = jnp.asarray([
            (m.num_bin - 1 if m.missing_type == 2 else
             (m.default_bin if m.missing_type == 1 else -1))
            for m in mappers], dtype=jnp.int32)
        # EFB bundle views (None on dense/trivial datasets)
        self._efb_dev = dataset.device_bundle_tables()
        self._efb_hist = dataset.device_hist_tables()
        self._tables_cache = None
        self.group_max_bin = dataset.group_max_bins
        # backend dispatch: ops/histogram.hist_method is the ONE shared
        # precision/layout choice for every learner; partition follows
        # suit (the two-stream kernel, lowered to the single-scratch
        # one by the footprint test below). The dataset argument lets
        # the occupancy-driven dispatcher pick the row-wise multival
        # layout for wide-sparse shapes (ops/multival.py).
        self._hist_method = H.hist_method(config, dataset)
        self._part_method = ("pallas2" if self._hist_method is not None
                             else "ref")
        # Mosaic lowers on a TPU only. The dispatch above never selects
        # a kernel elsewhere; tests that force it (H._use_tpu patched)
        # run the same kernels through the Pallas interpreter.
        self._interpret = jax.default_backend() != "tpu"
        # quantized-gradient training (ops/quantize.py): the persistent
        # iteration quantizes grads in-program, the grad plane carries
        # PACKED (qg << 16 | qh) words bitcast through the f32 lanes,
        # and the hist pool holds exact int32 level-sums. Host-side
        # per-iteration counter drives the stochastic-rounding keys.
        self._quant = bool(config.use_quantized_grad)
        self._quant_iter = 0
        self._quant_base_key = (
            jax.random.PRNGKey(config.objective_seed ^ 0x51A7)
            if self._quant else None)

        # planar layout: label/score/weight planes only when the
        # objective can run the persistent in-program loop. Codes pack
        # at 4 bits when every (bundle) column fits 16 bins — the
        # reference's DenseBin IS_4BIT mode (dense_bin.hpp:17-21),
        # halving code-plane HBM footprint and partition bandwidth.
        self._num_cols = int(dataset.bins.shape[1])
        group_bins = (dataset.group_max_bins
                      if dataset.device_hist_tables() is not None
                      else self.max_num_bin)
        if group_bins <= 16:
            self._code_bits = 4
        else:
            self._code_bits = 8 * int(
                np.dtype(dataset.bins.dtype).itemsize)
        n_actual = (dataset.num_data if num_rows_override is None
                    else num_rows_override)
        # canonical row bucketing (compile/signature.py): the layout is
        # sized to the bucket so every row-shaped executable is shared
        # across same-bucket datasets; the real row count rides through
        # the programs as the traced n_valid / bag-count argument and
        # pad lanes stay outside every window
        n = n_actual if num_rows_bucket is None \
            else max(int(num_rows_bucket), n_actual)
        self.actual_rows = n_actual
        persist = (objective is not None
                   and getattr(objective, "persistent_aux", None) is not None
                   and objective.persistent_aux() is not None
                   and objective.num_tree_per_iteration == 1)
        has_w = persist and objective.persistent_aux()[1] is not None

        # row-wise multival layout (ops/multival.py): the dataset's
        # present (group, bin) codes are packed once into [K, N] slot
        # planes that ride the planar state (make_layout mv_planes), so
        # the partition kernels keep them row-aligned for free and the
        # histogram pass reads K*4 bytes/row instead of G code bytes
        self._mv_layout = None
        self._mv_total_bins = 0
        self._mv_dev = None
        self._mv_tables = None
        mv_planes = 0
        if self._hist_method == "multival_pallas":
            from ..ops import multival as MV
            occ = dataset.occupancy
            if dataset.bundles is not None:
                gnb = dataset.bundles.group_num_bins
            else:
                gnb = np.asarray([m.num_bin for m in mappers], np.int32)
            mv_codes, mv_layout = MV.build_rowwise_codes(
                dataset.bins, gnb, occ.default_code)
            self._mv_layout = mv_layout
            self._mv_total_bins = mv_layout.total_bins
            self._mv_dev = jnp.asarray(np.ascontiguousarray(mv_codes.T))
            self._mv_tables = MV.group_tables(gnb, occ.default_code)
            mv_planes = mv_layout.row_capacity   # a multiple of 8

        def mk_layout(tile):
            return plane.make_layout(
                self._num_cols, self._code_bits, n,
                with_label=persist, with_score=persist, with_weight=has_w,
                tile=tile, mv_planes=mv_planes)

        self.layout = mk_layout(plane.DEF_TILE)
        # scoped-VMEM budgeting: every partition staging buffer spans
        # the full plane count P, so wide-EFB states (hundreds of code
        # planes) overflow the 16 MB scoped VMEM at the default tile —
        # shrink the lane tile until even the v1 kernel fits
        while (self.layout.tile > 512
               and plane.partition_vmem_bytes(self.layout, "pallas")
               > plane.PART_VMEM_BUDGET):
            t = self.layout.tile // 2
            log.info("partition VMEM at P=%d exceeds budget: shrinking "
                     "lane tile to %d", self.layout.num_planes, t)
            self.layout = mk_layout(t)
        self.persistent_capable = persist
        self._codes_planes_dev = None   # built lazily
        # wide-EFB HBM budgeting: the v2 partition kernel's scratch is
        # TWO window regions (L and R streams); when the planar state
        # itself is multi-GB, v1's single-region scratch keeps
        # state+scratch at 2x instead of 3x (the Allstate shape:
        # ~60 code planes x 13.2M lanes). v2 also holds 3x the staging
        # VMEM, so wide-plane states take v1 for the scoped limit too.
        if self._part_method == "pallas2":
            state_gb = (self.layout.num_planes * self.layout.num_lanes
                        * 4 / 1e9)
            v2_vmem = plane.partition_vmem_bytes(self.layout, "pallas2")
            if state_gb > 2.5 or v2_vmem > plane.PART_VMEM_BUDGET:
                self._part_method = "pallas"
                log.info("planar state %.1f GB / v2 scratch %.1f MB: "
                         "selecting the single-scratch partition kernel",
                         state_gb, v2_vmem / 1e6)

        # histogram_pool_size (MB; <=0 unlimited — reference
        # feature_histogram.hpp:1061 HistogramPool): when the dense
        # [L, F, B, 2] pool would not fit, run pool-less — both
        # children's histograms are computed directly (no subtraction),
        # nothing is cached, memory is O(F*B) instead of O(L*F*B)
        pool_mb = config.histogram_pool_size
        need = (self.num_leaves * self.num_features
                * self.max_num_bin * 2 * 4)
        self._use_hist_pool = pool_mb <= 0 or need <= pool_mb * 1024 * 1024
        if not self._use_hist_pool:
            log.info("histogram pool (%.0f MB) exceeds histogram_pool_size"
                     "=%.0f MB: disabling histogram subtraction",
                     need / 1e6, pool_mb)

        # user-forced splits: BFS schedule precomputed host-side
        # (leaf slot / inner feature / threshold bin per forced split);
        # the slot ids replay exactly the fused state's deterministic
        # slot assignment (split leaf keeps its slot, right child takes
        # slot n_leaves). Reference: ForceSplits,
        # serial_tree_learner.cpp:427
        self._forced_sched = None
        self._forced_sig = None
        if config.forcedsplits_filename:
            from .serial import _load_forced_splits
            forced = _load_forced_splits(config.forcedsplits_filename)
            sched = []
            if forced is not None:
                queue = [(forced, 0)]
                nl = 1
                while queue and nl < self.num_leaves:
                    node, slot = queue.pop(0)
                    rf = node.get("feature")
                    if rf is None:
                        continue
                    inner = dataset.inner_feature_index.get(int(rf))
                    if inner is None:
                        log.warning("Forced split on unused feature %s "
                                    "ignored", rf)
                        continue
                    m = mappers[inner]
                    tb = int(m.value_to_bin(float(node["threshold"])))
                    tb = max(0, min(tb, m.num_bin - 2))
                    sched.append((slot, inner, tb))
                    right_slot = nl
                    nl += 1
                    if isinstance(node.get("left"), dict):
                        queue.append((node["left"], slot))
                    if isinstance(node.get("right"), dict):
                        queue.append((node["right"], right_slot))
            if sched:
                arr = np.asarray(sched, np.int32)
                self._forced_sched = (jnp.asarray(arr[:, 0]),
                                      jnp.asarray(arr[:, 1]),
                                      jnp.asarray(arr[:, 2]))
                # forced splits are closed-over device constants: their
                # host values must refine the compile signature
                self._forced_sig = arr.tolist()

        # score updates can reuse the partition's leaf assignment only
        # when every scored row is in-bag (no bagging/GOSS/RF); with
        # bagging the out-of-bag rows are never partitioned and the
        # fallback is the tree re-traversal
        self._score_from_partition = not bag_active(config)

        # multi-chip: name of the mesh axis to psum histograms/counts
        # over (set by the data-parallel wrapper; None on one chip)
        self.psum_axis = None
        self._col_rng = np.random.RandomState(config.feature_fraction_seed)
        # capacity ladder for the REF-path lax.switch branches (the
        # XLA-sliced partition/histogram fallbacks need a static window
        # width). The pallas paths no longer ladder: their block sweeps
        # ride a dynamic grid dimension (ops/plane.py / ops/histogram.py
        # cap=None), so ONE lowered kernel serves every leaf size, the
        # while-body HLO holds one copy of each kernel instead of one
        # per ladder rung, and no step is ever launched past
        # the leaf window (the dynamic sweep subsumes the old
        # skipped-step cost model). Tile / row-block lengths are fixed
        # at the top-capacity choice — per-step overhead (~4 us) still
        # amortizes, small leaves just read one partially-valid block.
        tile = self.layout.tile
        top = self.layout.num_lanes - self.layout.max_tile
        from ..ops.partition import capacity_ladder
        self._caps = capacity_ladder(top, tile * 4, LADDER_FACTOR)
        self._dyn_tile = self._branch_tile(top)
        self._dyn_hist_rb = self._branch_hist_rb(top)
        # jit entry points go through the AOT compile manager
        # (lightgbm_tpu/compile): same-signature growers share one
        # executable, executables persist on disk, and warmup threads
        # can compile them ahead of the first iteration. The sharded
        # per-shard growers (num_rows_override set) keep plain jit —
        # their programs mutate post-init (psum_axis) and run under
        # shard_map.
        self._mgr = None
        if num_rows_override is None:
            from ..compile import get_manager
            self._mgr = get_manager()
        if self._mgr is not None:
            sig = self._compile_signature()
            self._grow_entry = self._mgr.shared_entry(
                "fused/grow_tree", sig,
                lambda: jax.jit(
                    self._entry_grow_tree,
                    static_argnames=("compute_score_update",)),
                profiled=True)
            self._iter_entry = self._mgr.shared_entry(
                "fused/train_iter", sig,
                lambda: jax.jit(self._entry_train_iter, donate_argnums=1),
                donate_argnums=(1,), profiled=True)
            self._sync_entry = self._mgr.shared_entry(
                "fused/sync_scores", sig,
                lambda: jax.jit(self._sync_scores))
            self._trav_entry = self._mgr.shared_entry(
                "fused/traverse", sig,
                lambda: jax.jit(self._entry_traverse))
            self._grow_jit = instrument_kernel(
                self._grow_entry, "fused", name="fused/grow_tree")
            self._iter_jit = instrument_kernel(
                self._iter_entry, "fused", name="fused/train_iter")
            self._sync_jit = instrument_kernel(
                self._sync_entry, "fused", name="fused/sync_scores")
            self._trav_jit = self._trav_entry
            self._register_warmup_specs()
        else:
            self._grow_jit = instrument_kernel(
                jax.jit(self._entry_grow_tree,  # tpulint: jit-ok(manager-disabled fallback branch)
                        static_argnames=("compute_score_update",)),
                "fused", name="fused/grow_tree")
            self._iter_jit = instrument_kernel(
                jax.jit(self._entry_train_iter, donate_argnums=1),  # tpulint: jit-ok(manager-disabled fallback branch)
                "fused", name="fused/train_iter")
            self._sync_jit = instrument_kernel(
                jax.jit(self._sync_scores),  # tpulint: jit-ok(manager-disabled fallback branch)
                "fused",
                name="fused/sync_scores")
            self._trav_jit = jax.jit(self._entry_traverse)  # tpulint: jit-ok(manager-disabled fallback branch)

    # ------------------------------------------------------------------
    def codes_planes(self) -> jax.Array:
        if self._codes_planes_dev is None:
            # host pack + one upload: no device program, so nothing of
            # this stage compiles; the stage is closed by one block
            with span("fused/pack_codes", stage="state/pack_codes"):
                # tpulint: sync-ok(set-up, once per state build, ahead of a compile-paying call that blocks anyway)
                self._codes_planes_dev = jax.block_until_ready(
                    plane.build_codes_planes(self.dataset.bins,
                                             self.layout))
        return self._codes_planes_dev

    # -- AOT compile manager integration -------------------------------
    def _tables(self) -> Dict:
        """Dataset-valued lookup tables as ONE pytree, passed as a jit
        ARGUMENT to every entry point. Closing over them instead would
        bake each dataset's bin boundaries into the executable, which
        kills cross-dataset executable sharing (and would silently alias
        programs if the compile signature missed a value).

        The snapshot is frozen on first use: `_bind_tables` temporarily
        rebinds the instance attributes to TRACERS while a warmup thread
        lowers an entry, and a concurrent training-thread call site must
        never pick those up as call arguments."""
        t = self._tables_cache
        if t is None:
            m = self.meta
            t = {
                "meta": {"num_bin": m.num_bin,
                         "missing_type": m.missing_type,
                         "default_bin": m.default_bin,
                         "is_categorical": m.is_categorical,
                         "monotone": m.monotone, "penalty": m.penalty},
                "miss": self.feature_miss_bin,
                "efb": self._efb_dev,
                "efb_hist": self._efb_hist,
                "mv": self._mv_tables,
            }
            # canonicalize scalar leaves (e.g. the EFB hist_tables' bg
            # int) to arrays so warmup specs can take avals of every
            # leaf and live calls produce the identical shape signature
            t = self._tables_cache = jax.tree_util.tree_map(
                lambda a: a if isinstance(a, jax.Array) else jnp.asarray(a),
                t)
        return t

    @contextlib.contextmanager
    def _bind_tables(self, tables: Dict):
        """Swap the instance's table attributes for traced values while
        an entry point traces. Serialized under the manager's trace lock
        (re-entrant) so a warmup thread lowering one entry can never
        race the training thread tracing another on this instance."""
        from ..compile import get_manager
        with get_manager()._trace_lock:
            saved = (self.meta, self.feature_miss_bin, self._efb_dev,
                     self._efb_hist, self._mv_tables)
            m = tables["meta"]
            self.meta = S.FeatureMeta(
                num_bin=m["num_bin"], missing_type=m["missing_type"],
                default_bin=m["default_bin"],
                is_categorical=m["is_categorical"],
                monotone=m["monotone"], penalty=m["penalty"],
                cat_idx=saved[0].cat_idx)
            self.feature_miss_bin = tables["miss"]
            self._efb_dev = tables["efb"]
            self._efb_hist = tables["efb_hist"]
            self._mv_tables = tables.get("mv")
            try:
                yield
            finally:
                (self.meta, self.feature_miss_bin, self._efb_dev,
                 self._efb_hist, self._mv_tables) = saved

    def _compile_signature(self) -> Dict:
        """Everything that shapes the traced programs EXCEPT the table
        values (traced args) and row-shaped arrays (in the per-call
        shape signature). Equal signatures => identical jaxprs."""
        from ..compile import config_signature
        return {
            "config": config_signature(self.config),
            "layout": tuple(self.layout),
            "caps": tuple(self._caps),
            "dyn": (self._dyn_tile, self._dyn_hist_rb),
            "num_features": self.num_features,
            "max_num_bin": self.max_num_bin,
            "group_max_bin": self.group_max_bin,
            "num_leaves": self.num_leaves,
            "any_categorical": self.any_categorical,
            "use_monotone": self.use_monotone,
            "cat_idx": tuple(self.meta.cat_idx),
            "hist_method": self._hist_method,
            "mv_total_bins": self._mv_total_bins,
            "part_method": self._part_method,
            "use_hist_pool": self._use_hist_pool,
            "score_from_partition": self._score_from_partition,
            "persistent": self.persistent_capable,
            "objective": (type(self.objective).__name__
                          if self.objective is not None else None),
            "split_cfg": self.split_cfg,
            "forced": self._forced_sig,
            "efb": self._efb_dev is not None,
            "efb_hist": self._efb_hist is not None,
        }

    def _entry_grow_tree(self, tables, codes_planes, grad, hess, in_bag,
                         n_valid, feature_mask, mv=None,
                         compute_score_update: bool = True):
        with self._bind_tables(tables):
            return self._grow_tree(codes_planes, grad, hess, in_bag,
                                   n_valid, feature_mask, mv,
                                   compute_score_update)

    def _entry_train_iter(self, tables, data, feature_mask, shrinkage,
                          bias, n_valid, key=None):
        with self._bind_tables(tables):
            return self._train_iter(data, feature_mask, shrinkage, bias,
                                    n_valid=n_valid, key=key)

    def _entry_traverse(self, tables, ta, bins):
        with self._bind_tables(tables):
            return self.traverse_bins(ta, bins)

    def _register_warmup_specs(self) -> None:
        """Abstract call specs (ShapeDtypeStructs) for the entries the
        training loop will hit, so compile/warmup.py can compile them
        before (or concurrently with) the first iteration."""
        Ly = self.layout
        aval = jax.ShapeDtypeStruct
        t_avals = jax.tree_util.tree_map(
            lambda a: aval(a.shape, a.dtype), self._tables())
        data_aval = aval((Ly.num_planes, Ly.num_lanes), jnp.int32)
        if self.config.feature_fraction_bynode < 1.0:
            mask_aval = aval((2 * self.num_leaves, self.num_features),
                             jnp.bool_)
        else:
            mask_aval = aval((self.num_features,), jnp.bool_)
        f32s = aval((), jnp.float32)
        i32s = aval((), jnp.int32)
        if self.persistent_capable and self._score_from_partition:
            if self._quant:
                key_aval = aval((2,), jnp.uint32)
                self._iter_entry.add_spec(
                    (t_avals, data_aval, mask_aval, f32s, f32s, i32s,
                     key_aval))
            else:
                self._iter_entry.add_spec(
                    (t_avals, data_aval, mask_aval, f32s, f32s, i32s))
            # fused/sync_scores is NOT warmed up: no iteration needs it,
            # and its [n]-sized scatter compiles for tens of seconds at
            # 21M rows — in the background that lands inside the first
            # iterations of a cold process (a benchmark's measured
            # window) now that no device pack hides it. It is built when
            # a host consumer first asks for the scores.
        elif self._score_from_partition:
            n = self.actual_rows
            cp_aval = aval((Ly.code_planes, Ly.num_lanes), jnp.int32)
            fvec = aval((n,), jnp.float32)
            mv_aval = (aval(self._mv_dev.shape, jnp.int32)
                       if self._mv_dev is not None else None)
            self._grow_entry.add_spec(
                (t_avals, cp_aval, fvec, fvec, None, i32s, mask_aval,
                 mv_aval), {"compute_score_update": True})

    def _branch_tile(self, cap: int) -> int:
        """Per-branch partition processing tile: the kernels are
        per-STEP-overhead bound (~4 us/step, scripts/part_micro.py), so
        larger capacity branches use larger tiles — up to cap/8, the
        layout's padded max_tile, and the scoped-VMEM budget."""
        Ly = self.layout
        s = Ly.tile
        while (s * 2 <= Ly.max_tile and s * 2 * 8 <= cap
               and cap % (s * 2) == 0       # window geometry requires it
               and plane.partition_vmem_bytes_at(
                   Ly.num_planes, s * 2, self._part_method)
               <= plane.PART_VMEM_BUDGET):
            s *= 2
        return s

    def _branch_hist_rb(self, cap: int) -> int:
        """Per-branch histogram row-block length (same per-step
        amortization as _branch_tile; the planar hist kernel's VMEM
        footprint is small, so only cap/8 and max_tile bound it)."""
        rb = min(H.PLANAR_RB, self.layout.max_tile)
        while rb > 1024 and cap % rb:
            rb //= 2                         # window coverage requires it
        while (rb * 2 <= min(8192, self.layout.max_tile, cap // 8)
               and cap % (rb * 2) == 0):
            rb *= 2
        return rb

    def _switch_by_cap(self, count, branches_of_cap, *args):
        """Static-capacity ladder dispatch — REF/row-major paths only
        (XLA slices need compile-time widths). The pallas kernel paths
        use the dynamic-grid cap=None mode instead and never ladder."""
        branches = [branches_of_cap(c) for c in self._caps]
        cap_arr = jnp.asarray(self._caps, jnp.int32)
        idx = jnp.searchsorted(cap_arr, jnp.maximum(count, 1))
        idx = jnp.minimum(idx, len(self._caps) - 1)
        return jax.lax.switch(idx, branches, *args)  # tpulint: switch-ok(XLA-sliced ref fallback needs static window widths; pallas paths are ladder-free)

    def _psum(self, x):
        """Cross-shard sum (reference Network::Allreduce of histogram
        buffers, data_parallel_tree_learner.cpp:169) — identity on one
        chip."""
        if self.psum_axis is None:
            return x
        with jax.named_scope("lgbm.allreduce"):
            return jax.lax.psum(x, self.psum_axis)

    def _psum_max(self, x):
        """Cross-shard max — identity on one chip (the quantization
        scales must agree across shards before any int32 hist psum)."""
        if self.psum_axis is None:
            return x
        with jax.named_scope("lgbm.allreduce"):
            return jax.lax.pmax(x, self.psum_axis)

    def _window_hist(self, b, g, h):
        """Histogram of bin codes with masked weights; EFB bundle
        columns are gathered back to per-feature space (FixHistogram
        mfb reconstruction)."""
        nbins = (self.group_max_bin if self._efb_hist is not None
                 else self.max_num_bin)
        return self._hist_from_groups(
            H.histogram(b, g, h, nbins, method=self._hist_method))

    def _hist_from_groups(self, ghist):
        """Group-level [G, Bg, 2] -> per-feature [F, B, 2] (EFB
        FixHistogram mfb reconstruction) or identity when unbundled."""
        if self._efb_hist is None:
            return ghist
        from ..io.efb import per_feature_hist
        total = ghist[0].sum(axis=0)
        return per_feature_hist(ghist, self._efb_hist, total[0], total[1])

    def _leaf_hist_switch(self, data, start, count):
        """Histogram of a leaf range straight off the planar state; the
        CPU/oracle path goes through the row-major bridge instead.

        The planar pallas kernel takes the dynamic-grid mode (cap=None):
        one lowered program for every leaf size, no capacity switch. The
        row-major bridge keeps the static-capacity ladder — its window
        slice width is a compile-time constant by construction."""
        Ly = self.layout
        R = Ly.num_lanes
        nbins = (self.group_max_bin if self._efb_hist is not None
                 else self.max_num_bin)
        # planar kernel reads CS super-chunks of SP planes off the grid;
        # ensure the padded super-chunks never read past the plane count
        _, sp, _, cs = H.planar_grid_dims(nbins, Ly.code_bits, Ly.num_cols)
        planar_ok = (self._hist_method is not None
                     and cs * sp <= Ly.num_planes)
        dtype = (jnp.bfloat16 if self._hist_method == "radix_pallas_bf16"
                 else jnp.float32)

        if self._hist_method == "multival_pallas":
            return self._leaf_hist_multival(data, start, count)

        if planar_ok:
            ghist = H.histogram_planar_pallas(
                data, start, count, num_bins=nbins,
                num_cols=Ly.num_cols, code_bits=Ly.code_bits,
                grad_plane=Ly.grad, cap=None, dtype=dtype,
                rows_per_block=self._dyn_hist_rb, quant=self._quant,
                interpret=self._interpret)
            return self._hist_from_groups(ghist)

        def branch(cap):
            def fn(data, start, count):
                rs = jnp.clip(jnp.asarray(start, jnp.int32), 0, R - cap)
                codes, gh = plane.window_rowmajor(data, self.layout, rs,
                                                  cap=cap)
                off = jnp.asarray(start, jnp.int32) - rs
                pos = jnp.arange(cap, dtype=jnp.int32)
                valid = (pos >= off) & (pos < off + count)
                if self._quant:
                    # the grad plane carries packed (qg, qh) words
                    # bitcast through the f32 lanes — unpack to int32
                    # levels so the hist kernels take their exact
                    # integer-accumulation paths
                    qg, qh = Q.unpack_gh(plane.f32_as_i32(gh[:, 0]))
                    zero = jnp.zeros((), jnp.int32)
                    g = jnp.where(valid, qg, zero)
                    h = jnp.where(valid, qh, zero)
                else:
                    g = jnp.where(valid, gh[:, 0], 0.0)
                    h = jnp.where(valid, gh[:, 1], 0.0)
                return self._window_hist(codes, g, h)
            return fn

        return self._switch_by_cap(count, branch, data, start, count)

    def _leaf_hist_multival(self, data, start, count):
        """Leaf histogram off the row-wise multi-value planes (wide-
        sparse shape): the kernel accumulates a flat [T+1, 2] pair
        vector over present codes only, then per-group rows are gathered
        back and the absent default cell of each group is reconstructed
        from the sentinel leaf totals (flat cell T)."""
        from ..ops import multival as MV
        Ly = self.layout
        dtype = (jnp.bfloat16
                 if self.config.tpu_hist_dtype == "bfloat16"
                 else jnp.float32)
        flat = MV.histogram_multival_planar(
            data, start, count,
            mv_start=Ly.mv_start, mv_planes=Ly.mv_planes,
            total_bins=self._mv_total_bins, grad_plane=Ly.grad,
            dtype=dtype, rows_per_block=self._dyn_hist_rb,
            quant=self._quant, interpret=self._interpret)
        ghist = MV.group_hist_from_flat(flat, self._mv_tables)
        if self._efb_hist is None:
            return ghist
        from ..io.efb import per_feature_hist
        total = flat[-1]
        return per_feature_hist(ghist, self._efb_hist, total[0], total[1])

    def _split_step(self, data, start, count, feature, thr, dl, miss_bin,
                    cat=None, bits=None):
        """Split one leaf: the carry-stream partition kernel moves its
        rows (ops/plane.py), then the smaller child's histogram comes
        from the freshly contiguous range at its own capacity bucket."""
        rscal = plane.route_scalars(self.layout, feature, thr, dl, miss_bin,
                                    self._efb_dev, is_cat=cat,
                                    cat_bitset=bits)
        return self._partition(data, start, count, rscal)

    def _partition(self, data, start, count, rscal):
        """Stable partition of the window [start, start + count) by the
        routing ``rscal`` with the grower's kernel: (data', nleft)."""
        if self._part_method in ("pallas", "pallas2"):
            # dynamic-grid partition: one lowered kernel for every leaf
            # size (ops/plane.py cap=None) — no capacity switch
            return plane.partition_window(
                data, self.layout, start, count, rscal, cap=None,
                method=self._part_method, tile=self._dyn_tile,
                interpret=self._interpret)

        def branch(cap):
            def fn(data, start, count, rscal):
                return plane.partition_window(
                    data, self.layout, start, count, rscal, cap=cap,
                    method=self._part_method, tile=self._branch_tile(cap))
            return fn

        data, nleft = self._switch_by_cap(count, branch, data, start, count,
                                          rscal)
        return data, nleft

    def _scan_leaf(self, hist, sum_g, sum_h, count, output, cmin, cmax,
                   feature_mask, qscales=None):
        """Best split of one leaf from its pooled histogram; categorical
        features go through the merged numerical+categorical scan and
        materialize their left-category bitset HERE (the device
        analogue of serial.py _cat_bins), so the loop state only
        carries [8] words per leaf, not the full sorted order.
        ``qscales``: (grad_scale, hess_scale) when the pool holds int32
        level-sums — the scans themselves always run in f32."""
        if qscales is not None:
            hist = S.dequantize_hist(hist, qscales[0], qscales[1])
        if self.any_categorical:
            res = S.best_split(hist, self.meta, self.split_cfg, sum_g,
                               sum_h, count, output, cmin, cmax,
                               any_categorical=True)
        else:
            res = S.numerical_split_scan(hist, self.meta, self.split_cfg,
                                         sum_g, sum_h, count, output,
                                         cmin, cmax)
        gains = jnp.where(feature_mask, res["gain"], S.K_MIN_SCORE)
        f = jnp.argmax(gains).astype(jnp.int32)
        g = gains[f]
        ok = jnp.isfinite(g) & (g > 0.0) \
            & (count >= 2 * self.split_cfg.min_data_in_leaf)
        out = dict(
            gain=jnp.where(ok, g, NEG_INF),
            feature=f,
            thr=res["threshold"][f],
            dl=res["default_left"][f],
            lg=res["left_sum_gradient"][f], lh=res["left_sum_hessian"][f],
            lcnt=res["left_count"][f], lout=res["left_output"][f],
            rg=res["right_sum_gradient"][f], rh=res["right_sum_hessian"][f],
            rcnt=res["right_count"][f], rout=res["right_output"][f])
        if self.any_categorical:
            out["cat"] = self.meta.is_categorical[f]
            out["bits"] = self._cat_bitset_device(res, f)
        else:
            out["cat"] = jnp.bool_(False)
            out["bits"] = jnp.zeros(8, jnp.int32)
        return out

    def _cat_bitset_device(self, res, f):
        """[8] i32 left-category bin bitset from the categorical scan's
        (family, position, sorted order, used) description — family 0 is
        the single one-vs-rest bin, 1/2 are prefix/suffix of the sorted
        order (feature_histogram.hpp:278 one-hot and directional scans;
        host-side mirror: serial.py _cat_bins)."""
        fam = res["cat_family"][f]
        pos = jnp.asarray(res["threshold"][f], jnp.int32)
        order = res["cat_sorted_order"][f].astype(jnp.int32)   # [B]
        used = res["cat_used_bin"][f]
        B = order.shape[0]
        idx = jnp.arange(B, dtype=jnp.int32)
        sel_fwd = idx <= pos
        sel_bwd = (idx >= used - 1 - pos) & (idx < used)
        sel = jnp.where(fam == 1, sel_fwd, sel_bwd) & (fam != 0)
        bins_eff = jnp.where(fam == 0, pos, order)
        sel = sel | ((fam == 0) & (idx == 0))
        bit = jnp.left_shift(jnp.int32(1), bins_eff & 31)
        words = []
        for w in range(8):
            words.append(jnp.sum(jnp.where(
                sel & ((bins_eff >> 5) == w), bit, 0)))
        return jnp.stack(words)

    def _scan_two_leaves(self, hist2, sum_g2, sum_h2, count2, output2,
                         cmin2, cmax2, feature_mask2, qscales=None):
        """Both children's best splits from one vmapped scan (halves the
        per-split scan kernel count vs two sequential _scan_leaf calls).
        feature_mask2: [2, F] — per-child masks (identical rows unless
        feature_fraction_bynode is active)."""
        res2 = jax.vmap(
            lambda h, sg, sh, c, o, lo, hi, m: self._scan_leaf(
                h, sg, sh, c, o, lo, hi, m, qscales=qscales)
        )(hist2, sum_g2, sum_h2, count2, output2, cmin2, cmax2,
          feature_mask2)
        first = {k: v[0] for k, v in res2.items()}
        second = {k: v[1] for k, v in res2.items()}
        return first, second

    # ------------------------------------------------------------------
    def _grow_tree_core(self, data, bag_cnt, feature_mask, qscales=None):
        """The while_loop tree builder over planar data. Returns
        (tree arrays dict, final FusedTreeState). feature_mask: [F]
        per-tree mask, or [2L, F] per-scan-event masks (see
        feature_masks_for_tree) — the rank is a static branch.
        ``qscales``: (grad_scale, hess_scale) traced scalars when the
        grad plane carries packed quantized levels; the hist pool and
        the subtraction then stay in exact int32, and every per-leaf
        f32 state field (sums, outputs) is dequantized at the scan
        boundary."""
        L = self.num_leaves
        F, B = self.num_features, self.max_num_bin
        f32, i32 = jnp.float32, jnp.int32
        quant = qscales is not None
        bynode = feature_mask.ndim == 2
        root_mask = feature_mask[0] if bynode else feature_mask

        with jax.named_scope("lgbm.root_hist"):
            root_hist = self._psum(self._leaf_hist_switch(
                data, jnp.int32(0), bag_cnt))
            bag_cnt_g = self._psum(jnp.asarray(bag_cnt, i32))
        with jax.named_scope("lgbm.split_scan"):
            if quant:
                sum_g = jnp.sum(root_hist[0, :, 0]).astype(f32) * qscales[0]
                sum_h = jnp.sum(root_hist[0, :, 1]).astype(f32) * qscales[1]
            else:
                sum_g = jnp.sum(root_hist[0, :, 0])
                sum_h = jnp.sum(root_hist[0, :, 1])
            root_best = self._scan_leaf(
                root_hist, sum_g, sum_h, bag_cnt_g, f32(0.0),
                f32(-jnp.inf), f32(jnp.inf), root_mask, qscales=qscales)

        def arr(val, dtype=f32):
            return jnp.full((L,), val, dtype)

        with jax.named_scope("lgbm.bookkeeping"):
            st = FusedTreeState(
                data=data, n_leaves=i32(1),
                leaf_start=arr(0, i32).at[0].set(0),
                leaf_count=arr(0, i32).at[0].set(bag_cnt),
                leaf_count_g=arr(0, i32).at[0].set(bag_cnt_g),
                leaf_sum_g=arr(0.0).at[0].set(sum_g),
                leaf_sum_h=arr(0.0).at[0].set(sum_h),
                leaf_output=arr(0.0),
                leaf_depth=arr(0, i32),
                leaf_parent=arr(-1, i32),
                leaf_cmin=arr(-jnp.inf), leaf_cmax=arr(jnp.inf),
                best_gain=arr(NEG_INF).at[0].set(root_best["gain"]),
                best_feature=arr(0, i32).at[0].set(root_best["feature"]),
                best_thr=arr(0, i32).at[0].set(root_best["thr"]),
                best_dl=arr(False, bool).at[0].set(root_best["dl"]),
                best_lg=arr(0.0).at[0].set(root_best["lg"]),
                best_lh=arr(0.0).at[0].set(root_best["lh"]),
                best_lcnt=arr(0, i32).at[0].set(root_best["lcnt"]),
                best_lout=arr(0.0).at[0].set(root_best["lout"]),
                best_rg=arr(0.0).at[0].set(root_best["rg"]),
                best_rh=arr(0.0).at[0].set(root_best["rh"]),
                best_rcnt=arr(0, i32).at[0].set(root_best["rcnt"]),
                best_rout=arr(0.0).at[0].set(root_best["rout"]),
                best_cat=arr(False, bool).at[0].set(root_best["cat"]),
                best_bits=jnp.zeros((L, 8), i32).at[0].set(
                    root_best["bits"]),
                hist_pool=(jnp.zeros((L, F, B, 2), i32 if quant else f32)
                           .at[0].set(root_hist)
                           if self._use_hist_pool
                           else jnp.zeros((1, 1, 1, 2),
                                          i32 if quant else f32)),
                t_feature=jnp.zeros((L - 1,), i32),
                t_thr=jnp.zeros((L - 1,), i32),
                t_dl=jnp.zeros((L - 1,), bool),
                t_left=jnp.zeros((L - 1,), i32),
                t_right=jnp.zeros((L - 1,), i32),
                t_gain=jnp.zeros((L - 1,), f32),
                t_ivalue=jnp.zeros((L - 1,), f32),
                t_iweight=jnp.zeros((L - 1,), f32),
                t_icount=jnp.zeros((L - 1,), i32),
                t_cat=jnp.zeros((L - 1,), bool),
                t_bits=jnp.zeros((L - 1, 8), i32),
            )

        max_depth = self.config.max_depth
        mono_dev = self.meta.monotone

        def cond(st: FusedTreeState):
            gains = st.best_gain
            if max_depth > 0:
                gains = jnp.where(st.leaf_depth >= max_depth, NEG_INF, gains)
            return (st.n_leaves < L) & (jnp.max(gains) > 0.0)

        def body(st: FusedTreeState, rec=None) -> FusedTreeState:
            """One split step. rec=None: split the best-gain leaf with
            its scanned best (the while_loop body). rec given: apply a
            FORCED split (leaf, feature, threshold fixed; sums computed
            from the pooled histogram) — reference ForceSplits,
            serial_tree_learner.cpp:427."""
            if rec is None:
                with jax.named_scope("lgbm.pick_leaf"):
                    gains = st.best_gain
                    if max_depth > 0:
                        gains = jnp.where(st.leaf_depth >= max_depth,
                                          NEG_INF, gains)
                    leaf = jnp.argmax(gains).astype(i32)
                    feat = st.best_feature[leaf]
                    thr = st.best_thr[leaf]
                    dl = st.best_dl[leaf]
                    cat = st.best_cat[leaf]
                    bits = st.best_bits[leaf]
                    rec = dict(
                        gain=st.best_gain[leaf],
                        lg=st.best_lg[leaf], lh=st.best_lh[leaf],
                        lout=st.best_lout[leaf],
                        rg=st.best_rg[leaf], rh=st.best_rh[leaf],
                        rout=st.best_rout[leaf])
            else:
                leaf = rec["leaf"]
                feat, thr = rec["feature"], rec["threshold"]
                dl = rec["dl"]
                cat = jnp.bool_(False)
                bits = jnp.zeros(8, i32)
            node = st.n_leaves - 1
            new_leaf = st.n_leaves
            miss = self.feature_miss_bin[feat]

            # --- tree bookkeeping (Tree::Split semantics, tree.h:61) ---
            with jax.named_scope("lgbm.bookkeeping"):
                parent = st.leaf_parent[leaf]
                has_parent = parent >= 0
                pl = st.t_left[jnp.maximum(parent, 0)]
                fix_left = has_parent & (pl == ~leaf)
                t_left = st.t_left.at[jnp.maximum(parent, 0)].set(
                    jnp.where(fix_left, node,
                              st.t_left[jnp.maximum(parent, 0)]))
                t_right = st.t_right.at[jnp.maximum(parent, 0)].set(
                    jnp.where(has_parent & ~fix_left, node,
                              st.t_right[jnp.maximum(parent, 0)]))
                t_feature = st.t_feature.at[node].set(feat)
                t_thr = st.t_thr.at[node].set(thr)
                t_dl = st.t_dl.at[node].set(dl)
                t_left = t_left.at[node].set(~leaf)
                t_right = t_right.at[node].set(~new_leaf)
                t_gain = st.t_gain.at[node].set(rec["gain"])
                t_ivalue = st.t_ivalue.at[node].set(st.leaf_output[leaf])
                t_iweight = st.t_iweight.at[node].set(st.leaf_sum_h[leaf])
                t_icount = st.t_icount.at[node].set(st.leaf_count_g[leaf])
                t_cat = st.t_cat.at[node].set(cat)
                t_bits = st.t_bits.at[node].set(bits)

            # --- shard-local partition; counts reduced globally ---
            start = st.leaf_start[leaf]
            count = st.leaf_count[leaf]
            count_g = st.leaf_count_g[leaf]
            with jax.named_scope("lgbm.partition"):
                new_data, nleft = self._split_step(
                    st.data, start, count, feat, thr, dl, miss,
                    cat=cat, bits=bits)
            nright = count - nleft
            nleft_g = self._psum(nleft)
            nright_g = count_g - nleft_g

            # smaller child by GLOBAL count — every shard must histogram
            # the same child for the psum + subtraction to be coherent
            left_smaller = nleft_g <= nright_g
            s_start = jnp.where(left_smaller, start, start + nleft)
            s_count = jnp.where(left_smaller, nleft, nright)
            with jax.named_scope("lgbm.hist"):
                hist_small = self._psum(
                    self._leaf_hist_switch(new_data, s_start, s_count))

            # --- children bookkeeping ---
            lout, rout = rec["lout"], rec["rout"]
            with jax.named_scope("lgbm.bookkeeping"):
                depth = st.leaf_depth[leaf] + 1
                cmin, cmax = st.leaf_cmin[leaf], st.leaf_cmax[leaf]
                if self.use_monotone:
                    monof = mono_dev[feat]
                    mid = (lout + rout) / 2.0
                    lcmax = jnp.where(monof > 0, jnp.minimum(cmax, mid), cmax)
                    rcmin = jnp.where(monof > 0, jnp.maximum(cmin, mid), cmin)
                    lcmin = jnp.where(monof < 0, jnp.maximum(cmin, mid), cmin)
                    rcmax = jnp.where(monof < 0, jnp.minimum(cmax, mid), cmax)
                else:
                    lcmin, lcmax, rcmin, rcmax = cmin, cmax, cmin, cmax

                leaf_start = st.leaf_start.at[new_leaf].set(start + nleft)
                leaf_count = st.leaf_count.at[leaf].set(nleft)\
                                           .at[new_leaf].set(nright)
                leaf_count_g = st.leaf_count_g.at[leaf].set(nleft_g)\
                                              .at[new_leaf].set(nright_g)
                leaf_sum_g = st.leaf_sum_g.at[leaf].set(rec["lg"])\
                                          .at[new_leaf].set(rec["rg"])
                leaf_sum_h = st.leaf_sum_h.at[leaf].set(rec["lh"])\
                                          .at[new_leaf].set(rec["rh"])
                leaf_output = st.leaf_output.at[leaf].set(lout)\
                                            .at[new_leaf].set(rout)
                leaf_depth = st.leaf_depth.at[leaf].set(depth)\
                                          .at[new_leaf].set(depth)
                leaf_parent = st.leaf_parent.at[leaf].set(node)\
                                            .at[new_leaf].set(node)
                leaf_cmin = st.leaf_cmin.at[leaf].set(lcmin)\
                                        .at[new_leaf].set(rcmin)
                leaf_cmax = st.leaf_cmax.at[leaf].set(lcmax)\
                                        .at[new_leaf].set(rcmax)

            # --- larger child: subtraction from the pooled parent (or a
            # second contiguous-slice histogram when pool-less) ---
            if self._use_hist_pool:
                with jax.named_scope("lgbm.pool"):
                    hist_large = st.hist_pool[leaf] - hist_small
                    hist_left = jnp.where(left_smaller, hist_small,
                                          hist_large)
                    hist_right = jnp.where(left_smaller, hist_large,
                                           hist_small)
                    # both child rows are finished before the first write,
                    # so the carry is updated in place; else XLA:TPU fuses
                    # the subtraction into the writes, the second reads the
                    # pre-write pool, and each split copies the pool twice
                    # (L*F*B*8 bytes each) — tests/test_pool_inplace.py
                    hist_left, hist_right = jax.lax.optimization_barrier(
                        (hist_left, hist_right))
                    hist_pool = st.hist_pool.at[leaf].set(hist_left)\
                                            .at[new_leaf].set(hist_right)
            else:
                l_start = jnp.where(left_smaller, start + nleft, start)
                l_count = jnp.where(left_smaller, nright, nleft)
                with jax.named_scope("lgbm.hist"):
                    hist_large = self._psum(
                        self._leaf_hist_switch(new_data, l_start, l_count))
                hist_left = jnp.where(left_smaller, hist_small, hist_large)
                hist_right = jnp.where(left_smaller, hist_large, hist_small)
                hist_pool = st.hist_pool

            # --- best splits for both children (one vmapped scan) ---
            with jax.named_scope("lgbm.split_scan"):
                if bynode:
                    mask2 = jnp.stack([feature_mask[2 * new_leaf - 1],
                                       feature_mask[2 * new_leaf]])
                else:
                    mask2 = jnp.stack([feature_mask, feature_mask])
                bl, br = self._scan_two_leaves(
                    jnp.stack([hist_left, hist_right]),
                    jnp.stack([rec["lg"], rec["rg"]]),
                    jnp.stack([rec["lh"], rec["rh"]]),
                    jnp.stack([nleft_g, nright_g]),
                    jnp.stack([lout, rout]),
                    jnp.stack([lcmin, rcmin]),
                    jnp.stack([lcmax, rcmax]), mask2, qscales=qscales)

            def upd(a, key, cast=lambda x: x):
                return a.at[leaf].set(cast(bl[key])).at[new_leaf].set(cast(br[key]))

            with jax.named_scope("lgbm.bookkeeping"):
                return FusedTreeState(
                    data=new_data, n_leaves=st.n_leaves + 1,
                    leaf_start=leaf_start, leaf_count=leaf_count,
                    leaf_count_g=leaf_count_g,
                    leaf_sum_g=leaf_sum_g, leaf_sum_h=leaf_sum_h,
                    leaf_output=leaf_output, leaf_depth=leaf_depth,
                    leaf_parent=leaf_parent, leaf_cmin=leaf_cmin,
                    leaf_cmax=leaf_cmax,
                    best_gain=upd(st.best_gain, "gain"),
                    best_feature=upd(st.best_feature, "feature"),
                    best_thr=upd(st.best_thr, "thr"),
                    best_dl=upd(st.best_dl, "dl"),
                    best_lg=upd(st.best_lg, "lg"),
                    best_lh=upd(st.best_lh, "lh"),
                    best_lcnt=upd(st.best_lcnt, "lcnt"),
                    best_lout=upd(st.best_lout, "lout"),
                    best_rg=upd(st.best_rg, "rg"),
                    best_rh=upd(st.best_rh, "rh"),
                    best_rcnt=upd(st.best_rcnt, "rcnt"),
                    best_rout=upd(st.best_rout, "rout"),
                    best_cat=upd(st.best_cat, "cat"),
                    best_bits=st.best_bits.at[leaf].set(bl["bits"])
                                          .at[new_leaf].set(br["bits"]),
                    hist_pool=hist_pool,
                    t_feature=t_feature, t_thr=t_thr, t_dl=t_dl,
                    t_left=t_left, t_right=t_right, t_gain=t_gain,
                    t_ivalue=t_ivalue, t_iweight=t_iweight,
                    t_icount=t_icount, t_cat=t_cat, t_bits=t_bits,
                )

        # --- user-forced splits first (BFS schedule precomputed on the
        # host; reference SerialTreeLearner::ForceSplits,
        # serial_tree_learner.cpp:427) ---
        if self._forced_sched is not None:
            f_leaf, f_feat, f_thr = self._forced_sched
            eps = S.K_EPSILON
            B = self.max_num_bin

            def forced_step(carry, k):
                st, alive = carry
                leaf = f_leaf[k]
                feat = f_feat[k]
                thr = f_thr[k]
                hist = st.hist_pool[leaf]            # [F, B, 2]
                if quant:
                    # same int->f32 boundary as the gain scans: the
                    # forced-split sums below are all-f32 arithmetic
                    hist = S.dequantize_hist(hist, qscales[0], qscales[1])
                h = jnp.sum(jnp.where(
                    (jnp.arange(F, dtype=i32) == feat)[:, None, None],
                    hist, 0.0), axis=0)              # [B, 2], no gather
                bidx = jnp.arange(B, dtype=i32)
                miss = self.feature_miss_bin[feat]
                sel = ((bidx <= thr) &
                       jnp.where(miss >= 0, bidx != miss, True))
                selm = sel.astype(f32)
                lg = jnp.sum(selm * h[:, 0])
                lh = jnp.sum(selm * h[:, 1])
                sum_g_l = st.leaf_sum_g[leaf]
                sum_h_l = st.leaf_sum_h[leaf]
                rg = sum_g_l - lg
                rh = sum_h_l - lh
                cntf = st.leaf_count_g[leaf].astype(f32) \
                    / (sum_h_l + 2 * eps)
                lcnt = jnp.floor(lh * cntf + 0.5).astype(i32)
                parent_out = st.leaf_output[leaf]
                cmin, cmax = st.leaf_cmin[leaf], st.leaf_cmax[leaf]
                # full CalculateSplittedLeafOutput semantics (L1/L2,
                # max_delta_step, path smoothing, monotone clamp) — the
                # same helper every scanned split uses
                lout = S._calc_output(lg, lh + eps, lcnt, self.split_cfg,
                                      parent_out, cmin, cmax)
                rout = S._calc_output(
                    rg, rh + eps, st.leaf_count_g[leaf] - lcnt,
                    self.split_cfg, parent_out, cmin, cmax)
                rec = dict(leaf=leaf, feature=feat, threshold=thr,
                           dl=jnp.bool_(False), gain=f32(0.0),
                           lg=lg, lh=lh, lout=lout,
                           rg=rg, rh=rh, rout=rout)
                # gate on hessian MASS per side (a truly empty side has
                # exactly zero mass; counts are hessian-derived
                # estimates in this design, ops/split.py:18, and could
                # round a small-but-real side to 0)
                ok = (alive & (lh > 1e-9) & (rh > 1e-9)
                      & (st.n_leaves < L)
                      & (st.leaf_count_g[leaf] > 0))
                st = jax.lax.cond(ok, lambda s: body(s, rec=rec),
                                  lambda s: s, st)
                # the host-precomputed slot schedule assumes every
                # earlier forced split succeeded; once one is skipped,
                # later slot ids would alias the wrong leaves — stop
                # forcing (conservative vs the reference's dynamic BFS:
                # the remaining forced splits are left to the normal
                # gain-driven loop)
                return (st, alive & ok), ()

            (st, _alive), _ = jax.lax.scan(
                forced_step, (st, jnp.bool_(True)),
                jnp.arange(f_leaf.shape[0]))

        st = jax.lax.while_loop(cond, body, st)

        tree_arrays = dict(
            n_leaves=st.n_leaves,
            split_feature=st.t_feature, threshold_bin=st.t_thr,
            default_left=st.t_dl, left_child=st.t_left, right_child=st.t_right,
            split_gain=st.t_gain, internal_value=st.t_ivalue,
            internal_weight=st.t_iweight, internal_count=st.t_icount,
            leaf_value=st.leaf_output, leaf_weight=st.leaf_sum_h,
            leaf_count=st.leaf_count_g, leaf_depth=st.leaf_depth,
            split_cat=st.t_cat, split_bits=st.t_bits,
        )
        return tree_arrays, st

    # ------------------------------------------------------------------
    def _pos_leaf_terms(self, st: FusedTreeState):
        """Sorted leaf-window starts + sort order (tiny [L] work).

        Leaves with a zero LOCAL count are excluded: they share their
        start with a sibling window (empty range), and a duplicate
        start would make the rank-among-starts trick attribute the
        rows to the empty leaf — bites on shards that hold no rows of
        some leaf (non-IID data-parallel sharding)."""
        L = self.num_leaves
        lid = jnp.arange(L, dtype=jnp.int32)
        valid = (lid < st.n_leaves) & (st.leaf_count > 0)
        starts = jnp.where(valid, st.leaf_start,
                           jnp.int32(self.layout.num_lanes) + 1)
        order = jnp.argsort(starts)
        return starts[order], order

    def _score_add_by_pos(self, st: FusedTreeState, leaf_vals):
        """Per-lane leaf value as a sum of step functions over the
        sorted window starts — fuses on the VPU, no [N] gather and no
        materialized one-hot."""
        sorted_starts, order = self._pos_leaf_terms(st)
        vals_sorted = leaf_vals[order]          # [L] gather — tiny
        d = vals_sorted - jnp.concatenate(
            [jnp.zeros((1,), jnp.float32), vals_sorted[:-1]])
        pos = jnp.arange(self.layout.num_lanes, dtype=jnp.int32)
        steps = (pos[:, None] >= sorted_starts[None, :]).astype(jnp.float32)
        return jnp.sum(steps * d[None, :], axis=1)

    # -- in-program leaf renewal (renew-tree-output objectives) --------
    def _renew_leaf_outputs(self, st: FusedTreeState, n, alpha: float,
                            weighted: bool):
        """Per-leaf weighted percentile of residuals straight off the
        leaf-ordered planar state — the device form of
        RegressionL1loss::RenewTreeOutput and the Percentile/
        WeightedPercentileFun selection (reference
        regression_objective.hpp:23-88,249).

        No sorts and no [N] gathers: residuals map to a monotone uint32
        key (sign-flipped float bits) and each leaf's order statistic is
        found by a 32-step bisection over key space. The per-step
        per-leaf counts come from one [R] compare + cumsum, read back at
        the window boundaries — every step is a fused VPU pass, and the
        counts psum across shards so the refit is exact under the
        sharded data-parallel learner.

        Tie semantics (weighted mode): the reference walks the stable
        sort order and takes the first item whose cumulative weight
        minus half its own weight crosses alpha*total; value-space
        bisection lumps equal-valued items into one mass and uses the
        half-mass rule. For distinct residuals (the generic case) the
        two rules select the same element; under exact ties they can
        pick adjacent values."""
        Ly = self.layout
        lanes = jnp.arange(Ly.num_lanes, dtype=jnp.int32)
        realm = lanes < jnp.asarray(n, jnp.int32)
        resid = (plane.get_f32(st.data, Ly.label)
                 - plane.get_f32(st.data, Ly.score))
        i = jax.lax.bitcast_convert_type(resid, jnp.int32)
        u = jax.lax.bitcast_convert_type(i, jnp.uint32)
        ukey = jnp.where(i < 0, ~u, u | jnp.uint32(0x80000000))

        sorted_starts, order = self._pos_leaf_terms(st)

        def per_lane(v_leaf, dtype):
            """Broadcast a [L] per-leaf vector to lanes by window —
            telescoping step sums, exact in modular uint32 arithmetic."""
            vs = v_leaf[order].astype(dtype)
            d = vs - jnp.concatenate([jnp.zeros((1,), dtype), vs[:-1]])
            steps = (lanes[:, None] >= sorted_starts[None, :])
            return jnp.sum(jnp.where(steps, d[None, :], 0), axis=1)

        ends = st.leaf_start + st.leaf_count
        sidx = jnp.maximum(st.leaf_start, 1) - 1

        def seg_sums(c):
            """Per-leaf window sums of a [R] vector via one cumsum.
            Shard-locally EMPTY windows at start 0 would read lane 0's
            value (ends==0 -> cs[0]); zero them explicitly BEFORE the
            psum so no shard contributes phantom mass."""
            cs = jnp.cumsum(c)
            lo = jnp.where(st.leaf_start > 0, cs[sidx], 0)
            raw = cs[jnp.maximum(ends, 1) - 1] - lo
            return self._psum(jnp.where(st.leaf_count > 0, raw, 0))

        L = self.num_leaves
        lid = jnp.arange(L, dtype=jnp.int32)
        cnt = st.leaf_count_g
        valid = (lid < st.n_leaves) & (cnt > 0)

        def bisect(pred_of_mid, shape):
            """Smallest uint32 key with monotone pred(mid) true."""
            lo = jnp.zeros(shape, jnp.uint32)
            hi = jnp.full(shape, 0xFFFFFFFF, jnp.uint32)

            def step(_, lh):
                lo, hi = lh
                mid = lo + (hi - lo) // jnp.uint32(2)
                p = pred_of_mid(mid)
                return (jnp.where(p, lo, mid + jnp.uint32(1)),
                        jnp.where(p, mid, hi))

            lo, hi = jax.lax.fori_loop(0, 32, step, (lo, hi))
            return lo

        def key_to_f32(k):
            neg = k < jnp.uint32(0x80000000)
            u_orig = jnp.where(neg, ~k, k & jnp.uint32(0x7FFFFFFF))
            return jax.lax.bitcast_convert_type(u_orig, jnp.float32)

        def order_stat_keys(targets):
            """Integer-exact order statistics: per-leaf uint32 keys at
            ascending 0-indexed ``targets`` [L, T]. Counts are int32
            cumsums, so these bisections cannot jitter."""
            T_ = targets.shape[1]

            def pred(mid):
                cm = jnp.stack([per_lane(mid[:, t], jnp.uint32)
                                for t in range(T_)], axis=0)   # [T, R]
                le = (ukey[None, :] <= cm) & realm[None, :]
                cnts = jnp.stack(
                    [seg_sums(le[t].astype(jnp.int32)) for t in range(T_)],
                    axis=1)                                    # [L, T]
                return cnts >= targets + 1

            return bisect(pred, targets.shape)

        if not weighted:
            # PercentileFun: DESCENDING selection at float_pos =
            # (1-alpha)*cnt via ArgMaxAtK — in ascending ranks the two
            # selected order statistics are cnt-pos and cnt-pos-1, and
            # the result is d[pos-1] - (d[pos-1]-d[pos])*bias. Edge
            # rules (pos<1 -> max, pos>=cnt -> min, cnt<=1 -> the
            # value) mirror the macro exactly.
            cf = cnt.astype(jnp.float32)
            float_pos = (1.0 - jnp.float32(alpha)) * cf
            pos = jnp.floor(float_pos).astype(jnp.int32)
            bias = float_pos - pos.astype(jnp.float32)
            edge_max = pos < 1                     # includes cnt <= 1
            edge_min = pos >= cnt
            r_hi = jnp.clip(cnt - pos, 0, jnp.maximum(cnt - 1, 0))
            r_lo = jnp.clip(cnt - pos - 1, 0, jnp.maximum(cnt - 1, 0))
            r_hi = jnp.where(edge_max, jnp.maximum(cnt - 1, 0),
                             jnp.where(edge_min, 0, r_hi))
            r_lo = jnp.where(edge_max | edge_min, r_hi, r_lo)
            bias = jnp.where(edge_max | edge_min, 0.0, bias)
            keys = order_stat_keys(jnp.stack([r_hi, r_lo], axis=1))
            v1 = key_to_f32(keys[:, 0])            # d[pos-1]
            v2 = key_to_f32(keys[:, 1])            # d[pos]
            out = v1 - (v1 - v2) * bias
        else:
            # WeightedPercentileFun: ascending weighted CDF,
            # pos = upper_bound(cdf, alpha*total); returns the value at
            # pos, except the (next-step-weight >= 1.0) branch which
            # interpolates with a negative factor — mirrored as-is.
            # The value-space bisection uses f32 mass sums (the [R]
            # cumsum carries ~1e-7*prefix rounding and the host uses
            # f64), so the crossing is then SNAPPED to a true data key
            # with integer-exact rank bisections; under exact residual
            # ties the per-index CDF is approximated at value
            # granularity (tie block = one mass).
            w = plane.get_f32(st.data, Ly.weight)
            w = jnp.where(realm, w, 0.0)
            wtot = seg_sums(w)
            thresh = jnp.float32(alpha) * wtot                 # [L]

            def wle_at(mid):
                cm = per_lane(mid, jnp.uint32)                 # [R]
                return seg_sums(jnp.where((ukey <= cm) & realm, w, 0.0))

            b = bisect(lambda mid: wle_at(mid) > thresh, (L,))
            # snap to the data key at the crossing: rank = count(< b),
            # clamped like the reference's pos = min(pos, cnt-1)
            cmb = per_lane(b, jnp.uint32)
            c_lt = seg_sums(((ukey < cmb) & realm).astype(jnp.int32))
            c_lt = jnp.minimum(c_lt, jnp.maximum(cnt - 1, 0))
            prev_rank = jnp.maximum(c_lt - 1, 0)
            keys = order_stat_keys(
                jnp.stack([c_lt, prev_rank], axis=1))
            v2k, v1k = keys[:, 0], keys[:, 1]
            v2 = key_to_f32(v2k)                   # value at pos
            v1 = key_to_f32(v1k)                   # value at pos-1
            # masses at the snapped key: cdf[pos] and the next step
            cm2 = per_lane(v2k, jnp.uint32)
            wle2 = seg_sums(jnp.where((ukey <= cm2) & realm, w, 0.0))
            c_le2 = seg_sums(((ukey <= cm2) & realm).astype(jnp.int32))
            nxt = order_stat_keys(
                jnp.minimum(c_le2, jnp.maximum(cnt - 1, 0))[:, None])[:, 0]
            cm3 = per_lane(nxt, jnp.uint32)
            wle3 = seg_sums(jnp.where((ukey <= cm3) & realm, w, 0.0))
            wnext = wle3 - wle2
            pos0 = c_lt == 0
            islast = c_le2 >= cnt
            interp = (~pos0) & (~islast) & (wnext >= 1.0)
            out_i = (thresh - wle2) / jnp.where(wnext == 0, 1.0, wnext) \
                * (v2 - v1) + v1
            out = jnp.where(interp, out_i, v2)
        return jnp.where(valid, out, 0.0).astype(jnp.float32)

    def _renew_quant_leaves(self, st: FusedTreeState, n):
        """Leaf values from the RAW f32 gradient/hessian sums after a
        quantized-gradient tree search (the reference's
        RenewIntGradTreeOutput, gradient_discretizer.cpp) — the tree
        STRUCTURE keeps the quantized split decisions, the leaf OUTPUTS
        drop the rounding error. Raw grads come from persistent_grads on
        the final state's score/label planes (values unchanged by the
        growth loop, only lane-permuted with the rows), then per-leaf
        window sums via the one-cumsum trick of _renew_leaf_outputs."""
        Ly = self.layout
        lanes = jnp.arange(Ly.num_lanes, dtype=jnp.int32)
        realm = lanes < jnp.asarray(n, jnp.int32)
        score = plane.get_f32(st.data, Ly.score)
        label = plane.get_f32(st.data, Ly.label)
        weight = plane.get_f32(st.data, Ly.weight) if Ly.weight >= 0 \
            else None
        g, h = self.objective.persistent_grads(score, label, weight)
        g = jnp.where(realm, g, 0.0)
        h = jnp.where(realm, h, 0.0)

        ends = st.leaf_start + st.leaf_count
        sidx = jnp.maximum(st.leaf_start, 1) - 1

        def seg_sums(c):
            # per-leaf window sums of a [R] vector via one cumsum;
            # shard-locally empty windows zeroed BEFORE the psum (see
            # _renew_leaf_outputs)
            cs = jnp.cumsum(c)
            lo = jnp.where(st.leaf_start > 0, cs[sidx], 0.0)
            raw = cs[jnp.maximum(ends, 1) - 1] - lo
            return self._psum(jnp.where(st.leaf_count > 0, raw, 0.0))

        sg = seg_sums(g)
        sh = seg_sums(h)
        cfg = self.split_cfg
        # CalculateSplittedLeafOutput's basic form (threshold_l1 is the
        # identity at lambda_l1=0); the monotone bounds carried in the
        # state still clamp the renewed values
        out = -S.threshold_l1(sg, cfg.lambda_l1) \
            / (sh + cfg.lambda_l2 + S.K_EPSILON)
        if cfg.max_delta_step > 0:
            out = jnp.clip(out, -cfg.max_delta_step, cfg.max_delta_step)
        out = jnp.clip(out, st.leaf_cmin, st.leaf_cmax)
        lid = jnp.arange(self.num_leaves, dtype=jnp.int32)
        valid = (lid < st.n_leaves) & (st.leaf_count_g > 0)
        return jnp.where(valid, out, st.leaf_output).astype(jnp.float32)

    # ------------------------------------------------------------------
    def _grow_tree(self, codes_planes, grad, hess, in_bag, n_valid,
                   feature_mask, mv=None,
                   compute_score_update: bool = True):
        """Per-tree program for the non-persistent path. Returns
        (tree arrays dict, leaf_of_row [n] in ORIGINAL row order or
        None). ``codes_planes`` / ``mv`` are the RESIDENT row-order
        planes ([code_planes, R] / slot-major [K, n]), ``grad`` /
        ``hess`` are in row order and ``n_valid`` (traced) counts the
        real rows among them. Either way the planar state is laid out
        from the resident planes as they lie. ``in_bag`` None, a static
        fact: every row is in the tree (a ranking or custom objective,
        multiclass, DART, GOSS before sampling starts) and that state is
        the tree's (`lgbm.build_state`). Otherwise ``in_bag`` is the
        bag as a [n] bool flag (bagging, GOSS, RF) and ONE stable pass
        of the partition kernel over all rows compacts the flagged rows
        to the front (`lgbm.bag_gather`, _compact_bag): its left count
        is the bag's size, whatever that is, so one program serves
        every bag. Every row's leaf comes from replaying the tree's
        splits over the resident planes (`lgbm.row_traverse`): one
        path, and no row-sized scatter of the partition's leaf
        assignment back to row order. The data-parallel grower runs
        this same function per shard, on the shard's slice of the
        flag."""
        n = self.layout.num_rows
        if in_bag is None:
            with jax.named_scope("lgbm.build_state"):
                data = plane.build_data(self.layout, codes_planes, grad,
                                        hess, mv=mv)
            bag_cnt = n_valid
        else:
            with jax.named_scope("lgbm.bag_gather"):
                data, bag_cnt = self._compact_bag(
                    codes_planes, grad, hess, in_bag, n_valid, mv)
        ta, st = self._grow_tree_core(data, bag_cnt, feature_mask)

        leaf_of_row = None
        if compute_score_update:
            with jax.named_scope("lgbm.row_traverse"):
                leaf_of_row = self.traverse_planes(ta, codes_planes)[:n]
        return ta, leaf_of_row

    def _compact_bag(self, codes_planes, grad, hess, in_bag, n_valid,
                     mv=None):
        """(planar state with the bag in lanes [0, count) in ascending
        row order, count): the state of ALL rows, the flag riding the
        row-id plane's sign bit (set = left out; pad lanes too), then a
        stable split of the window [0, n_valid) on that bit by the
        grower's own partition kernel at its largest tile — to the
        kernel a split like any other, routed by scalars. No per-index
        gather and no permutation: the rows left out end up behind the
        bag, outside every window of the tree."""
        Ly = self.layout
        lanes = jnp.arange(Ly.num_lanes, dtype=jnp.int32)
        keep = jnp.pad(in_bag, (0, Ly.num_lanes - in_bag.shape[0]))
        data = plane.build_data(
            Ly, codes_planes, grad, hess, mv=mv,
            rowid=jnp.where(keep, lanes, lanes | jnp.int32(-1 << 31)))
        return self._partition(data, jnp.int32(0), n_valid,
                               plane.sign_route_scalars(Ly.rowid))

    @staticmethod
    def _count_tree_layout(in_bag) -> None:
        """One tree dispatched, by how its rows are laid out: a host
        counter, no sync."""
        from .. import obs
        reg = obs.active()
        if reg is not None:
            reg.inc("fused.full_state_builds" if in_bag is None
                    else "fused.bag_compactions")

    def grow_device(self, grad, hess, in_bag=None,
                    compute_score_update=True):
        """Returns (tree_arrays dict of device arrays, leaf_of_row).
        ``in_bag``: [n] bool device flag of the rows the tree is grown
        on, None for all of them."""
        self._count_tree_layout(in_bag)
        ta, leaf = self._grow_jit(self._tables(), self.codes_planes(),
                                  grad, hess, in_bag,
                                  jnp.int32(self.actual_rows),
                                  self.feature_masks_for_tree(),
                                  self._mv_dev,
                                  compute_score_update=compute_score_update)
        if leaf is not None and leaf.shape[0] != self.actual_rows:
            # row-bucketed layout: the traverse covers the pad rows too
            leaf = leaf[:self.actual_rows]
        return ta, leaf

    # -- persistent mode -----------------------------------------------
    def init_persistent_state(self, score_vec) -> jax.Array:
        """Planar state carrying label/score/row-id across iterations.
        score_vec: [n] f32 current raw scores in ORIGINAL row order."""
        assert self.persistent_capable
        aux_label, aux_weight = self.objective.persistent_aux()
        codes_planes = self.codes_planes()
        with span("fused/build_data", stage="state/build_data"):
            data = plane.build_data(
                self.layout, codes_planes,
                jnp.zeros(self.layout.num_rows, jnp.float32),
                jnp.zeros(self.layout.num_rows, jnp.float32),
                label=jnp.asarray(aux_label, jnp.float32),
                score=jnp.asarray(score_vec, jnp.float32),
                weight=(None if aux_weight is None
                        else jnp.asarray(aux_weight, jnp.float32)),
                mv=self._mv_dev)
        # the persistent program carries the codes INSIDE `data`; the
        # cached planes copy would sit in HBM for nothing (3.9 GB at
        # the Allstate shape, next to the state and the partition
        # scratch). Drop it — the per-tree path rebuilds lazily.
        self._codes_planes_dev = None
        return data

    def _train_iter(self, data, feature_mask, shrinkage, bias,
                    n_valid=None, key=None):
        """One full boosting iteration in ONE program: gradients from
        the in-state score, tree growth, and the score update — all in
        leaf-permuted lane order (GBDT::TrainOneIter, gbdt.cpp:337,
        minus the host loop). ``n_valid`` overrides the static row
        count (traced, for per-shard row counts under shard_map).
        ``key``: per-iteration PRNG key for the stochastic rounding of
        the quantized pass (required when use_quantized_grad)."""
        Ly = self.layout
        n = jnp.int32(Ly.num_rows) if n_valid is None \
            else jnp.asarray(n_valid, jnp.int32)
        lanes = jnp.arange(Ly.num_lanes, dtype=jnp.int32)
        realm = lanes < n  # pad lanes never enter any window

        qscales = None
        with jax.named_scope("lgbm.grad"):
            score = plane.get_f32(data, Ly.score)
            label = plane.get_f32(data, Ly.label)
            weight = (plane.get_f32(data, Ly.weight) if Ly.weight >= 0
                      else None)
            g, h = self.objective.persistent_grads(score, label, weight)
            g = jnp.where(realm, g, 0.0)
            h = jnp.where(realm, h, 0.0)
            if self._quant:
                # per-iteration device quantization pass: the grad plane
                # carries the packed (qg << 16 | qh) words bitcast
                # through the f32 lanes, the hess plane zeros (the
                # kernels unpack both levels from the one word). Scales
                # psum-max across shards so every shard quantizes on the
                # same grid and the int32 histogram psums stay coherent.
                gmax = self._psum_max(jnp.max(jnp.abs(g)))
                hmax = self._psum_max(jnp.max(h))
                qg, qh, gs, hs = Q.quantize_gradients(
                    g, h, self.config.num_grad_quant_bins, key,
                    stochastic=self.config.stochastic_rounding,
                    grad_max=gmax, hess_max=hmax)
                qscales = (gs, hs)
                packed = plane.i32_as_f32(Q.pack_gh(qg, qh))
                data = plane.set_gh_packed(data, Ly, packed)
            else:
                data = plane.set_gh(data, Ly, g, h)

        ta, st = self._grow_tree_core(data, n, feature_mask,
                                      qscales=qscales)

        renew = (self.objective.persistent_renew_spec()
                 if self.objective is not None else None)
        with jax.named_scope("lgbm.renew"):
            if renew is not None:
                # leaf refit BEFORE shrinkage, like the reference's
                # RenewTreeOutput -> Shrinkage order (gbdt.cpp:379-386)
                alpha, weighted = renew
                ta = dict(ta, leaf_value=self._renew_leaf_outputs(
                    st, n, alpha, weighted))
            elif self._quant and self.config.quant_train_renew_leaf:
                # RenewIntGradTreeOutput (gradient_discretizer.cpp): leaf
                # values recomputed from the RAW f32 gradient sums so the
                # rounding error of the quantized split search never
                # enters the model output. The raw grads are recomputed
                # from the (permuted, but value-unchanged) score/label
                # planes of the FINAL state — pre-growth g/h are in
                # pre-partition lane order and would pair with the wrong
                # windows.
                ta = dict(ta, leaf_value=self._renew_quant_leaves(st, n))

        with jax.named_scope("lgbm.score_update"):
            vals = ta["leaf_value"] * shrinkage
            add = self._score_add_by_pos(st, vals.astype(jnp.float32))
            score2 = plane.get_f32(st.data, Ly.score) + add + bias
            data = plane.set_f32(st.data, Ly.score, score2)
        return data, ta

    def _next_quant_key(self):
        """[2] u32 stochastic-rounding key of the next iteration, from
        the host-side iteration counter (deterministic across runs; each
        boosting iteration gets a fresh fold_in of the base key)."""
        Q.note_requantize(self.config.num_grad_quant_bins)
        i = self._quant_iter
        self._quant_iter += 1
        return jax.random.fold_in(self._quant_base_key, jnp.uint32(i))

    def train_iter_persistent(self, data, shrinkage, bias):
        args = (self._tables(), data, self.feature_masks_for_tree(),
                jnp.float32(shrinkage), jnp.float32(bias),
                jnp.int32(self.actual_rows))
        if self._quant:
            # extra key arg ONLY under quant: the default path's call
            # arity (and so its cached executables) stays identical
            return self._iter_jit(*args, self._next_quant_key())
        return self._iter_jit(*args)

    def _sync_scores(self, data):
        n = self.layout.num_rows
        rowids = data[self.layout.rowid][:n]
        score = plane.get_f32(data, self.layout.score)[:n]
        return jnp.zeros(n, jnp.float32).at[rowids].set(
            score, unique_indices=True)

    def sync_scores(self, data) -> jax.Array:
        """[n] f32 raw scores in original row order (one scatter — only
        runs when a host consumer asks)."""
        out = self._sync_jit(data)
        if self._num_rows_override is None \
                and out.shape[0] != self.actual_rows:
            # bucketed layout: pad lanes landed beyond the real rows
            out = out[:self.actual_rows]
        return out

    # -- checkpoint/resume (robust/checkpoint.py) ----------------------
    def persistent_lane_state(self, data):
        """(rowid_lanes, score_bits) — the two planes of the persistent
        state that evolve irrecoverably. The LANE ORDER is part of the
        numeric state (histogram and score accumulation follow it), so
        checkpointing row-order scores would not resume bit-identically;
        every other plane is a pure function of the dataset gathered
        through the rowid plane and is rebuilt on restore."""
        Ly = self.layout
        # tpulint: sync-ok(checkpoint capture; periodic, off the iteration path)
        rowid, score_bits = jax.device_get([data[Ly.rowid], data[Ly.score]])
        return np.asarray(rowid, np.int32), np.asarray(score_bits, np.int32)

    def restore_persistent_state(self, rowid_lanes, score_bits) -> jax.Array:
        """Rebuild the planar state from a checkpoint's lane planes.
        Partitions only permute lanes within [0, actual_rows), so codes
        / label / weight at lane j equal the dataset values of row
        rowid[j]; grad/hess are dead between iterations (set_gh
        overwrites them before any read); the score plane is restored
        bit-exactly from the saved words."""
        assert self.persistent_capable
        Ly = self.layout
        n = self.actual_rows
        rid = jnp.asarray(np.asarray(rowid_lanes, np.int32))
        rid_n = rid[:n]
        aux_label, aux_weight = self.objective.persistent_aux()
        # gathered and packed on the host, like the first build
        cp = plane.build_codes_planes(
            np.asarray(self.dataset.bins)[np.asarray(rowid_lanes,
                                                     np.int32)[:n]], Ly)
        lab = jnp.asarray(aux_label, jnp.float32)[rid_n]
        wgt = None if aux_weight is None \
            else jnp.asarray(aux_weight, jnp.float32)[rid_n]
        zeros = jnp.zeros(n, jnp.float32)
        mv = None if self._mv_dev is None else self._mv_dev[:, rid_n]
        data = plane.build_data(Ly, cp, zeros, zeros, rowid=rid,
                                label=lab, score=zeros, weight=wgt,
                                mv=mv)
        data = data.at[Ly.score].set(
            jnp.asarray(np.asarray(score_bits, np.int32)))
        self._codes_planes_dev = None
        return data

    # ------------------------------------------------------------------
    def traverse_bins(self, ta, bins) -> jax.Array:
        """Leaf index for every row of a ROW-MAJOR bin table via
        bin-space traversal of the freshly built tree: validation-set
        score updates, whose tables are not planar. The training rows'
        leaves come from traverse_planes."""
        n = bins.shape[0]
        node = jnp.where(ta["n_leaves"] > 1, 0, -1) * jnp.ones(n, jnp.int32)
        miss_tbl = self.feature_miss_bin
        efb = self._efb_dev

        def gather_bin(f):
            if efb is None:
                return jnp.take_along_axis(
                    bins, f[:, None], axis=1)[:, 0].astype(jnp.int32)
            group_of, offset_of, nslots_of, skip_of = efb
            codes = jnp.take_along_axis(
                bins, group_of[f][:, None], axis=1)[:, 0].astype(jnp.int32)
            rel = codes - offset_of[f]
            inband = (rel >= 0) & (rel < nslots_of[f])
            dec = rel + (rel >= skip_of[f])
            return jnp.where(inband, dec, skip_of[f]).astype(jnp.int32)

        def cond(node):
            return jnp.any(node >= 0)

        def body(node):
            nid = jnp.maximum(node, 0)
            f = ta["split_feature"][nid]
            b = gather_bin(f)
            thr = ta["threshold_bin"][nid]
            mb = miss_tbl[f]
            go_left = b <= thr
            is_missing = (b == mb) & (mb >= 0)
            go_left = jnp.where(is_missing, ta["default_left"][nid], go_left)
            if self.any_categorical:
                words = ta["split_bits"][nid]          # [N, 8]
                word = jnp.take_along_axis(
                    words, (b >> 5)[:, None], axis=1)[:, 0]
                cat_left = ((word >> (b & 31)) & 1) == 1
                go_left = jnp.where(ta["split_cat"][nid], cat_left, go_left)
            nxt = jnp.where(go_left, ta["left_child"][nid],
                            ta["right_child"][nid])
            return jnp.where(node < 0, node, nxt)

        node = jax.lax.while_loop(cond, body, node)
        return -node - 1

    @property
    def row_traverse_method(self) -> str:
        """What replays a tree's splits over the resident planes under
        row sampling: the Pallas kernel where the partition's run."""
        return "xla" if self._part_method == "ref" else "pallas"

    def traverse_planes(self, ta, codes_planes) -> jax.Array:
        """Leaf index of every lane of the resident planar codes (lane
        r = row r, out-of-bag rows included): the score update's side
        of GBDT::UpdateScore under row sampling. The tree's splits are
        replayed in the order they were made, each with the partition's
        own routing (plane.route_scalars: EFB decode, missing bin,
        categorical bitset) — no per-row gather and no row-major table.
        Where the Pallas kernels run, one kernel makes one pass over
        the planes with the splits as its inner loop; elsewhere the
        splits are the outer loop of a pass each."""
        if self.row_traverse_method == "xla":
            return plane.traverse_planes_ref(
                codes_planes, self.layout, ta, self.feature_miss_bin,
                self._efb_dev)
        table = plane.traverse_table(self.layout, ta, self.feature_miss_bin,
                                     self._efb_dev)
        return plane.traverse_planes_pallas(codes_planes, table,
                                            interpret=self._interpret)

    # ------------------------------------------------------------------
    def _tree_mask_np(self) -> np.ndarray:
        f = self.num_features
        mask = np.ones(f, dtype=bool)
        frac = self.config.feature_fraction
        if frac < 1.0:
            k = max(1, int(np.ceil(frac * f)))
            chosen = self._col_rng.choice(f, size=k, replace=False)
            mask[:] = False
            mask[chosen] = True
        return mask

    def feature_mask_tree(self) -> jax.Array:
        if self.config.feature_fraction >= 1.0:
            # constant all-ones mask: upload ONCE. A fresh jnp.asarray
            # per iteration is a host->device transfer on the dispatch
            # path of every tree
            if getattr(self, "_mask_ones_dev", None) is None:
                self._mask_ones_dev = jnp.ones(self.num_features,
                                               dtype=bool)
            return self._mask_ones_dev
        return jnp.asarray(self._tree_mask_np())

    def feature_masks_for_tree(self) -> jax.Array:
        """Per-tree scan masks: [F] (by-tree sampling only) or
        [2L, F] per-scan-event masks when feature_fraction_bynode < 1
        (col_sampler.hpp GetByNode semantics: a fresh k-subset of the
        tree's selected features per candidate node; event 0 = root
        scan, events 2*new_leaf-1 / 2*new_leaf = the two children of
        the split that created leaf slot new_leaf). The shape is a
        static trace-time branch in _grow_tree_core."""
        frac = self.config.feature_fraction_bynode
        if frac >= 1.0:
            return self.feature_mask_tree()
        tm = self._tree_mask_np()
        idx = np.flatnonzero(tm)
        k = max(1, int(np.ceil(frac * len(idx))))
        E = 2 * self.num_leaves
        masks = np.zeros((E, self.num_features), dtype=bool)
        for e in range(E):
            masks[e, self._col_rng.choice(idx, size=k, replace=False)] = True
        return jnp.asarray(masks)

    def _valid_traverse_jit(self, ta, bins):
        """Jitted traversal for valid-set score updates; dispatches
        through the compile manager so same-signature boosters reuse
        one executable per valid-set shape."""
        return self._trav_jit(self._tables(), ta, bins)

    def materialize_tree(self, tree_arrays: Dict) -> Tree:
        """Device tree arrays → host Tree (real feature ids, real
        thresholds, decision_type bits). One synchronous fetch."""
        ta = {k: np.asarray(v) for k, v in tree_arrays.items()}
        k = int(ta["n_leaves"])
        tree = Tree(self.num_leaves)
        tree.num_leaves = k
        ni = max(k - 1, 0)
        mappers = self.dataset.bin_mappers
        real_idx = self.dataset.real_feature_index
        inner_feat = ta["split_feature"][:ni]
        tree.split_feature_inner[:ni] = inner_feat
        tree.split_feature[:ni] = [real_idx[f] for f in inner_feat]
        tree.threshold_in_bin[:ni] = ta["threshold_bin"][:ni]
        cat_flags = ta.get("split_cat")
        tree.threshold[:ni] = [
            0.0 if (cat_flags is not None and bool(cat_flags[i]))
            else mappers[f].bin_to_value(int(tb))
            for i, (f, tb) in enumerate(zip(inner_feat,
                                            ta["threshold_bin"][:ni]))]
        from ..models.tree import _to_bitset
        dt = np.zeros(max(ni, 1), dtype=np.int8)
        cat_nodes = ta.get("split_cat")
        for i, f in enumerate(inner_feat):
            if cat_nodes is not None and bool(cat_nodes[i]):
                # reconstruct the left-category sets from the device
                # bitset (Tree::Split categorical case, tree.cpp:70-91)
                words = np.asarray(ta["split_bits"][i], dtype=np.uint32)
                bin_set = [b for b in range(mappers[f].num_bin)
                           if (words[b >> 5] >> (b & 31)) & 1]
                cat_vals = sorted(
                    mappers[f].bin_2_categorical[b] for b in bin_set
                    if mappers[f].bin_2_categorical[b] >= 0)
                dt[i] = np.int8(np.uint8(
                    K_CATEGORICAL_MASK
                    | ((mappers[f].missing_type & 3) << 2)))
                tree.threshold_in_bin[i] = tree.num_cat
                tree.threshold[i] = tree.num_cat
                tree.num_cat += 1
                bits_inner = _to_bitset(bin_set)
                bits_raw = _to_bitset(cat_vals)
                tree.cat_boundaries_inner.append(
                    tree.cat_boundaries_inner[-1] + len(bits_inner))
                tree.cat_threshold_inner.extend(bits_inner)
                tree.cat_boundaries.append(
                    tree.cat_boundaries[-1] + len(bits_raw))
                tree.cat_threshold.extend(bits_raw)
            else:
                dt[i] = np.int8((2 if ta["default_left"][i] else 0) |
                                ((mappers[f].missing_type & 3) << 2))
        tree.decision_type[:ni] = dt[:ni]
        tree.left_child[:ni] = ta["left_child"][:ni]
        tree.right_child[:ni] = ta["right_child"][:ni]
        tree.split_gain[:ni] = ta["split_gain"][:ni]
        tree.internal_value[:ni] = ta["internal_value"][:ni]
        tree.internal_weight[:ni] = ta["internal_weight"][:ni]
        tree.internal_count[:ni] = ta["internal_count"][:ni]
        tree.leaf_value[:k] = ta["leaf_value"][:k]
        tree.leaf_weight[:k] = ta["leaf_weight"][:k]
        tree.leaf_count[:k] = ta["leaf_count"][:k]
        tree.leaf_depth[:k] = ta["leaf_depth"][:k]
        return tree

    def replay_arrays(self, tree: Tree) -> Dict:
        """The tree arrays a replay reads (REPLAY_KEYS, at this grower's
        num_leaves) of a host Tree: what materialize_tree turned into the
        tree, back again, for a tree that is no longer pending (restored
        from a checkpoint, materialized by a host consumer). Its leaf and
        internal values are the tree's as they stand now."""
        L, n = self.num_leaves, tree.num_leaves - 1
        dt = np.asarray(tree.decision_type[:n]).astype(np.uint8)
        cat = (dt & K_CATEGORICAL_MASK) != 0
        bits = np.zeros((L - 1, plane.CAT_WORDS), np.int32)
        for i in np.flatnonzero(cat):
            c = int(tree.threshold_in_bin[i])
            lo, hi = tree.cat_boundaries_inner[c:c + 2]
            words = np.asarray(tree.cat_threshold_inner[lo:hi], np.uint64)
            bits[i, :len(words)] = words.astype(np.uint32).view(np.int32)

        def nodes(a, dtype):
            out = np.zeros(L - 1, dtype)
            out[:n] = np.asarray(a[:n])
            return out

        leaf = np.zeros(L, np.float32)
        leaf[:n + 1] = tree.leaf_value[:n + 1]
        return {"n_leaves": np.int32(tree.num_leaves),
                "split_feature": nodes(tree.split_feature_inner, np.int32),
                "threshold_bin": nodes(tree.threshold_in_bin, np.int32),
                "default_left": nodes((dt & K_DEFAULT_LEFT_MASK) != 0, bool),
                "split_cat": nodes(cat, bool), "split_bits": bits,
                "left_child": nodes(tree.left_child, np.int32),
                "right_child": nodes(tree.right_child, np.int32),
                "leaf_value": leaf,
                "internal_value": nodes(tree.internal_value, np.float32)}


class PendingTree:
    """Lazily-materialized device tree: keeps the raw device arrays until
    a host consumer needs a real Tree, so the training loop never blocks
    on a device→host fetch. Any Tree attribute access (num_leaves,
    to_string, leaf_index_raw, ...) transparently materializes the host
    Tree once and delegates to it, so consumers that read GBDT.models
    directly keep working without an explicit materialize pass.

    ``tree_arrays`` is the grow program's output dict: device arrays
    until GBDT._materialize_models swaps in their host copies. Until
    then the tree's output is ``leaf_value * pending_shrinkage +
    pending_bias``, and the calls that made it are kept in order, so a
    late materialize() repeats exactly the float64 steps an early one
    would have been put through."""

    def __init__(self, grower: FusedSerialGrower, tree_arrays: Dict) -> None:
        self._tree: Optional[Tree] = None
        self.grower = grower
        self.tree_arrays = tree_arrays
        self.pending_shrinkage = 1.0
        self.pending_bias = 0.0
        self._pending_ops: list = []
        # host-cached leaf count (GBDT._batched_tree_stats): immutable
        # once the tree is grown, so one batched fetch serves forever
        self._n_leaves_host: Optional[int] = None

    def apply_shrinkage(self, rate: float) -> None:
        """Tree::Shrinkage: the whole output, a bias already added too."""
        if self._tree is not None:
            self._tree.apply_shrinkage(rate)
        else:
            self.pending_shrinkage *= rate
            self.pending_bias *= rate
            self._pending_ops.append(("apply_shrinkage", rate))

    def add_bias(self, val: float) -> None:
        if self._tree is not None:
            self._tree.add_bias(val)
        else:
            self.pending_bias += val
            self._pending_ops.append(("add_bias", val))

    def leaf_values_device(self):
        if self._tree is not None:
            return self._tree.leaf_values_device()
        return (self.tree_arrays["leaf_value"] * self.pending_shrinkage
                + self.pending_bias)

    def materialize(self) -> Tree:
        if self._tree is None:
            tree = self.grower.materialize_tree(self.tree_arrays)
            for op, val in self._pending_ops:
                getattr(tree, op)(val)
            self._tree = tree
        return self._tree

    def __getattr__(self, name: str):
        # only reached when normal lookup fails → a Tree attribute;
        # materialize once and delegate. Guard against recursion during
        # unpickling/copy before __init__ has run.
        if name.startswith("__") or name in ("_tree", "grower", "tree_arrays",
                                             "pending_shrinkage",
                                             "pending_bias", "_pending_ops"):
            raise AttributeError(name)
        return getattr(self.materialize(), name)


# what a replay reads of a tree's arrays (plane.traverse_table and
# plane.replay_values)
REPLAY_KEYS = ("n_leaves", "split_feature", "threshold_bin", "default_left",
               "split_cat", "split_bits", "left_child", "right_child",
               "leaf_value", "internal_value")
# trees a forest's tables grow by: the replay compiles once per step, so
# a run of a few hundred trees never compiles it inside a timed stretch
FOREST_STEP = 512


def _forest_put(routes, values, t, ta, miss, efb, *, layout):
    """Row ``t`` of the forest's tables: the tree's traverse_table and
    replay_values, in place (the tables are donated). Under the scope the
    grow program builds the same table in, `lgbm.row_traverse`: XLA gives
    the two programs' table ops the same names, and a trace read by
    instruction name then books them to one scope either way."""
    with jax.named_scope("lgbm.row_traverse"):
        tb = plane.traverse_table(layout, ta, miss, efb)
        vv = plane.replay_values(ta)
        routes = jax.lax.dynamic_update_slice(routes, tb[None], (t, 0))
        values = jax.lax.dynamic_update_slice(values, vv[None], (t, 0))
    return routes, values


_FOREST_PUT: list = []


def _forest_put_entry():
    if not _FOREST_PUT:
        from ..compile import get_manager
        jitted = jax.jit(_forest_put, static_argnames=("layout",),  # tpulint: jit-ok(registered by jit_entry on the next line; the manager counts its compiles)
                         donate_argnums=(0, 1))
        _FOREST_PUT.append(get_manager().jit_entry(
            "fused/forest_put", jitted, donate_argnums=(0, 1)))
    return _FOREST_PUT[0]


class ForestTables:
    """The replay tables of a forest's trees, resident on the device:
    row t of ``routes`` [capacity, W] i32 is tree t's traverse_table and
    row t of ``values`` [capacity, Wv] f32 its replay_values, as the
    tree was grown (the boosting layer keeps each tree's scale on the
    host). ``count`` rows are filled; the capacity grows in steps of
    ``step`` trees. plane.replay_forest_* read them."""

    def __init__(self, grower: FusedSerialGrower,
                 step: int = FOREST_STEP) -> None:
        self.grower = grower
        self.step = step
        self.width = plane.replay_widths(grower.num_leaves)
        self.routes = jnp.zeros((0, self.width[0]), jnp.int32)
        self.values = jnp.zeros((0, self.width[1]), jnp.float32)
        self.count = 0

    def reserve(self, n: int) -> None:
        cap = -(-n // self.step) * self.step
        have = self.routes.shape[0]
        if cap > have:
            grow = ((0, cap - have), (0, 0))
            self.routes = jnp.pad(self.routes, grow)
            self.values = jnp.pad(self.values, grow)

    def put(self, t: int, tree_arrays: Dict) -> None:
        """Tree ``t``'s row from its tree arrays (device or host)."""
        g = self.grower
        ta = {k: tree_arrays[k] for k in REPLAY_KEYS}
        self.routes, self.values = _forest_put_entry()(
            self.routes, self.values, jnp.int32(t), ta,
            g.feature_miss_bin, g._efb_dev, layout=g.layout)
