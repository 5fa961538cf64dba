"""GBDT boosting driver and variants (DART, GOSS, RF).

TPU re-design of the reference boosting layer (reference:
src/boosting/gbdt.cpp — Init :42, TrainOneIter :337, BoostFromAverage
:312, UpdateScore :458, RollbackOneIter :421; goss.hpp:25; dart.hpp:23;
rf.hpp:25; model text IO gbdt_model_text.cpp:306 SaveModelToString /
:410 LoadModelFromString).

Scores live on-device as [num_tree_per_iteration, N] float32 arrays; a
tree's contribution is applied with one vectorized binned traversal +
leaf-value gather (replacing ScoreUpdater::AddScore's partition-indexed
adds, score_updater.hpp:88). Objective gradient computation is a jitted
program over the score array. The host drives the iteration loop.
"""
from __future__ import annotations

import collections
import functools
import os
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np
import jax
import jax.numpy as jnp

from ..config import Config
from ..io.dataset import BinnedDataset
from ..io.binning import BIN_CATEGORICAL
from ..models.tree import Tree
from ..objective.functions import ObjectiveFunction
from ..metric.metrics import Metric
from ..obs import span as obs_span
from ..treelearner.serial import SerialTreeGrower
from ..utils import log

K_EPSILON = 1e-15
K_MODEL_VERSION = "v3"
# DART draws kept on the host for readers: (iteration, dropped iterations)
DROP_HISTORY = 1024


def parse_tree_blocks(text: str) -> List[Tree]:
    """The Tree= blocks of a model text as host Trees (shared by
    load_model_from_string and checkpoint resume — resume rebuilds the
    forest from the checkpointed model text instead of re-predicting,
    because Tree text round-trips bit-exactly via repr())."""
    body = text[text.index("tree_sizes="):]
    out = []
    for blk in body.split("Tree=")[1:]:
        blk = blk.split("end of trees")[0]
        out.append(Tree.from_string(blk.partition("\n")[2]))
    return out


def _pack_rng(rng: np.random.RandomState) -> dict:
    kind, keys, pos, has_gauss, cached = rng.get_state()
    return {"kind": kind, "keys": np.asarray(keys, dtype=np.uint32),
            "pos": int(pos), "has_gauss": int(has_gauss),
            "cached": float(cached)}


def _unpack_rng(rng: np.random.RandomState, state: dict) -> None:
    rng.set_state((state["kind"], np.asarray(state["keys"], np.uint32),
                   int(state["pos"]), int(state["has_gauss"]),
                   float(state["cached"])))


class _ScoreState:
    """Per-dataset score accumulator (reference score_updater.hpp:21)."""

    def __init__(self, dataset: BinnedDataset, num_trees_per_iter: int) -> None:
        self.dataset = dataset
        self.num_data = dataset.num_data
        init = np.zeros((num_trees_per_iter, dataset.num_data), dtype=np.float32)
        if dataset.metadata.init_score is not None:
            isc = np.asarray(dataset.metadata.init_score, dtype=np.float32)
            init += isc.reshape(num_trees_per_iter, dataset.num_data)
            self.has_init_score = True
        else:
            self.has_init_score = False
        self.score = jnp.asarray(init)

    def add_constant(self, val: float, class_id: int) -> None:
        self.score = self.score.at[class_id].add(jnp.float32(val))

    def add_tree(self, tree: Tree, class_id: int, miss_bin_map: np.ndarray) -> None:
        leaf_idx = tree.leaf_index_binned(self.dataset.device_bins(), miss_bin_map,
                                          efb=self.dataset.device_bundle_tables())
        vals = tree.leaf_values_device()
        self.score = self.score.at[class_id].add(vals[leaf_idx])


class GBDT:
    """The boosting driver (reference gbdt.h:34)."""

    def __init__(self) -> None:
        self.models: List[Tree] = []
        self.iter = 0
        self.num_init_iteration = 0
        self.config: Optional[Config] = None
        self.train_data: Optional[BinnedDataset] = None
        self.objective: Optional[ObjectiveFunction] = None
        self.metrics: List[Metric] = []
        self.valid_metrics: List[List[Metric]] = []
        self.valid_score: List[_ScoreState] = []
        self.best_iter = 0
        self.average_output = False
        self.loaded_parameter = ""
        self.feature_names_: List[str] = []
        self.label_idx = 0
        self._convert_jit = None  # jitted objective.convert_output
        self.predict_path: Optional[str] = None  # last predictor taken

    # ------------------------------------------------------------------
    def init(self, config: Config, train_data: BinnedDataset,
             objective: Optional[ObjectiveFunction],
             metrics: Sequence[Metric]) -> None:
        """reference GBDT::Init (gbdt.cpp:42)."""
        self.config = config
        self.train_data = train_data
        self.objective = objective
        self.num_data = train_data.num_data
        self.num_tree_per_iteration = (
            objective.num_tree_per_iteration if objective is not None
            else max(config.num_class, 1))
        self.shrinkage_rate = config.learning_rate
        self.metrics = list(metrics)
        self.max_feature_idx = train_data.num_total_features - 1
        self.feature_names_ = list(train_data.feature_names)

        if objective is not None:
            objective.init(train_data.metadata, self.num_data)
        for m in self.metrics:
            m.init(train_data.metadata, self.num_data)

        self.tree_learner = self._create_tree_learner(config, train_data)
        # fused single-dispatch path (treelearner/fused.py); the
        # host-loop grower covers what it rejects
        from ..treelearner.fused import (FusedSerialGrower,
                                         fused_reject_reason)
        self._fused = None
        self._fused_state = None     # persistent planar state (device)
        self._score_dirty = False    # train_score stale vs _fused_state
        # tree_learner=data is the fused learner over the visible chips:
        # sharded over the mesh where there are several (the persistent
        # path when eligible, the per-tree sharded path otherwise:
        # bagging, multiclass, custom fobj), and on one chip the serial
        # learner itself
        data_parallel = config.tree_learner == "data"
        cfg_fused = config
        if data_parallel:
            import copy as _copy
            cfg_fused = _copy.copy(config)
            cfg_fused.tree_learner = "serial"
        reason = fused_reject_reason(cfg_fused, train_data, objective)
        if reason is None and data_parallel and len(jax.devices()) > 1:
            from ..treelearner.parallel import FusedDataParallelGrower
            self._fused = FusedDataParallelGrower(
                train_data, config, objective)
        elif reason is None:
            # canonical row bucket (compile/signature.py): pads the
            # planar layout so same-bucket datasets share executables
            from ..compile import bucket_rows
            self._fused = FusedSerialGrower(
                train_data, cfg_fused, objective,
                num_rows_bucket=bucket_rows(train_data.num_data))
        if config.tree_learner != "serial" \
                and type(self.tree_learner) is SerialTreeGrower \
                and not getattr(self._fused, "is_multichip", False):
            log.warning("Only one machine/chip: using serial tree learner")
        if self._fused is None and jax.default_backend() == "tpu" \
                and reason not in (None, "tpu_fused=false") \
                and config.tree_learner in ("serial", "data"):
            # name the responsible option: the host-loop grower
            # dispatches >= 2 kernels per SPLIT instead of one program
            # per iteration
            log.warning(
                "Config option [%s] is not supported by the fused "
                "single-dispatch tree grower; falling back to the "
                "host-loop grower (~10x slower per iteration on TPU)",
                reason)
        # persistent single-program iterations: pointwise objective, one
        # tree per iteration, no bagging/GOSS/RF/DART score surgery
        self._fused_persist = (
            self._fused is not None and self._fused.persistent_capable
            and self._fused._score_from_partition
            and self.num_tree_per_iteration == 1
            and config.boosting == "gbdt" and type(self) is GBDT)
        # round-4: the sharded fused grower also covers the per-tree
        # path (bagging via per-shard local permutations, multiclass);
        # no more persistent-only restriction
        self._fused_check_every = 50
        # dispatch-ahead / fetch-behind pipelining (LGBM_TPU_PIPELINE=0
        # restores the fully synchronous loop — the parity reference):
        # the periodic stop-check readback trails one check period
        # behind its dispatch, so the host never blocks on it while
        # device work is in flight
        self._pipeline = os.environ.get("LGBM_TPU_PIPELINE", "1") != "0"
        self._stop_fetch = None    # in-flight trailing stop-check
        self._stop_pending = None  # drained-but-unconsumed stop verdict
        # device-side eval toggle: the degraded-mode ladder (rung 2)
        # clears it to force the host-eval fallback
        self._device_eval = True
        # numeric-health sentinels (robust/sentinel.py): per-tree
        # finiteness/overflow checks whose verdicts ride the existing
        # trailing fetches
        self._sentinel = None
        if config.numeric_sentinels:
            from ..robust.sentinel import NumericSentinel
            self._sentinel = NumericSentinel(
                overflow_limit=config.sentinel_overflow_limit,
                max_trips=config.sentinel_max_trips)
        self._poison_next = None   # train.iteration:nan/overflow drill
        self.train_score = _ScoreState(train_data, self.num_tree_per_iteration)
        self.class_need_train = [True] * self.num_tree_per_iteration

        # bagging state (reference GBDT::ResetBaggingConfig, gbdt.cpp:700)
        self._bag_rng = np.random.RandomState(config.bagging_seed)
        self._full_perm = jnp.arange(self.num_data, dtype=jnp.int32)
        self._set_bag(None, self.num_data)
        self._reset_boosting_state()

    def execution_plan(self) -> Dict[str, object]:
        """What this booster resolved to run on: the JAX backend and
        devices, the execution tier, and the histogram / partition
        kernels (engine.train logs it once per call)."""
        dev = jax.devices()
        if self._fused is None:
            tier = "host-loop"
        elif self._fused_persist:
            tier = "persistent-fused"
        else:
            tier = "per-tree-fused"
        grower = self._fused if self._fused is not None \
            else self.tree_learner
        from ..ops import histogram as H
        hist = (self._fused._hist_method if self._fused is not None
                else H.hist_method(self.config, self.train_data))
        plan = {"backend": jax.default_backend(),
                "device_kind": dev[0].device_kind, "device_count": len(dev),
                "tier": tier, "learner": type(grower).__name__,
                "hist": hist or "scatter",
                "partition": getattr(self._fused, "_part_method", "xla"),
                "sampling": self._sampling_plan()}
        if tier == "per-tree-fused" or plan["sampling"] is not None:
            # what gives every row its leaf after a tree of the per-tree
            # tier: the tree's splits replayed over the resident planes
            plan["row_traverse"] = getattr(self._fused,
                                           "row_traverse_method", "xla")
        if plan["sampling"] is not None:
            # how a sampled tree's rows reach lane order: the fused
            # tiers compact the bag flag with one pass of the partition
            # kernel, the host loop indexes by the permutation
            plan["bag_layout"] = ("partition-compaction"
                                  if self._fused is not None
                                  else "permutation")
        if self._fused is not None:
            # where the [code_planes, lanes] planes are packed ("host":
            # no device program, nothing to compile), and on a mesh the
            # rows each chip owns
            plan["codes_pack"] = self._fused.codes_pack
            if self._fused.is_multichip:
                plan["shard_rows"] = self._fused.shard_rows
        if getattr(self.objective, "need_group", False):
            # a ranking objective: the queries, their padded sizes and
            # the pair terms its gradient program evaluates an iteration
            plan["rank_grad"] = self.objective.rank_plan()
        return plan

    def _sampling_plan(self) -> Optional[str]:
        """The row sampling each tree is grown under, None without."""
        cfg = self.config
        by_label = (cfg.pos_bagging_fraction < 1.0
                    or cfg.neg_bagging_fraction < 1.0)
        if cfg.bagging_freq <= 0 or not (cfg.bagging_fraction < 1.0
                                         or by_label):
            return None
        if by_label:
            return (f"bagging(pos {cfg.pos_bagging_fraction:g}, neg "
                    f"{cfg.neg_bagging_fraction:g}/{cfg.bagging_freq})")
        return f"bagging({cfg.bagging_fraction:g}/{cfg.bagging_freq})"

    def _create_tree_learner(self, config: Config, train_data: BinnedDataset):
        if config.tree_learner in ("serial", "feature", "data", "voting"):
            if config.tree_learner != "serial" and config.num_machines <= 1 \
                    and not config.tpu_mesh_shape:
                # init() says so where no sharded fused grower takes over
                return SerialTreeGrower(train_data, config)
            if config.tree_learner == "serial":
                return SerialTreeGrower(train_data, config)
            from ..treelearner.parallel import create_parallel_learner
            return create_parallel_learner(config.tree_learner, train_data, config)
        log.fatal("Unknown tree learner type %s", config.tree_learner)

    def _reset_boosting_state(self) -> None:
        self._grad: Optional[jax.Array] = None
        self._hess: Optional[jax.Array] = None

    # ------------------------------------------------------------------
    def add_valid_data(self, valid_data: BinnedDataset,
                       metrics: Sequence[Metric]) -> None:
        for m in metrics:
            m.init(valid_data.metadata, valid_data.num_data)
        self.valid_metrics.append(list(metrics))
        self.valid_score.append(_ScoreState(valid_data, self.num_tree_per_iteration))

    # ------------------------------------------------------------------
    def _boost_from_average(self, class_id: int, update_scorer: bool) -> float:
        """reference GBDT::BoostFromAverage (gbdt.cpp:312)."""
        cfg = self.config
        if self.models or self.train_score.has_init_score or self.objective is None:
            return 0.0
        if cfg.boost_from_average or self.train_data.num_features == 0:
            init_score = self.objective.boost_from_score(class_id)
            if abs(init_score) > K_EPSILON:
                if update_scorer:
                    self.train_score.add_constant(init_score, class_id)
                    for vs in self.valid_score:
                        vs.add_constant(init_score, class_id)
                log.info("Start training from score %f", init_score)
                return init_score
        elif self.objective.name in ("regression_l1", "quantile", "mape"):
            log.warning("Disabling boost_from_average in %s may cause the slow convergence",
                        self.objective.name)
        return 0.0

    def _boosting(self) -> None:
        """Objective gradients from the current score (GBDT::Boosting,
        gbdt.cpp:151)."""
        if self.objective is None:
            log.fatal("No objective function provided")
        score = self.get_training_score()
        if self.num_tree_per_iteration == 1:
            g, h = self.objective.get_gradients(score[0])
            self._grad, self._hess = g[None, :], h[None, :]
        else:
            self._grad, self._hess = self.objective.get_gradients(score)

    def device_score_state(self):
        """The device array that per-iteration work actually updates —
        for block_until_ready in benchmarks/profilers."""
        if self._fused_state is not None:
            return self._fused_state
        return self.train_score.score

    def get_training_score(self) -> jax.Array:
        if self._score_dirty and self._fused_state is not None:
            # one scatter back to row order, only when a host consumer
            # (metrics, refit, rollback, custom fobj) actually asks
            self.train_score.score = \
                self._fused.sync_scores(self._fused_state)[None, :]
            self._score_dirty = False
        return self.train_score.score

    def _invalidate_fused_state(self) -> None:
        """Call after any direct train_score mutation (rollback, refit,
        DART normalize): the persistent planar state is rebuilt lazily
        from the synced scores on the next iteration."""
        if self._fused_state is not None:
            self.get_training_score()
            self._fused_state = None

    # ------------------------------------------------------------------
    def _set_bag(self, in_bag, count: int) -> None:
        """The rows the next trees are grown on: ``in_bag`` a [n] bool
        flag (host or device), None when no row is left out; ``count``
        how many it keeps."""
        if count >= self.num_data:
            in_bag = None
        self._in_bag = None if in_bag is None else jnp.asarray(in_bag)
        self.bag_data_cnt = int(count)
        self._perm_of_bag = None

    @property
    def _perm(self) -> jax.Array:
        """The bag as a permutation, [bag rows | the rest], ascending on
        both sides, for the consumers that index by it (the host-loop
        learners, a checkpoint): derived from the flag on first read,
        kept until the bag changes. The fused growers never ask."""
        if self._in_bag is None:
            return self._full_perm
        if self._perm_of_bag is None:
            flag = np.asarray(self._in_bag)
            self._perm_of_bag = jnp.asarray(np.concatenate(
                [np.flatnonzero(flag), np.flatnonzero(~flag)]
            ).astype(np.int32))
        return self._perm_of_bag

    def _bagging(self, iteration: int) -> None:
        """Per-iteration row subsetting (reference GBDT::Bagging,
        gbdt.cpp:209; pos/neg bagging for binary)."""
        cfg = self.config
        need = cfg.bagging_freq > 0 and (
            cfg.bagging_fraction < 1.0 or cfg.pos_bagging_fraction < 1.0
            or cfg.neg_bagging_fraction < 1.0)
        if not need or iteration % cfg.bagging_freq != 0:
            return
        n = self.num_data
        if cfg.pos_bagging_fraction != 1.0 or cfg.neg_bagging_fraction != 1.0:
            label = np.asarray(self.train_data.metadata.label)
            is_pos = label > 0
            r = self._bag_rng.rand(n)
            keep = np.where(is_pos, r < cfg.pos_bagging_fraction,
                            r < cfg.neg_bagging_fraction)
        else:
            cnt = max(1, int(n * cfg.bagging_fraction))
            keep = np.zeros(n, bool)
            keep[self._bag_rng.choice(n, size=cnt, replace=False)] = True
        self._set_bag(keep, int(keep.sum()))

    # ------------------------------------------------------------------
    def train_one_iter(self, gradients: Optional[np.ndarray] = None,
                       hessians: Optional[np.ndarray] = None) -> bool:
        """One boosting iteration (reference GBDT::TrainOneIter,
        gbdt.cpp:337). Returns True when training should stop."""
        # any model mutation invalidates the packed prediction forest
        self._pred_revision = getattr(self, "_pred_revision", 0) + 1
        k = self.num_tree_per_iteration
        init_scores = [0.0] * k
        custom_grad = gradients is not None and hessians is not None
        if not custom_grad:
            for c in range(k):
                init_scores[c] = self._boost_from_average(c, True)
            if not (self._fused_persist and self._fused is not None):
                with obs_span("gbdt/boosting (gradients)", phase="boost"):
                    self._boosting()
                self._apply_grad_poison()
        else:
            g = jnp.asarray(np.asarray(gradients, np.float32).reshape(k, self.num_data))
            h = jnp.asarray(np.asarray(hessians, np.float32).reshape(k, self.num_data))
            self._grad, self._hess = g, h

        self._sentinel_check_grads()
        self._bagging(self.iter)

        if self._fused is not None:
            if self._fused_persist and not custom_grad:
                return self._train_one_iter_persistent(init_scores)
            if self._fused_persist and custom_grad:
                # custom fobj supplies gradients in row order: leave the
                # persistent state and fall through to the per-tree path
                self._invalidate_fused_state()
            return self._train_one_iter_fused(init_scores)

        tl = self.tree_learner
        gh: list = []
        for c in range(k):
            if self.class_need_train[c] and self.train_data.num_features > 0:
                gh.append((self._grad[c], self._hess[c]))
                if hasattr(tl, "prefetch_quantize"):
                    # dispatch-ahead quantization: every class-tree's
                    # quantize (and its stochastic-rounding draw) is
                    # enqueued up front, so the packed plane for tree
                    # c+1 builds while tree c's host-driven growth —
                    # and its leaf-renewal readback — is still running
                    tl.prefetch_quantize(*gh[-1])
            else:
                gh.append((None, None))

        should_continue = False
        for c in range(k):
            if self.class_need_train[c] and self.train_data.num_features > 0:
                with obs_span("gbdt/grow_tree (host loop)", phase="grow"):
                    new_tree = self.tree_learner.grow(
                        gh[c][0], gh[c][1], self._perm,
                        self.bag_data_cnt)
            else:
                new_tree = Tree(2)
            if new_tree.num_leaves > 1:
                should_continue = True
                self._renew_tree_output(new_tree, c)
                new_tree.apply_shrinkage(self.shrinkage_rate)
                self._update_score(new_tree, c)
                if abs(init_scores[c]) > K_EPSILON:
                    new_tree.add_bias(init_scores[c])
            else:
                # constant-tree path (reference gbdt.cpp:389-407)
                if len(self.models) < k:
                    output = init_scores[c]
                    if not self.class_need_train[c] and self.objective is not None:
                        output = self.objective.boost_from_score(c)
                    new_tree.set_leaf_value(0, output)
                    self.train_score.add_constant(output, c)
                    for vs in self.valid_score:
                        vs.add_constant(output, c)
            self.models.append(new_tree)

        if not should_continue:
            if self._quarantine_degenerate_iter(k):
                return False
            log.warning("Stopped training because there are no more leaves "
                        "that meet the split requirements")
            if len(self.models) > k:
                del self.models[-k:]
            return True
        self._sentinel_check_trees(self.models[-k:])
        self.iter += 1
        return False

    def _apply_grad_poison(self) -> None:
        """``train.iteration:nan``/``overflow`` drill: poison one
        gradient element so the corruption propagates through histogram
        accumulation, split finding, and leaf values exactly like real
        divergence would (the sentinel must catch it downstream)."""
        mode, self._poison_next = self._poison_next, None
        if mode is None or self._grad is None:
            return
        bad = jnp.float32(float("nan") if mode == "nan" else 2e30)
        self._grad = self._grad.at[0, 0].set(bad)
        log.warning("fault injection: poisoned the gradient plane with %s "
                    "at iteration %d", mode, self.iter)

    def _sentinel_check_grads(self) -> None:
        """Gradient/hessian-plane health checks: async device reductions
        whose verdicts ride the trailing fetches like the leaf checks.
        The persistent fused path computes gradients in-program and is
        covered by its leaf-value checks instead."""
        if self._sentinel is None or self._grad is None:
            return
        with obs_span("sentinel health check (dispatch)", phase="sentinel"):
            self._sentinel.dispatch([self._grad, self._hess], self.iter)

    def _quarantine_degenerate_iter(self, k: int) -> bool:
        """An all-degenerate iteration is ALSO the exact signature of a
        poisoned gradient plane: NaN gains reject every split. Before
        declaring convergence, resolve the in-flight sentinel verdicts;
        when THIS iteration's gradient check tripped, discard its trees
        as a quarantine and keep training — the next iteration
        recomputes clean gradients from the untouched scores."""
        if self._sentinel is None:
            return False
        self.sentinel_drain()
        trips = self._sentinel.pop_trips()
        mine = [t for t in trips if t[0] == self.iter]
        others = [t for t in trips if t[0] != self.iter]
        if others:
            # earlier iterations' trips go back to the recovery policy
            self._sentinel._trips_out = others + self._sentinel._trips_out
        if not mine:
            return False
        del self.models[-k:]
        if not self.models:
            # iteration 0's boost_from_average constant was folded into
            # the scores before its trees were discarded
            self._rebuild_scores()
        from .. import obs
        reg = obs.active()
        if reg is not None:
            reg.inc("health.quarantined", k)
        log.warning(
            "numeric sentinel: quarantined the tree(s) of iteration %d "
            "(%s gradient plane); training continues", self.iter,
            mine[0][1])
        return True

    def _sentinel_check_trees(self, trees) -> None:
        """Numeric-health checks on this iteration's new trees
        (robust/sentinel.py). Device-resident leaf values get an async
        [nonfinite, overflow] reduction whose tiny verdict rides the
        NEXT trailing fetch; host trees are judged immediately. Costs
        zero extra blocking syncs either way."""
        sent = self._sentinel
        if sent is None:
            return
        from ..treelearner.fused import PendingTree
        arrays: list = []
        with obs_span("sentinel health check (dispatch)", phase="sentinel"):
            for t in trees:
                if isinstance(t, PendingTree) and t._tree is None:
                    arrays.append(t.tree_arrays["leaf_value"])
                else:
                    tree = t._tree if isinstance(t, PendingTree) else t
                    arrays.append(np.asarray(
                        tree.leaf_value[:max(tree.num_leaves, 1)],
                        dtype=np.float32))
            if arrays:
                sent.dispatch(arrays, self.iter)

    def _train_one_iter_persistent(self, init_scores) -> bool:
        """Persistent fused path: the ENTIRE boosting iteration
        (gradients, tree growth, score update) is one device program
        over the leaf-permuted planar state — no [N]-sized scatter, no
        repacking, zero synchronous host transfers."""
        from ..treelearner.fused import PendingTree
        if self._fused_state is None:
            # created AFTER _boost_from_average, so the state's score
            # already carries the init constant — in-program bias is 0
            # (the PendingTree still gets add_bias for the model)
            self._fused_state = self._fused.init_persistent_state(
                self.get_training_score()[0])
        self._fused_state, ta = self._fused.train_iter_persistent(
            self._fused_state, self.shrinkage_rate, 0.0)
        pending = PendingTree(self._fused, ta)
        if self.valid_score:
            vals = pending.leaf_values_device() * self.shrinkage_rate
            for vs in self.valid_score:
                vleaf = self._fused._valid_traverse_jit(
                    ta, vs.dataset.device_bins())
                vs.score = vs.score.at[0].add(vals[vleaf])
        self._score_dirty = True
        pending.apply_shrinkage(self.shrinkage_rate)
        if abs(init_scores[0]) > K_EPSILON:
            pending.add_bias(init_scores[0])
        self.models.append(pending)
        self._sentinel_check_trees(self.models[-1:])
        self.iter += 1
        if self.iter % self._fused_check_every == 0 and \
                self._periodic_stop_check(self.models[-1:]):
            return True
        return False

    def _train_one_iter_fused(self, init_scores) -> bool:
        """Fused path: one device dispatch per class-tree, zero
        synchronous host transfers (trees stay on device as PendingTree
        until a host consumer needs them)."""
        from ..treelearner.fused import PendingTree
        k = self.num_tree_per_iteration
        for c in range(k):
            ta, leaf_of_row = self._fused.grow_device(
                self._grad[c], self._hess[c], self._in_bag)
            pending = PendingTree(self._fused, ta)
            pending.apply_shrinkage(self.shrinkage_rate)
            vals = pending.leaf_values_device()
            self.train_score.score = _score_add_entry()(
                self.train_score.score, vals, leaf_of_row, class_id=c)
            for vs in self.valid_score:
                vleaf = self._fused._valid_traverse_jit(
                    ta, vs.dataset.device_bins())
                vs.score = _score_add_entry()(vs.score, vals, vleaf,
                                              class_id=c)
            if abs(init_scores[c]) > K_EPSILON:
                pending.add_bias(init_scores[c])
            self.models.append(pending)
        self._sentinel_check_trees(self.models[-k:])
        self.iter += 1
        # deferred no-more-splits detection: syncing every iteration
        # would stall the dispatch pipeline, so check periodically and
        # roll back ALL trailing degenerate iterations on detection
        if self.iter % self._fused_check_every == 0 and \
                self._periodic_stop_check(self.models[-k:]):
            return True
        return False

    def _periodic_stop_check(self, trees) -> bool:
        """Deferred no-more-splits detection shared by the fused paths.
        Pipelined (default): resolve the verdict whose readback was
        DISPATCHED at the previous check — it has been in flight for a
        whole check period, so the host never blocks on it — then kick
        off this period's readback. Stopping therefore trails detection
        by one period; the final model is unaffected because
        _trim_degenerate_tail removes ALL trailing degenerate
        iterations either way. LGBM_TPU_PIPELINE=0 restores the
        synchronous order (dispatch, then resolve immediately)."""
        if self._pipeline:
            stop = self._resolve_stop_check()
            self._begin_stop_check(trees)
        else:
            self._begin_stop_check(trees)
            stop = self._resolve_stop_check()
        if stop:
            trimmed = self._trim_degenerate_tail()
            if trimmed == 0 and \
                    len(self.models) > self.num_tree_per_iteration:
                # stale verdict: the window it covered was degenerate
                # but later iterations found splits again — keep going
                return False
            log.warning("Stopped training because there are no more "
                        "leaves that meet the split requirements")
            return True
        return False

    def _begin_stop_check(self, trees) -> None:
        """Start the leaf-count readback for ``trees`` without blocking:
        collect the same per-tree scalar refs _batched_tree_stats would
        and begin their device->host copy. _resolve_stop_check reads
        the verdict later (one check period later in steady state)."""
        from .. import obs
        from ..treelearner.fused import PendingTree
        refs: list = []
        counts: list = []
        for t in trees:
            if isinstance(t, PendingTree) and t._tree is None:
                if t._n_leaves_host is not None:
                    counts.append(int(t._n_leaves_host))
                    continue
                ref = t.tree_arrays["n_leaves"]
                try:
                    ref.copy_to_host_async()
                except Exception:
                    pass   # host copy is an optimization, not a contract
                refs.append((t, ref))
            else:
                tree = t._tree if isinstance(t, PendingTree) else t
                counts.append(int(tree.num_leaves))
        tr = obs.active_tracer()
        self._stop_fetch = (refs, counts, self.iter,
                            tr.iteration if tr is not None else -1)
        if refs:
            reg = obs.active()
            if reg is not None:
                reg.inc("pipeline.inflight_fetches")

    def _resolve_stop_check(self) -> bool:
        """Verdict of the previously dispatched stop check: True when
        every tree in that window was a single leaf. Returns False when
        nothing is in flight (first check of a run, or after resume)."""
        from .. import obs
        if self._stop_pending is not None:
            out, self._stop_pending = self._stop_pending, None
            return bool(out)
        if self._stop_fetch is None:
            return False
        refs, counts, disp_iter, disp_trace_iter = self._stop_fetch
        self._stop_fetch = None
        counts = list(counts)
        sent = self._sentinel
        s_pending = sent.take_pending() if sent is not None else []
        if refs or s_pending:
            from ..robust.watchdog import watch_phase
            with obs_span("trailing stop-check (readback)",
                          phase="stop_check"), \
                    obs.sync_attribution(disp_trace_iter), \
                    watch_phase("readback:stop check"):
                # tpulint: sync-ok(trailing-fetch: resolves the readback dispatched one check period earlier, already host-resident in steady state)
                vals = jax.device_get([r for _, r in refs] +
                                      [r for _, r in s_pending])
            for (t, _), v in zip(refs, vals):
                if t._n_leaves_host is None:
                    t._n_leaves_host = int(v)
                counts.append(int(v))
            if s_pending:
                # sentinel verdicts ride the same batched fetch
                sent.resolve(s_pending, vals[len(refs):])
        stop = bool(counts) and all(v <= 1 for v in counts)
        if stop and self.iter > disp_iter:
            reg = obs.active()
            if reg is not None:
                # iterations trained past the detected degenerate window
                # (all trimmed again by _trim_degenerate_tail)
                reg.inc("pipeline.delayed_stop_iters",
                        self.iter - disp_iter)
        return stop

    def _drain_stop_check(self) -> None:
        """Resolve any in-flight trailing stop-check and park the
        verdict for the next periodic check. Checkpoint capture and
        state restores call this: a checkpoint must not carry live
        device refs, and a positive verdict must survive resume."""
        if self._stop_fetch is not None:
            self._stop_pending = self._resolve_stop_check() or None

    def _tree_num_leaves(self, t) -> int:
        """Leaf count without forcing a full host materialization."""
        return self._batched_tree_stats([t])[0][0]

    def _batched_tree_stats(self, trees, with_gains: bool = False):
        """(leaf_counts, split_gain_arrays) for ``trees`` with at most
        ONE jax.device_get across all of them. The periodic stop check
        and the telemetry sampler both read these per tree; a per-tree
        fetch costs a device round trip each, so every unmaterialized
        tree's scalars ride one batched transfer and the leaf count is
        cached on the PendingTree (immutable once grown)."""
        from ..treelearner.fused import PendingTree
        refs: Dict = {}
        for i, t in enumerate(trees):
            if not (isinstance(t, PendingTree) and t._tree is None):
                continue
            if t._n_leaves_host is None:
                refs[(i, "n_leaves")] = t.tree_arrays["n_leaves"]
            if with_gains:
                refs[(i, "split_gain")] = t.tree_arrays["split_gain"]
        with obs_span("batched tree stats (device fetch)",
                      phase="stop_check"):
            # tpulint: sync-ok(batched tree stats, ONE transfer per stop check)
            fetched = jax.device_get(refs) if refs else {}
        counts, gains = [], []
        for i, t in enumerate(trees):
            if isinstance(t, PendingTree) and t._tree is None:
                if (i, "n_leaves") in fetched:
                    t._n_leaves_host = int(fetched[(i, "n_leaves")])
                counts.append(int(t._n_leaves_host))
                if with_gains:
                    g = np.asarray(fetched[(i, "split_gain")])
                    gains.append(g[:max(counts[-1] - 1, 0)])
            else:
                tree = t._tree if isinstance(t, PendingTree) else t
                counts.append(int(tree.num_leaves))
                if with_gains:
                    gains.append(np.asarray(
                        tree.split_gain[:max(tree.num_leaves - 1, 0)]))
        return counts, gains

    def telemetry_stats(self) -> Dict[str, float]:
        """Per-iteration model/memory stats for the obs layer (only
        called when telemetry is enabled — the PendingTree fetches here
        cost a device round trip the normal path never pays)."""
        k = self.num_tree_per_iteration
        stats: Dict[str, float] = {}
        best_gain = 0.0
        # one batched device fetch serves leaf counts AND gains of all
        # k class-trees of the iteration
        counts, gain_arrays = self._batched_tree_stats(
            self.models[-k:], with_gains=True)
        for gains in gain_arrays:
            if gains.size:
                best_gain = max(best_gain, float(np.max(gains)))
        stats["num_leaves"] = int(sum(counts))
        stats["best_gain"] = round(best_gain, 6)
        gauges = {}
        bins = getattr(self.train_data, "bins", None)
        if bins is not None and hasattr(bins, "nbytes"):
            # bin bundle resident in HBM (uploaded lazily; same size)
            gauges["hbm_bins_bytes"] = int(bins.nbytes)
        tl = self.tree_learner
        if tl is not None and hasattr(tl, "num_features") \
                and hasattr(tl, "max_num_bin"):
            gauges["hbm_hist_pool_bytes"] = int(
                self.config.num_leaves * tl.num_features
                * tl.max_num_bin * 2 * 4)
            try:
                hist_ci = tl._hist_fn.cache_info()
                part_ci = tl._partition_fn.cache_info()
                gauges["compile_cache_hits"] = int(hist_ci.hits
                                                   + part_ci.hits)
                gauges["compile_cache_misses"] = int(hist_ci.misses
                                                     + part_ci.misses)
            except AttributeError:
                pass
        # AOT compile-manager stats (lightgbm_tpu/compile): executable
        # cache traffic + compile/serialize seconds as gauges so the
        # JSONL record always carries the session-cumulative totals
        try:
            from ..compile import get_manager
            for k, v in get_manager().snapshot().items():
                gauges[f"aot_{k}"] = float(v)
        except Exception:
            pass
        # planar per-iteration training state (score planes the update
        # loop rewrites in place — schema minor 5 mem.* family)
        try:
            leaves = jax.tree_util.tree_leaves(self.device_score_state())
            gauges["mem.planar_state_bytes"] = int(
                sum(int(getattr(a, "nbytes", 0) or 0) for a in leaves))
        except Exception:
            pass
        from ..obs import active as obs_active
        reg = obs_active()
        if reg is not None:
            for name, v in gauges.items():
                reg.set_gauge(name, v)
        return stats

    def _trim_degenerate_tail(self) -> int:
        """Delete every trailing iteration whose trees are all single
        leaves (the fused path trains blind between periodic stop
        checks; the reference rolls back at the first degenerate
        iteration — gbdt.cpp:389-407)."""
        k = self.num_tree_per_iteration
        removed = 0
        while len(self.models) > k:
            if all(v <= 1 for v in
                   self._batched_tree_stats(self.models[-k:])[0]):
                del self.models[-k:]
                self.iter -= 1
                removed += 1
            else:
                break
        return removed

    def _materialize_models(self) -> None:
        """Swap PendingTree entries for concrete host Trees. The device
        arrays of EVERY pending tree ride ONE jax.device_get instead of
        one round trip per array per tree."""
        from ..treelearner.fused import PendingTree
        pend = [(i, t) for i, t in enumerate(self.models)
                if isinstance(t, PendingTree) and t._tree is None]
        if pend:
            # tpulint: sync-ok(model materialization, batched; snapshot/finalize only)
            host = jax.device_get([t.tree_arrays for _, t in pend])
            for (_, t), ta in zip(pend, host):
                t.tree_arrays = ta
        for i, t in enumerate(self.models):
            if isinstance(t, PendingTree):
                self.models[i] = t.materialize()

    def rollback_one_iter(self) -> None:
        """reference GBDT::RollbackOneIter (gbdt.cpp:421)."""
        if self._replays_on_device:
            self._rollback_by_replay()
            return
        self._materialize_models()
        self._invalidate_fused_state()
        if self.iter <= 0:
            return
        k = self.num_tree_per_iteration
        miss = self.tree_learner.feature_miss_bin
        for c in range(k):
            tree = self.models[len(self.models) - k + c]
            tree.apply_shrinkage(-1.0)
            self.train_score.add_tree(tree, c, miss)
            for vs in self.valid_score:
                vs.add_tree(tree, c, miss)
        del self.models[-k:]
        self.iter -= 1

    # ------------------------------------------------------------------
    # whole trees replayed over the resident planes (fused, one chip)
    # ------------------------------------------------------------------
    @property
    def _replays_on_device(self) -> bool:
        """Score surgery by whole trees (DART's drop and normalize, a
        rollback) replays them over the resident code planes: the fused
        learner on one chip, outside the persistent tier."""
        f = self._fused
        return (f is not None and not f.is_multichip
                and not self._fused_persist)

    def _replay_source(self, tree):
        """(tree arrays, scale, bias) of a model, whose output on a row
        is the arrays' output x scale + bias."""
        from ..treelearner.fused import PendingTree
        if isinstance(tree, PendingTree) and tree._tree is None:
            return (tree.tree_arrays, tree.pending_shrinkage,
                    tree.pending_bias)
        host = tree._tree if isinstance(tree, PendingTree) else tree
        return self._fused.replay_arrays(host), 1.0, 0.0

    def _replay_subtract(self, forest, rows, weights, biases, kmax: int):
        """score[c] -= biases[c] + sum over j of weights[c][j] x the
        output of the forest's row rows[c][j] on every training row, by
        one call of `boosting/dart_replay`; returns what it took off,
        [K, n] on the device. The index vector is kmax long whatever the
        count, padded by its last entry at weight 0."""
        K = self.num_tree_per_iteration
        idx = np.zeros((K, kmax), np.int32)
        w = np.zeros((K, kmax), np.float32)
        count = np.zeros(K, np.int32)
        for c in range(K):
            r = list(rows[c])
            idx[c, :len(r)] = r
            idx[c, len(r):] = r[-1] if r else 0
            w[c, :len(r)] = weights[c]
            count[c] = len(r)
        g = self._fused
        self.train_score.score, dropped = _dart_replay_entry()(
            forest.routes, forest.values, g.codes_planes(), idx, w, count,
            np.asarray(biases, np.float32), self.train_score.score,
            method=g.row_traverse_method, interpret=g._interpret)
        return dropped

    def _valid_add(self, vs, tree, class_id: int, factor: float) -> None:
        """A validation set's score += factor x the tree's output, the
        tree left as it is."""
        from ..treelearner.fused import PendingTree
        bins = vs.dataset.device_bins()
        if isinstance(tree, PendingTree) and tree._tree is None:
            leaf = self._fused._valid_traverse_jit(tree.tree_arrays, bins)
        else:
            tree = tree._tree if isinstance(tree, PendingTree) else tree
            leaf = tree.leaf_index_binned(
                bins, self.tree_learner.feature_miss_bin,
                efb=vs.dataset.device_bundle_tables())
        vs.score = _score_add_entry()(
            vs.score, tree.leaf_values_device() * factor, leaf,
            class_id=class_id)

    def _rollback_by_replay(self) -> None:
        """The last iteration's trees taken off the training score by
        one replay over the resident planes, each at its own scale: no
        tree is materialized and no row-major table is uploaded."""
        if self.iter <= 0:
            return
        k = self.num_tree_per_iteration
        trees = self.models[-k:]
        forest, rows, scales, biases, kmax = self._last_trees_in_forest(k)
        self._replay_subtract(forest, [[r] for r in rows],
                              [[s] for s in scales], biases, kmax)
        for c, tree in enumerate(trees):
            for vs in self.valid_score:
                self._valid_add(vs, tree, c, -1.0)
        del self.models[-k:]
        self.iter -= 1

    def _last_trees_in_forest(self, k: int):
        """(forest, rows, scales, biases, kmax) that replay the last k
        trees: here tables of their own and a replay of one tree."""
        from ..treelearner.fused import ForestTables
        forest = ForestTables(self._fused, step=k)
        forest.reserve(k)
        src = [self._replay_source(t) for t in self.models[-k:]]
        for c, (ta, _, _) in enumerate(src):
            forest.put(c, ta)
        return (forest, list(range(k)), [s for _, s, _ in src],
                [b for _, _, b in src], 1)

    # ------------------------------------------------------------------
    # numeric-health quarantine (robust/sentinel.py)
    # ------------------------------------------------------------------
    def quarantine_iter(self, iteration: int) -> bool:
        """Discard the tree(s) of one absolute (0-based) iteration that
        a numeric sentinel flagged, then REBUILD every score state from
        the surviving trees. Rollback-by-subtraction would re-touch the
        poisoned leaf values (NaN - NaN = NaN) and contaminate the
        scores permanently; the rebuild never reads them."""
        k = self.num_tree_per_iteration
        idx = iteration - self.num_init_iteration
        if idx < 0 or (idx + 1) * k > len(self.models):
            return False
        self._pred_revision = getattr(self, "_pred_revision", 0) + 1
        self._materialize_models()
        self._drain_stop_check()
        del self.models[idx * k:(idx + 1) * k]
        self._on_quarantine(idx)
        self.iter -= 1
        # the persistent planar state carries the poisoned scores; it
        # is rebuilt lazily from the fresh train_score next iteration
        self._fused_state = None
        self._score_dirty = False
        self._rebuild_scores()
        from .. import obs
        reg = obs.active()
        if reg is not None:
            reg.inc("health.quarantined", k)
        return True

    def _on_quarantine(self, idx: int) -> None:
        """Boosting-mode hook: drop per-iteration side state for the
        quarantined (relative) iteration ``idx``."""

    def _rebuild_scores(self) -> None:
        """Recompute train/valid scores from scratch off the surviving
        forest. Fresh _ScoreState re-applies init scores; the
        boost_from_average constant needs no special casing because it
        is folded into the first iteration's trees (add_bias / the
        constant-tree leaf)."""
        k = self.num_tree_per_iteration
        miss = self.tree_learner.feature_miss_bin
        self.train_score = _ScoreState(self.train_data, k)
        self.valid_score = [_ScoreState(vs.dataset, k)
                            for vs in self.valid_score]
        for i, tree in enumerate(self.models):
            self.train_score.add_tree(tree, i % k, miss)
            for vs in self.valid_score:
                vs.add_tree(tree, i % k, miss)

    def sentinel_drain(self) -> None:
        """Force-resolve in-flight sentinel verdicts. End-of-training
        and pre-rollback only — in steady state verdicts ride the
        trailing fetches instead."""
        sent = self._sentinel
        if sent is None:
            return
        pending = sent.take_pending()
        if pending:
            # tpulint: sync-ok(sentinel drain: end-of-training/rollback only, one batched fetch)
            vals = jax.device_get([r for _, r in pending])
            sent.resolve(pending, vals)

    def process_sentinel_trips(self) -> bool:
        """Quarantine every iteration a sentinel flagged since the last
        call. Returns True when accumulated trips reached the
        escalation threshold (the engine then rolls back to the last
        checkpoint and steps down the degraded-mode ladder)."""
        sent = self._sentinel
        if sent is None:
            return False
        flagged: Dict[int, str] = {}
        for iteration, kind in sent.pop_trips():
            flagged.setdefault(iteration, kind)
        # highest iteration first: quarantining an iteration shifts
        # every LATER iteration's position in self.models, never an
        # earlier one's
        for iteration in sorted(flagged, reverse=True):
            if self.quarantine_iter(iteration):
                log.warning(
                    "numeric sentinel: quarantined the tree(s) of "
                    "iteration %d (%s detected in leaf values); "
                    "training continues on the healthy forest",
                    iteration, flagged[iteration])
        sent.poll_quant_tripwire()
        return sent.trips >= sent.max_trips

    # ------------------------------------------------------------------
    def _renew_tree_output(self, tree: Tree, class_id: int) -> None:
        """Objective-specific leaf refit (reference
        SerialTreeLearner::RenewTreeOutput, serial_tree_learner.cpp:661;
        percentile refits for L1/quantile/MAPE)."""
        obj = self.objective
        if obj is None or not obj.is_renew_tree_output:
            return
        with obs_span("renew tree output (leaf refit)", phase="renew"):
            self._renew_tree_output_impl(tree, class_id)

    def _renew_tree_output_impl(self, tree: Tree, class_id: int) -> None:
        obj = self.objective
        miss = self.tree_learner.feature_miss_bin
        leaf_idx = np.asarray(tree.leaf_index_binned(
            self.train_data.device_bins(), miss,
            efb=self.train_data.device_bundle_tables()))
        score = np.asarray(self.train_score.score[class_id])
        label = np.asarray(self.train_data.metadata.label)
        residual = label - score
        if self._in_bag is not None:
            bag_rows = np.flatnonzero(np.asarray(self._in_bag))
            out = obj.renew_tree_output(leaf_idx[bag_rows], residual[bag_rows],
                                        tree.num_leaves)
        else:
            out = obj.renew_tree_output(leaf_idx, residual, tree.num_leaves)
        if out is not None:
            tree.leaf_value[:tree.num_leaves] = out
            tree._device = None

    def _update_score(self, tree: Tree, class_id: int) -> None:
        """reference GBDT::UpdateScore (gbdt.cpp:458): train + valid."""
        miss = self.tree_learner.feature_miss_bin
        self.train_score.add_tree(tree, class_id, miss)
        for vs in self.valid_score:
            vs.add_tree(tree, class_id, miss)

    # ------------------------------------------------------------------
    def eval_at_iter(self) -> Dict[str, List[Tuple[str, str, float, bool]]]:
        """All metric values: list of (dataset_name, metric_name, value,
        bigger_is_better). Synchronous form of the begin/finish pair
        below — dispatch and resolve back to back."""
        return self.finish_eval_at_iter(self.begin_eval_at_iter())

    def begin_eval_at_iter(self):
        """Dispatch this iteration's metric evaluation; the scalar
        readback starts immediately but is NOT waited on. Returns an
        opaque handle for finish_eval_at_iter, which the pipelined
        engine loop resolves one iteration later, while the next
        iteration's device work is already in flight.

        Metrics with a device reduction (metric/metrics.py eval_device)
        are reduced ON DEVICE and only their scalars transferred — one
        batched device_get for the whole eval, instead of an [N]-sized
        np.asarray per dataset per iteration. Host fallback covers
        averaged-output models (DART weights need the host divide),
        multiclass score blocks, and metrics without a device path;
        fallback metrics evaluate eagerly here (they need the host
        score either way)."""
        from .. import obs
        reg = obs.active()
        out: list = []
        dev_slots: list = []    # (out index, 0-d device array)
        div = 1.0
        if self.average_output and self.current_iteration > 0:
            div = float(self.current_iteration)
        use_device = (div == 1.0 and self._device_eval and os.environ.get(
            "LGBM_TPU_DEVICE_EVAL", "1") != "0")

        def eval_set(ds_name, metrics, score):
            sc_host = None
            for m in metrics:
                res = None
                if use_device and score.shape[0] == 1:
                    # None = this metric has no device reduction; an
                    # exception is a real failure and propagates
                    res = m.eval_device(score[0], self.objective)
                if res is not None:
                    for name, val in res:
                        out.append([ds_name, name, val,
                                    m.bigger_is_better])
                        dev_slots.append((len(out) - 1, val))
                    continue
                if sc_host is None:
                    sc_host = np.asarray(score) / div
                    if reg is not None:
                        reg.inc("eval.host_transfer_rows",
                                int(sc_host.shape[-1]))
                sc = sc_host[0] if sc_host.shape[0] == 1 else sc_host
                for name, val in m.eval(sc, self.objective):
                    out.append([ds_name, name, val, m.bigger_is_better])

        if self.metrics:
            eval_set("training", self.metrics, self.get_training_score())
        for i, ms in enumerate(self.valid_metrics):
            eval_set(f"valid_{i}", ms, self.valid_score[i].score)
        for _, v in dev_slots:
            try:
                v.copy_to_host_async()
            except Exception:
                pass   # host copy is an optimization, not a contract
        if dev_slots and reg is not None:
            reg.inc("pipeline.inflight_fetches")
        tr = obs.active_tracer()
        return (out, dev_slots, tr.iteration if tr is not None else -1)

    def finish_eval_at_iter(self, handle):
        """Resolve a begin_eval_at_iter handle: one batched device_get
        over every device-reduced scalar of that eval. In the pipelined
        engine loop the handle is one iteration old, so the scalars are
        already host-resident and the fetch does not block."""
        from .. import obs
        from ..robust.watchdog import watch_phase
        out, dev_slots, disp_iter = handle
        sent = self._sentinel
        s_pending = sent.take_pending() if sent is not None else []
        if dev_slots or s_pending:
            reg = obs.active()
            with obs.sync_attribution(disp_iter), \
                    watch_phase("readback:eval scalars"):
                # tpulint: sync-ok(trailing-fetch: batched eval scalars dispatched an iteration earlier; one transfer per eval)
                vals = jax.device_get([v for _, v in dev_slots] +
                                      [r for _, r in s_pending])
            for (idx, _), v in zip(dev_slots, vals):
                out[idx][2] = float(v)
            if s_pending:
                # sentinel verdicts ride the same batched fetch — zero
                # extra blocking syncs for numeric-health checks
                sent.resolve(s_pending, vals[len(dev_slots):])
            if reg is not None and dev_slots:
                reg.inc("eval.device_scalars", len(dev_slots))
        return [tuple(t) for t in out]

    # ------------------------------------------------------------------
    # prediction (reference gbdt_prediction.cpp + c_api predict paths)
    # ------------------------------------------------------------------
    def _used_models(self, start_iteration: int, num_iteration: int):
        self._materialize_models()
        k = self.num_tree_per_iteration
        total = len(self.models) // k
        start = max(0, min(start_iteration, total))
        if num_iteration > 0:
            end = min(start + num_iteration, total)
        else:
            end = total
        return self.models[start * k:end * k]

    def _packed_forest(self, start_iteration: int, num_iteration: int):
        """Cached PackedForest over the selected tree range (reference
        SingleRowPredictor caches its Predictor the same way)."""
        from ..models.forest import PackedForest
        models = self._used_models(start_iteration, num_iteration)
        key = (start_iteration, num_iteration, len(self.models),
               getattr(self, "_pred_revision", 0))
        cache = getattr(self, "_forest_cache", None)
        if cache is None or cache[0] != key:
            forest = PackedForest(models, self.num_tree_per_iteration)
            self._forest_cache = (key, forest)
        return self._forest_cache[1], models

    def _path_forest(self, start_iteration: int, num_iteration: int):
        """Cached PathForest (models/pathforest.py) — the gather-free
        MXU traversal; None when the model is out of its scope
        (categorical splits)."""
        from ..models.pathforest import PathForest, build_path_tables
        models = self._used_models(start_iteration, num_iteration)
        key = (start_iteration, num_iteration, len(self.models),
               getattr(self, "_pred_revision", 0))
        cache = getattr(self, "_path_forest_cache", None)
        if cache is None or cache[0] != key:
            forest = None
            if models:
                tabs = build_path_tables(models)
                if tabs is not None:
                    forest = PathForest(models,
                                        self.num_tree_per_iteration, tabs)
            self._path_forest_cache = (key, forest)
        return self._path_forest_cache[1]

    @staticmethod
    def _pad_rows(x: np.ndarray):
        """Pad the batch to a power-of-two bucket (>=8) so the jitted
        forest kernels specialize on O(log N) batch shapes — this is
        the single-row fast path: a 1-row predict reuses the 8-row
        program from the jit cache."""
        n = x.shape[0]
        cap = 8
        while cap < n:
            cap *= 2
        if cap == n:
            return x, n
        return np.pad(x, ((0, cap - n), (0, 0))), n

    def _raw_scores_device(self, x: np.ndarray, start_iteration: int,
                           num_iteration: int):
        """Device-resident [k, cap] raw scores + (n, had_models). The
        whole path is one host→device upload and one program;
        conversion/averaging stay device-side too."""
        models = self._used_models(start_iteration, num_iteration)
        k = self.num_tree_per_iteration
        n_in = np.asarray(x).shape[0]
        if not models:
            return None, n_in
        # large batches run in chunks: bounds the [T, chunk] traversal
        # state and the pow-2 padding waste
        CHUNK = 131072
        if n_in > CHUNK:
            xx = np.asarray(x, dtype=np.float32)
            parts = [self._raw_scores_device(xx[i:i + CHUNK],
                                             start_iteration,
                                             num_iteration)[0][:, :min(
                                                 CHUNK, n_in - i)]
                     for i in range(0, n_in, CHUNK)]
            return jnp.concatenate(parts, axis=1), n_in
        xp, n = self._pad_rows(np.asarray(x, dtype=np.float32))
        xd = jnp.asarray(xp)
        cfg = self.config
        path_forest = None
        if (os.environ.get("LGBM_TPU_PRED_PATH", "1") != "0"
                and not (cfg is not None and cfg.pred_early_stop)):
            path_forest = self._path_forest(start_iteration, num_iteration)
        if cfg is not None and cfg.pred_early_stop:
            path = "walker (pred_early_stop)"
            forest, _ = self._packed_forest(start_iteration, num_iteration)
            score = forest.raw_scores_early_stop(
                xd, max(1, cfg.pred_early_stop_freq),
                float(cfg.pred_early_stop_margin))
        elif path_forest is not None:
            # gather-free MXU path traversal (models/pathforest.py);
            # the walker covers categorical/oversized models — and is
            # only BUILT on the branches that use it
            path = "pathforest"
            score = path_forest.raw_scores(xd)
        else:
            path = "walker (categorical split or path tables > 512 MB)"
            forest, _ = self._packed_forest(start_iteration, num_iteration)
            score = forest.raw_scores(xd)
        if path != self.predict_path:
            # the two predictors differ by orders of magnitude in cost
            # (ROADMAP A7): say which one a model landed on
            self.predict_path = path
            log.info("Predicting with %s", path)
        if self.average_output:
            score = score / (len(models) // k)
        return score, n

    def predict_raw(self, x: np.ndarray, start_iteration: int = 0,
                    num_iteration: int = -1) -> np.ndarray:
        """Raw scores [N] or [N, num_class] — one device dispatch via
        the packed forest (replacing one dispatch per tree)."""
        k = self.num_tree_per_iteration
        score, n = self._raw_scores_device(x, start_iteration, num_iteration)
        if score is None:
            out = np.zeros((k, n), dtype=np.float64)
            return out[0] if k == 1 else out.T
        out = np.asarray(score, dtype=np.float64)[:, :n]
        return out[0] if k == 1 else out.T

    def predict(self, x: np.ndarray, start_iteration: int = 0,
                num_iteration: int = -1) -> np.ndarray:
        k = self.num_tree_per_iteration
        score, n = self._raw_scores_device(x, start_iteration, num_iteration)
        if score is None:
            out = np.zeros((k, n), dtype=np.float64)
        elif self.objective is not None:
            if self._convert_jit is None:
                conv = self.objective.convert_output
                from ..compile import get_manager
                self._convert_jit = get_manager().jit_entry(
                    "predict/convert_output", jax.jit(lambda s: conv(s)))
            out = np.asarray(self._convert_jit(score.T), dtype=np.float64).T
        else:
            out = np.asarray(score, dtype=np.float64)
        out = out[:, :n]
        return out[0] if k == 1 else out.T

    def predict_leaf_index(self, x: np.ndarray, start_iteration: int = 0,
                           num_iteration: int = -1) -> np.ndarray:
        forest, models = self._packed_forest(start_iteration, num_iteration)
        if not models:
            return np.empty((np.asarray(x).shape[0], 0), dtype=np.int32)
        xp, n = self._pad_rows(np.asarray(x, dtype=np.float32))
        return np.asarray(forest.leaf_indices(jnp.asarray(xp)))[:n]

    def predict_contrib(self, x: np.ndarray, start_iteration: int = 0,
                        num_iteration: int = -1) -> np.ndarray:
        """SHAP values (reference Tree::PredictContrib / tree.cpp
        TreeSHAP recursion), computed per tree on the host."""
        from ..models.shap import tree_shap
        xx = np.asarray(x, dtype=np.float64)
        n = xx.shape[0]
        k = self.num_tree_per_iteration
        nf = self.max_feature_idx + 1
        out = np.zeros((k, n, nf + 1))
        models = self._used_models(start_iteration, num_iteration)
        for i, tree in enumerate(models):
            out[i % k] += tree_shap(tree, xx)
        if k == 1:
            return out[0]
        # multiclass layout: per row, contribs of every class side by side
        return np.concatenate([out[c] for c in range(k)], axis=1)

    def num_predict(self, num_row: int, predict_leaf: bool, predict_contrib: bool) -> int:
        k = self.num_tree_per_iteration
        if predict_contrib:
            return num_row * k * (self.max_feature_idx + 2)
        if predict_leaf:
            return num_row * len(self.models)
        return num_row * k

    # ------------------------------------------------------------------
    # model IO (reference gbdt_model_text.cpp)
    # ------------------------------------------------------------------
    def _feature_infos(self) -> List[str]:
        ds = self.train_data
        infos = ["none"] * (self.max_feature_idx + 1)
        if ds is None:
            return getattr(self, "_loaded_feature_infos", infos)
        for i, f in enumerate(ds.real_feature_index):
            m = ds.bin_mappers[i]
            if m.bin_type == BIN_CATEGORICAL:
                infos[f] = ":".join(str(c) for c in m.bin_2_categorical)
            else:
                infos[f] = f"[{m.min_val}:{m.max_val}]"
        return infos

    def save_model_to_string(self, start_iteration: int = 0,
                             num_iteration: int = -1,
                             importance_type: int = 0) -> str:
        lines = ["tree", f"version={K_MODEL_VERSION}",
                 f"num_class={self.config.num_class if self.config else self.num_tree_per_iteration}",
                 f"num_tree_per_iteration={self.num_tree_per_iteration}",
                 f"label_index={self.label_idx}",
                 f"max_feature_idx={self.max_feature_idx}"]
        if self.objective is not None:
            lines.append(f"objective={self.objective.to_string()}")
        if self.average_output:
            lines.append("average_output")
        lines.append("feature_names=" + " ".join(self.feature_names_))
        lines.append("feature_infos=" + " ".join(self._feature_infos()))

        models = self._used_models(start_iteration, num_iteration)
        tree_strs = []
        for i, t in enumerate(models):
            tree_strs.append(f"Tree={i}\n" + t.to_string())
        sizes = [len(s) + 1 for s in tree_strs]
        lines.append("tree_sizes=" + " ".join(str(s) for s in sizes))
        lines.append("")
        body = "\n".join(s for s in tree_strs)
        tail = ["end of trees", ""]
        imp = self.feature_importance(importance_type, num_iteration)
        pairs = [(int(v), self.feature_names_[i]) for i, v in enumerate(imp) if v > 0]
        pairs.sort(key=lambda p: -p[0])
        tail.append("feature_importances:")
        for v, nm in pairs:
            tail.append(f"{nm}={v}")
        tail.append("")
        tail.append("parameters:")
        tail.append(self.config.to_params_string() if self.config else self.loaded_parameter)
        tail.append("end of parameters")
        return "\n".join(lines) + "\n" + body + "\n" + "\n".join(tail) + "\n"

    def save_model_to_file(self, filename: str, start_iteration: int = 0,
                           num_iteration: int = -1, importance_type: int = 0) -> None:
        with open(filename, "w") as fh:
            fh.write(self.save_model_to_string(start_iteration, num_iteration,
                                               importance_type))

    def load_model_from_string(self, text: str) -> None:
        """reference GBDT::LoadModelFromString (gbdt_model_text.cpp:410)."""
        head, _, rest = text.partition("\ntree_sizes=")
        kv: Dict[str, str] = {}
        for line in head.splitlines():
            if "=" in line:
                key, val = line.split("=", 1)
                kv[key.strip()] = val
            elif line.strip() == "average_output":
                self.average_output = True
        self.num_tree_per_iteration = int(kv.get("num_tree_per_iteration", "1"))
        self._loaded_num_class = int(kv.get("num_class", "1"))
        self.label_idx = int(kv.get("label_index", "0"))
        self.max_feature_idx = int(kv.get("max_feature_idx", "0"))
        self.feature_names_ = kv.get("feature_names", "").split()
        self._loaded_feature_infos = kv.get("feature_infos", "").split()
        self._loaded_objective = kv.get("objective", "")
        if self._loaded_objective:
            from ..objective.functions import create_objective
            name = self._loaded_objective.split()[0]
            params: Dict[str, object] = {"objective": name, "verbosity": -1}
            for tok in self._loaded_objective.split()[1:]:
                if ":" in tok:
                    pk, pv = tok.split(":", 1)
                    params[pk] = pv
                elif tok == "sqrt":
                    params["reg_sqrt"] = True
            if name in ("multiclass", "multiclassova"):
                params["num_class"] = self._loaded_num_class
            try:
                cfg = Config.from_params(params)
                self.objective = create_objective(cfg)
            except BaseException:
                self.objective = None
        self.models = list(parse_tree_blocks(text))
        self.iter = len(self.models) // max(self.num_tree_per_iteration, 1)
        self.num_init_iteration = self.iter
        pstart = text.find("\nparameters:")
        if pstart >= 0:
            self.loaded_parameter = text[pstart + len("\nparameters:"):]\
                .split("end of parameters")[0].strip()

    # ------------------------------------------------------------------
    # checkpoint/resume (robust/checkpoint.py, docs/ROBUSTNESS.md)
    # ------------------------------------------------------------------
    def checkpoint_state(self) -> Dict:
        """Loop state beyond the model text that an interrupted run
        needs to continue bit-identically: every host RNG stream, the
        bagging permutation, and the f32 score accumulators (restored
        directly — recomputing scores from the trees would change the
        accumulation order and drift in the last ulp)."""
        self._materialize_models()
        # the pipelined loop must not leak live device refs into the
        # checkpoint; a drained positive verdict is persisted instead
        self._drain_stop_check()
        st: Dict = {
            "iter": int(self.iter),
            "num_init_iteration": int(self.num_init_iteration),
            "shrinkage_rate": float(self.shrinkage_rate),
            "class_need_train": [bool(v) for v in self.class_need_train],
            "bag_data_cnt": int(self.bag_data_cnt),
            "bag_rng": _pack_rng(self._bag_rng),
            "best_iter": int(self.best_iter),
        }
        if self._in_bag is not None:
            st["perm"] = np.asarray(self._perm)
        tl = self.tree_learner
        if getattr(tl, "_col_rng", None) is not None:
            st["tl_col_rng"] = _pack_rng(tl._col_rng)
        if getattr(tl, "_extra_rng", None) is not None:
            st["tl_extra_rng"] = _pack_rng(tl._extra_rng)
        if self._fused is not None:
            if getattr(self._fused, "_col_rng", None) is not None:
                st["fused_col_rng"] = _pack_rng(self._fused._col_rng)
            st["quant_iter"] = int(getattr(self._fused, "_quant_iter", 0))
        if self._fused_state is not None \
                and hasattr(self._fused, "persistent_lane_state"):
            # lane order is part of the numeric state: histogram and
            # score accumulation follow it, so save the permuted planes
            # (rowid + score bits) instead of row-order scores
            rowid, score_bits = self._fused.persistent_lane_state(
                self._fused_state)
            st["fused_lane_rowid"] = rowid
            st["fused_lane_score"] = score_bits
        else:
            st["train_score"] = np.asarray(self.get_training_score())
        st["valid_scores"] = [np.asarray(vs.score) for vs in self.valid_score]
        if self._stop_pending:
            st["stop_pending"] = True
        return st

    def restore_checkpoint_state(self, state: Dict, model_text: str) -> None:
        """Inverse of checkpoint_state against a freshly-initialized
        booster on the same dataset/config."""
        self._pred_revision = getattr(self, "_pred_revision", 0) + 1
        # in-flight refs never cross a checkpoint boundary; a drained
        # positive verdict resumes via the additive "stop_pending" key
        # (absent in older checkpoints -> no verdict, same as before)
        self._stop_fetch = None
        self._stop_pending = True if state.get("stop_pending") else None
        # a mid-run restore (watchdog auto-resume, sentinel rollback)
        # lands on a LIVE booster: in-flight sentinel verdicts belong
        # to the abandoned timeline
        if self._sentinel is not None:
            self._sentinel.drop_pending()
        self.models = list(parse_tree_blocks(model_text))
        # the text format drops bin-space fields; train-time score
        # surgery (DART drop/normalize, rollback) traverses in bin
        # space, so every restored tree must re-link to the dataset
        for t in self.models:
            t.relink_to_dataset(self.train_data)
        self.iter = int(state["iter"])
        self.num_init_iteration = int(state.get("num_init_iteration", 0))
        self.shrinkage_rate = float(
            state.get("shrinkage_rate", self.shrinkage_rate))
        if "class_need_train" in state:
            self.class_need_train = [bool(v)
                                     for v in state["class_need_train"]]
        cnt = int(state.get("bag_data_cnt", self.num_data))
        if "bag_rng" in state:
            _unpack_rng(self._bag_rng, state["bag_rng"])
        if "perm" in state:
            keep = np.zeros(self.num_data, bool)
            keep[np.asarray(state["perm"])[:cnt]] = True
            self._set_bag(keep, cnt)
        else:
            self._set_bag(None, self.num_data)
        self.best_iter = int(state.get("best_iter", 0))
        tl = self.tree_learner
        if "tl_col_rng" in state and getattr(tl, "_col_rng", None) is not None:
            _unpack_rng(tl._col_rng, state["tl_col_rng"])
        if "tl_extra_rng" in state \
                and getattr(tl, "_extra_rng", None) is not None:
            _unpack_rng(tl._extra_rng, state["tl_extra_rng"])
        if self._fused is not None:
            if "fused_col_rng" in state \
                    and getattr(self._fused, "_col_rng", None) is not None:
                _unpack_rng(self._fused._col_rng, state["fused_col_rng"])
            if hasattr(self._fused, "_quant_iter"):
                self._fused._quant_iter = int(state.get("quant_iter", 0))
        if "fused_lane_rowid" in state:
            if self._fused is None \
                    or not hasattr(self._fused, "restore_persistent_state"):
                log.fatal(
                    "Checkpoint holds fused persistent-path state but the "
                    "current configuration selected a different tree grower; "
                    "refusing to resume (delete the checkpoint directory or "
                    "restore the original parameters)")
            self._fused_state = self._fused.restore_persistent_state(
                state["fused_lane_rowid"], state["fused_lane_score"])
            self._score_dirty = True
        elif "train_score" in state:
            self.train_score.score = jnp.asarray(
                np.asarray(state["train_score"], np.float32))
            self._fused_state = None
            self._score_dirty = False
        vs_arrays = state.get("valid_scores", [])
        if len(vs_arrays) != len(self.valid_score):
            log.warning(
                "Checkpoint has %d valid-set score arrays but the resumed "
                "train() call wired %d valid sets; resumed eval metrics may "
                "not match the uninterrupted run",
                len(vs_arrays), len(self.valid_score))
        for vs, arr in zip(self.valid_score, vs_arrays):
            vs.score = jnp.asarray(np.asarray(arr, np.float32))

    # ------------------------------------------------------------------
    def feature_importance(self, importance_type: int = 0,
                           num_iteration: int = -1) -> np.ndarray:
        """0 = split count, 1 = total gain (reference
        GBDT::FeatureImportance, gbdt.cpp:756)."""
        nf = self.max_feature_idx + 1
        out = np.zeros(nf)
        models = self._used_models(0, num_iteration)
        for tree in models:
            ni = tree.num_leaves - 1
            for i in range(ni):
                f = int(tree.split_feature[i])
                if importance_type == 0:
                    if tree.split_gain[i] > 0:
                        out[f] += 1.0
                else:
                    out[f] += max(float(tree.split_gain[i]), 0.0)
        return out

    @property
    def current_iteration(self) -> int:
        return len(self.models) // max(self.num_tree_per_iteration, 1)

    def refit_tree(self, tree_leaf_prediction: np.ndarray) -> None:
        """reference GBDT::RefitTree (gbdt.cpp:266): re-fit leaf values
        of the existing structure with new gradients."""
        cfg = self.config
        self._pred_revision = getattr(self, "_pred_revision", 0) + 1
        leaf_pred = np.asarray(tree_leaf_prediction, dtype=np.int64)
        self._materialize_models()
        self._invalidate_fused_state()
        self._boosting()
        grad = np.asarray(self._grad)
        hess = np.asarray(self._hess)
        k = self.num_tree_per_iteration
        for i, tree in enumerate(self.models):
            c = i % k
            lp = leaf_pred[:, i]
            nl = tree.num_leaves
            gs = np.bincount(lp, weights=grad[c], minlength=nl)
            hs = np.bincount(lp, weights=hess[c], minlength=nl)
            for leaf in range(nl):
                g, h = gs[leaf], hs[leaf]
                if cfg.lambda_l1 > 0:
                    g = np.sign(g) * max(0.0, abs(g) - cfg.lambda_l1)
                new_out = -g / (h + cfg.lambda_l2)
                old = tree.leaf_value[leaf]
                tree.set_leaf_value(
                    leaf, cfg.refit_decay_rate * old
                    + (1.0 - cfg.refit_decay_rate) * new_out * self.shrinkage_rate)
            self._update_score(tree, c)


class DART(GBDT):
    """Dropout boosting (reference dart.hpp:23: DroppingTrees, Normalize).

    Each iteration draws the dropped set J on the host, takes the dropped
    trees' output D = sum_{j in J} s_j tree_j off the training score, grows
    the new tree at shrinkage lr / (k + 1) (lr / (lr + k) in xgboost mode)
    against what is left, and puts k / (k + 1) D (k / (lr + k)) back, each
    dropped tree's scale s_j going by the same factor. On the fused
    learner of one chip D is ONE replay of the dropped trees over the
    resident code planes (`boosting/dart_replay`, plane.replay_forest_*)
    from the forest's tables on the device (fused.ForestTables), with
    every s_j a float64 on the host: no tree is fetched, no row-major
    table is uploaded, and the iteration makes no blocking sync. Elsewhere
    (the host loop, a mesh) the dropped trees are materialized and walked
    one by one, the oracle path."""

    def init(self, config, train_data, objective, metrics):
        super().init(config, train_data, objective, metrics)
        self._drop_rng = np.random.RandomState(config.drop_seed)
        self.tree_weight: List[float] = []
        self.sum_weight = 0.0
        self.drop_index: List[int] = []
        self.shrinkage_rate = config.learning_rate
        # the device replay: the forest's tables, the model each row was
        # put from, and each row's current output = row x scale + bias
        self._forest = None
        self._forest_trees: list = []
        self._forest_scale: List[float] = []
        self._forest_bias: List[float] = []
        self._dropped = None            # this iteration's D, [K, n] device
        # (iteration, dropped iterations) of the last DROP_HISTORY draws
        self.drop_history = collections.deque(maxlen=DROP_HISTORY)

    def execution_plan(self) -> Dict[str, object]:
        plan = super().execution_plan()
        if self._replays_on_device:
            plan["dart"] = {"replay": self._fused.row_traverse_method,
                            "kmax": self._kmax()}
        return plan

    def _kmax(self) -> int:
        """Length of the dropped-index vector a replay takes: max_drop,
        or without a cap the forest's capacity."""
        if self.config.max_drop > 0:
            return int(self.config.max_drop)
        from ..treelearner.fused import FOREST_STEP
        cap = 0 if self._forest is None else self._forest.routes.shape[0]
        return max(FOREST_STEP, cap)

    def checkpoint_state(self) -> Dict:
        st = super().checkpoint_state()
        st["dart"] = {"drop_rng": _pack_rng(self._drop_rng),
                      "tree_weight": [float(w) for w in self.tree_weight],
                      "sum_weight": float(self.sum_weight)}
        if self._forest is not None and self._forest.count:
            # the replay's own numbers, so a resumed run takes off and
            # puts back exactly what an uninterrupted one does
            n = self._forest.count
            st["dart"].update(
                forest_scale=[float(v) for v in self._forest_scale],
                forest_bias=[float(v) for v in self._forest_bias],
                forest_values=np.asarray(self._forest.values[:n]))
        return st

    def restore_checkpoint_state(self, state: Dict, model_text: str) -> None:
        super().restore_checkpoint_state(state, model_text)
        self._forest, self._dropped = None, None
        d = state.get("dart")
        if d:
            _unpack_rng(self._drop_rng, d["drop_rng"])
            self.tree_weight = [float(w) for w in d["tree_weight"]]
            self.sum_weight = float(d["sum_weight"])
            self.drop_index = []
        if d and "forest_values" in d and self._replays_on_device \
                and len(d["forest_scale"]) == len(self.models):
            self._sync_forest()
            n = len(self.models)
            f = self._forest
            f.values = f.values.at[:n].set(
                jnp.asarray(np.asarray(d["forest_values"], np.float32)))
            self._forest_scale = [float(v) for v in d["forest_scale"]]
            self._forest_bias = [float(v) for v in d["forest_bias"]]

    def _on_quarantine(self, idx: int) -> None:
        # keep the dropout weights aligned with the surviving forest
        if idx < len(self.tree_weight):
            self.sum_weight -= self.tree_weight[idx]
            del self.tree_weight[idx]
        self._forest = None

    def train_one_iter(self, gradients=None, hessians=None) -> bool:
        if gradients is None or hessians is None:
            self._dropping_trees()
        res = super().train_one_iter(gradients, hessians)
        if not res:
            self._normalize()
            if not self.config.uniform_drop:
                self.tree_weight.append(self.shrinkage_rate)
                self.sum_weight += self.shrinkage_rate
        self._dropped = None
        if self._replays_on_device:
            self._sync_forest()
        return res

    def _dropping_trees(self) -> None:
        cfg = self.config
        self.drop_index = []
        if self._drop_rng.rand() >= cfg.skip_drop:
            drop_rate = cfg.drop_rate
            if not cfg.uniform_drop:
                if self.tree_weight:
                    inv_avg = len(self.tree_weight) / self.sum_weight
                    if cfg.max_drop > 0:
                        drop_rate = min(drop_rate,
                                        cfg.max_drop * inv_avg / self.sum_weight)
                    for i in range(self.iter):
                        if self._drop_rng.rand() < drop_rate * self.tree_weight[i] * inv_avg:
                            self.drop_index.append(self.num_init_iteration + i)
                            if len(self.drop_index) >= cfg.max_drop:
                                break
            else:
                if cfg.max_drop > 0 and self.iter > 0:
                    drop_rate = min(drop_rate, cfg.max_drop / float(self.iter))
                for i in range(self.iter):
                    if self._drop_rng.rand() < drop_rate:
                        self.drop_index.append(self.num_init_iteration + i)
                        if len(self.drop_index) >= cfg.max_drop:
                            break
        self.drop_history.append((self.iter, tuple(self.drop_index)))
        k = self.num_tree_per_iteration
        if self._replays_on_device:
            self._drop_on_device()
        else:
            miss = self.tree_learner.feature_miss_bin
            self._materialize_models()
            for i in self.drop_index:
                for c in range(k):
                    t = self.models[i * k + c]
                    t.apply_shrinkage(-1.0)
                    self.train_score.add_tree(t, c, miss)
        if not self.config.xgboost_dart_mode:
            self.shrinkage_rate = self.config.learning_rate / (1.0 + len(self.drop_index))
        else:
            if not self.drop_index:
                self.shrinkage_rate = self.config.learning_rate
            else:
                self.shrinkage_rate = self.config.learning_rate / \
                    (self.config.learning_rate + len(self.drop_index))

    def _drop_on_device(self) -> None:
        """score -= D by one replay of the dropped trees; D is kept for
        _normalize."""
        if not self.drop_index:
            return
        k = self.num_tree_per_iteration
        self._sync_forest()
        rows = [[i * k + c for i in self.drop_index] for c in range(k)]
        self._dropped = self._replay_subtract(
            self._forest, rows,
            [[self._forest_scale[t] for t in r] for r in rows],
            [sum(self._forest_bias[t] for t in r) for r in rows],
            self._kmax())
        from .. import obs
        reg = obs.active()
        if reg is not None:
            reg.inc("dart.trees_replayed", k * len(self.drop_index))
            reg.inc("dart.drop_rounds")

    def _last_trees_in_forest(self, k: int):
        """The resident forest's rows, at the replay shape the drops
        compiled: a rollback puts no table and compiles nothing."""
        self._sync_forest()
        rows = list(range(len(self.models) - k, len(self.models)))
        return (self._forest, rows, [self._forest_scale[t] for t in rows],
                [self._forest_bias[t] for t in rows], self._kmax())

    def _sync_forest(self) -> None:
        """Every model's row in the forest's tables: the rows from the
        first model that is not the one a row was put from (trees taken
        off the end by a rollback or a trimmed tail) are put again, the
        new ones as they were grown, their scale and bias kept here."""
        from ..treelearner.fused import ForestTables, PendingTree
        if self._forest is None:
            self._forest = ForestTables(self._fused)
            self._forest_trees = []
        kept, n = self._forest_trees, len(self.models)
        same = 0
        while same < min(len(kept), n) and (
                self.models[same] is kept[same]
                or (isinstance(kept[same], PendingTree)
                    and self.models[same] is kept[same]._tree)):
            same += 1
        del kept[same:], self._forest_scale[same:], self._forest_bias[same:]
        f = self._forest
        f.reserve(n)
        for t in range(same, n):
            ta, scale, bias = self._replay_source(self.models[t])
            f.put(t, ta)
            kept.append(self.models[t])
            self._forest_scale.append(scale)
            self._forest_bias.append(bias)
        f.count = n

    def _normalize(self) -> None:
        cfg = self.config
        k_drop = float(len(self.drop_index))
        k = self.num_tree_per_iteration
        if self._replays_on_device:
            self._normalize_on_device(k_drop)
        else:
            miss = self.tree_learner.feature_miss_bin
            self._materialize_models()
            for i in self.drop_index:
                for c in range(k):
                    t = self.models[i * k + c]
                    if not cfg.xgboost_dart_mode:
                        t.apply_shrinkage(1.0 / (k_drop + 1.0))
                        for vs in self.valid_score:
                            vs.add_tree(t, c, miss)
                        t.apply_shrinkage(-k_drop)
                        self.train_score.add_tree(t, c, miss)
                    else:
                        t.apply_shrinkage(self.shrinkage_rate)
                        for vs in self.valid_score:
                            vs.add_tree(t, c, miss)
                        t.apply_shrinkage(-k_drop / cfg.learning_rate)
                        self.train_score.add_tree(t, c, miss)
        if not cfg.uniform_drop:
            for i in self.drop_index:
                j = i - self.num_init_iteration
                if not cfg.xgboost_dart_mode:
                    self.sum_weight -= self.tree_weight[j] / (k_drop + 1.0)
                    self.tree_weight[j] *= k_drop / (k_drop + 1.0)
                else:
                    self.sum_weight -= self.tree_weight[j] / (k_drop + cfg.learning_rate)
                    self.tree_weight[j] *= k_drop / (k_drop + cfg.learning_rate)

    def _normalize_on_device(self, k_drop: float) -> None:
        """score += f D, f = k / (k + 1) (k / (k + lr) in xgboost mode);
        each dropped tree is scaled by f, and a validation set, which
        never had it taken off, gains (f - 1) of it."""
        if self._dropped is None:
            return
        lr = self.config.learning_rate
        f = k_drop / (k_drop + (lr if self.config.xgboost_dart_mode
                                else 1.0))
        self.train_score.score = _dart_add_back_entry()(
            self.train_score.score, self._dropped, np.float32(f))
        k = self.num_tree_per_iteration
        for i in self.drop_index:
            for c in range(k):
                t = i * k + c
                for vs in self.valid_score:
                    self._valid_add(vs, self.models[t], c, f - 1.0)
                self.models[t].apply_shrinkage(f)
                self._forest_scale[t] *= f
                self._forest_bias[t] *= f


def _score_add_device(score, leaf_values, leaf_of_row, *, class_id: int):
    """score[class_id] += leaf_values[leaf_of_row] (GBDT::UpdateScore
    for one tree of the per-tree fused tier). The lookup is a compare
    against every leaf id, summed: it fuses on the VPU, where a [N]-sized
    gather from the leaf table pays a per-row toll."""
    with jax.named_scope("lgbm.score_update"):
        leaf_ids = jnp.arange(leaf_values.shape[0], dtype=jnp.int32)
        add = jnp.sum(jnp.where(leaf_of_row[:, None] == leaf_ids[None, :],
                                leaf_values[None, :], 0.0), axis=1)
        return score.at[class_id].add(add.astype(score.dtype))


def _manager_entry(name: str, fn, **jit_kwargs):
    """`fn` jitted, registered with the compile manager (its compiles
    land in the manager's counters) and called under an `lgbm:` span."""
    from ..compile import get_manager
    from ..obs import instrument_kernel
    jitted = jax.jit(fn, **jit_kwargs)  # tpulint: jit-ok(registered by jit_entry on the next line; the manager counts its compiles)
    return instrument_kernel(get_manager().jit_entry(name, jitted),
                             "boost", name=name)


@functools.lru_cache(maxsize=1)
def _score_add_entry():
    return _manager_entry("boosting/score_add", _score_add_device,
                          static_argnames=("class_id",))


def _dart_replay_device(routes, values, codes_planes, idx, w, count, bias,
                        score, *, method: str, interpret: bool):
    """(score - D, D): D[c] = bias[c] + sum over j < count[c] of w[c, j]
    x the output on every row of the forest's tree idx[c, j] (routes /
    values: fused.ForestTables), by one replay over the resident code
    planes a class; idx's length is the grid's static tree extent."""
    from ..ops import plane
    with jax.named_scope("lgbm.dart_replay"):
        n = score.shape[1]
        out = []
        for c in range(idx.shape[0]):
            vals = values[idx[c]] * w[c][:, None]
            sel = jnp.concatenate([count[c:c + 1], idx[c]])
            if method == "pallas":
                d = plane.replay_forest_pallas(codes_planes, routes, vals,
                                               sel, interpret=interpret)
            else:
                d = plane.replay_forest_ref(codes_planes, routes, vals, sel)
            out.append(d[:n] + bias[c])
        dropped = jnp.stack(out)
        return score - dropped, dropped


@functools.lru_cache(maxsize=1)
def _dart_replay_entry():
    return _manager_entry("boosting/dart_replay", _dart_replay_device,
                          static_argnames=("method", "interpret"))


def _dart_add_back_device(score, dropped, factor):
    with jax.named_scope("lgbm.dart_replay"):
        return score + factor * dropped


@functools.lru_cache(maxsize=1)
def _dart_add_back_entry():
    return _manager_entry("boosting/dart_add_back", _dart_add_back_device)


def _kth_largest(x, k: int, digit_bits: int = 1):
    """The k-th largest value of float32 [n] x, exactly and with no
    sort. Each float goes to the uint32 whose unsigned order is the
    float's (-0.0 as +0.0, as `lax.sort` compares them; sign bit set on
    a non-negative, every bit inverted on a negative), and the answer —
    the largest t with count(key >= t) >= k — is fixed from the top,
    `digit_bits` bits a pass: one fused compare-and-count over [n]
    against every value of the digit. 32 one-bit passes over 22M keys
    take 1.2 ms on a v5e, where the sort took 73 ms to hand over one
    element; wider digits make fewer passes of more compares and win
    only past ~30M rows, where the key no longer stays on the chip
    (scripts/kth_micro.py times 1, 4 and 8 bits; PERF.md section 6,
    PR 35). A loop, not 32 unrolled passes: the benchmark books a
    trace's ops by instruction name, and one body has few."""
    assert x.dtype == jnp.float32 and 32 % digit_bits == 0
    sign = jnp.uint32(1 << 31)
    bits = jax.lax.bitcast_convert_type(
        jnp.where(x == 0, jnp.float32(0), x), jnp.uint32)
    key = jnp.where(bits >= sign, ~bits, bits | sign)
    digits = jnp.arange(1, 1 << digit_bits, dtype=jnp.uint32)

    def one_pass(i, t):
        shift = jnp.uint32(32 - digit_bits) - jnp.uint32(digit_bits) * i
        # counts fall as the digit grows: the digit is how many reach k
        reach = jnp.sum(key[None, :] >= (t | (digits << shift))[:, None],
                        axis=1, dtype=jnp.int32) >= k
        return t | (jnp.sum(reach, dtype=jnp.uint32) << shift)

    t = jax.lax.fori_loop(jnp.uint32(0), jnp.uint32(32 // digit_bits),
                          one_pass, jnp.uint32(0))
    return jax.lax.bitcast_convert_type(
        jnp.where(t >= sign, t ^ sign, ~t), jnp.float32)


def _largest_k_mask(x, k: int):
    """[n] bool: the k largest of x, equal values to the lower index —
    the rows `jax.lax.top_k(x, k)` picks, found from the exact k-th
    largest value (`_kth_largest`: a few counting passes, no sort)
    instead of by scattering top_k's indices: on a TPU a [n]-sized
    scatter costs seconds at 10^7 rows."""
    kth = _kth_largest(x, k)
    above = x > kth
    tie = x == kth
    need = k - jnp.sum(above, dtype=jnp.int32)
    return above | (tie & (jnp.cumsum(tie.astype(jnp.int32)) <= need))


def _goss_sample_device(grad, hess, seed, *, top_k: int, other_k: int):
    """Device-side GOSS round (reference goss.hpp:111-147): top_k rows
    by sum_c |g*h|, other_k uniform from the rest upweighted by
    (n - top_k) / other_k. Returns the weighted (grad, hess) and the
    bag as a [n] bool flag — all without host round-trips of [C, N]
    arrays, and with no sort and no [N]-sized scatter or gather: both
    selections are masks from an exact k-th value (counting passes over
    the float bits), the weighting is a select, and the flag is the two
    masks' union. What lays the flagged rows out in lane order, ascending
    as the host path's sorted bag, is the grower's compaction pass
    (treelearner/fused.py _compact_bag)."""
    with jax.named_scope("lgbm.goss_sample"):
        n = grad.shape[1]
        weight = jnp.sum(jnp.abs(grad * hess), axis=0)            # [n]
        is_top = _largest_k_mask(weight, top_k)
        # uniform sample WITHOUT replacement from the rest: random keys,
        # top rows masked below every real key, take the other_k largest
        r = jax.random.uniform(jax.random.PRNGKey(seed), (n,))
        sampled = _largest_k_mask(jnp.where(is_top, -1.0, r), other_k)
        multiply = jnp.float32((n - top_k) / other_k)
        grad = jnp.where(sampled[None, :], grad * multiply, grad)
        hess = jnp.where(sampled[None, :], hess * multiply, hess)
        in_bag = is_top | sampled
    return grad, hess, in_bag


@functools.lru_cache(maxsize=1)
def _goss_sample_entry():
    """Manager-registered entry for the GOSS sampling kernel, so its
    (re)compiles land in the same compile counters as the rest of the
    stack instead of hiding behind an ad-hoc module-level jit."""
    return _manager_entry("boosting/goss_sample", _goss_sample_device,
                          static_argnames=("top_k", "other_k"))


class GOSS(GBDT):
    """Gradient-based One-Side Sampling (reference goss.hpp:25)."""

    def init(self, config, train_data, objective, metrics):
        super().init(config, train_data, objective, metrics)
        if config.bagging_freq > 0 and config.bagging_fraction != 1.0:
            log.fatal("Cannot use bagging in GOSS")
        if not (config.top_rate > 0 and config.other_rate > 0
                and config.top_rate + config.other_rate <= 1.0):
            log.fatal("Invalid top_rate/other_rate for GOSS")
        log.info("Using GOSS")

    def _sampling_plan(self) -> Optional[str]:
        cfg = self.config
        return f"goss(top_rate={cfg.top_rate:g}, other_rate={cfg.other_rate:g})"

    def _bagging(self, iteration: int) -> None:
        cfg = self.config
        n = self.num_data
        if iteration < int(1.0 / cfg.learning_rate):
            self._set_bag(None, n)
            return
        top_k = max(1, int(n * cfg.top_rate))
        other_k = max(1, min(int(n * cfg.other_rate), n - top_k))
        if self._in_bag is None:
            log.info("GOSS sampling starts at iteration %d: top_k=%d "
                     "other_k=%d, %d of %d rows a tree", iteration, top_k,
                     other_k, top_k + other_k, n)
        seed = jnp.int32(self._bag_rng.randint(1 << 31))
        self._grad, self._hess, in_bag = _goss_sample_entry()(
            self._grad, self._hess, seed, top_k=top_k, other_k=other_k)
        self._set_bag(in_bag, top_k + other_k)


class RF(GBDT):
    """Random forest mode (reference rf.hpp:25): constant baseline
    gradients each iteration, no shrinkage, averaged output."""

    def init(self, config, train_data, objective, metrics):
        super().init(config, train_data, objective, metrics)
        self.average_output = True
        self.shrinkage_rate = 1.0
        if not (config.bagging_freq > 0 and config.bagging_fraction < 1.0):
            log.fatal("Random forest needs bagging_freq > 0 and bagging_fraction < 1")

    def _boosting(self) -> None:
        # gradients from the constant init score, not the accumulated one
        k = self.num_tree_per_iteration
        if not hasattr(self, "_rf_base_score"):
            init = np.zeros((k, self.num_data), dtype=np.float32)
            for c in range(k):
                init[c] = self.objective.boost_from_score(c)
            self._rf_base_score = jnp.asarray(init)
        if k == 1:
            g, h = self.objective.get_gradients(self._rf_base_score[0])
            self._grad, self._hess = g[None, :], h[None, :]
        else:
            self._grad, self._hess = self.objective.get_gradients(self._rf_base_score)

    def _boost_from_average(self, class_id, update_scorer):
        return 0.0

    def _update_score(self, tree: Tree, class_id: int) -> None:
        # averaged output: score accumulates tree outputs; final predict
        # divides by iteration count (handled at predict via shrinkage)
        super()._update_score(tree, class_id)


def create_boosting(boosting_type: str) -> GBDT:
    """reference Boosting::CreateBoosting (boosting.cpp:35)."""
    if boosting_type == "gbdt":
        return GBDT()
    if boosting_type == "dart":
        return DART()
    if boosting_type == "goss":
        return GOSS()
    if boosting_type == "rf":
        return RF()
    log.fatal("Unknown boosting type %s", boosting_type)
    return GBDT()
