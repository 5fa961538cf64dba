"""Objective functions (gradient/hessian producers).

TPU re-design of the reference objective layer
(reference: src/objective/ — factory at objective_function.cpp:15-52;
regression_objective.hpp, binary_objective.hpp, multiclass_objective.hpp,
xentropy_objective.hpp, rank_objective.hpp). Per-row OpenMP loops become
jitted jnp element-wise programs over the score array; the ranking
objectives build padded per-query segments instead of per-query scalar
loops (no sigmoid lookup table — transcendentals are cheap on the VPU).

Every objective exposes:
- ``get_gradients(score) -> (grad, hess)``  [device, jitted]
- ``boost_from_score(class_id) -> float``   (BoostFromScore)
- ``convert_output(raw)``                   (ConvertOutput)
- ``is_renew_tree_output`` / ``renew_tree_output(...)`` leaf refits
  (L1/quantile/MAPE percentile refits, RenewTreeOutput)
- ``num_tree_per_iteration`` (num_class for softmax)
"""
from __future__ import annotations

import functools
from typing import Callable, Dict, Optional

import numpy as np
import jax
import jax.numpy as jnp

from ..config import Config
from ..utils import log


def _np_weighted_percentile(values: np.ndarray, weights: Optional[np.ndarray],
                            alpha: float) -> float:
    """PercentileFun / WeightedPercentileFun, faithful to the reference
    (regression_objective.hpp:18-88). Two quirks of that code are
    mirrored deliberately rather than "fixed": the unweighted rule
    selects DESCENDING at float_pos = (1-alpha)*cnt via ArgMaxAtK
    (so the even-count median of [1,2,3,4] is 3, not 2.5), and the
    weighted rule interpolates only when the next item's cumulative-
    weight step is >= 1.0 — with threshold < cdf[pos], i.e. a negative
    interpolation factor, exactly as the reference computes it."""
    n = len(values)
    if n == 0:
        return 0.0
    if n <= 1:
        return float(values[0])
    if weights is None:
        float_pos = (1.0 - alpha) * n
        pos = int(float_pos)
        if pos < 1:
            return float(np.max(values))
        if pos >= n:
            return float(np.min(values))
        bias = float_pos - pos
        d = np.sort(values)[::-1]            # descending, like ArgMaxAtK
        return float(d[pos - 1] - (d[pos - 1] - d[pos]) * bias)
    order = np.argsort(values, kind="stable")
    sv = values[order]
    cdf = np.cumsum(weights[order].astype(np.float64))
    threshold = alpha * cdf[-1]
    pos = int(np.searchsorted(cdf, threshold, side="right"))  # upper_bound
    pos = min(pos, n - 1)
    if pos == 0 or pos == n - 1:
        return float(sv[pos])
    v1, v2 = float(sv[pos - 1]), float(sv[pos])
    if cdf[pos + 1] - cdf[pos] >= 1.0:
        return (threshold - cdf[pos]) / (cdf[pos + 1] - cdf[pos]) \
            * (v2 - v1) + v1
    return float(v2)


def _gradient_kernel(fn):
    """The jit of a pointwise `get_gradients(self, score)` (static self),
    its ops under `lgbm.grad`: the scope the persistent program gives the
    same step, so one reader serves the per-tree tiers too. `self` carries
    the labels: every dataset builds the entry anew (ROADMAP A6)."""
    @functools.wraps(fn)
    def scoped(self, score):
        with jax.named_scope("lgbm.grad"):
            return fn(self, score)
    jitted = jax.jit(scoped, static_argnums=0)
    entries = {}

    @functools.wraps(fn)
    def dispatch(self, score):
        if self.name not in entries:
            from ..compile import get_manager
            entries[self.name] = get_manager().jit_entry(
                f"objective/get_gradients/{self.name}", jitted)
        return entries[self.name](self, score)
    dispatch.lower = jitted.lower       # AOT introspection, as a jit's
    return dispatch


class ObjectiveFunction:
    name = "custom"
    num_tree_per_iteration = 1
    is_constant_hessian = False
    is_renew_tree_output = False
    need_group = False

    def __init__(self, config: Config) -> None:
        self.config = config

    def init(self, metadata, num_data: int) -> None:
        self.num_data = num_data
        self.label = None if metadata.label is None else \
            np.asarray(metadata.label, dtype=np.float32)
        self.weights = None if metadata.weights is None else \
            np.asarray(metadata.weights, dtype=np.float32)
        self._label_dev = None if self.label is None else jnp.asarray(self.label)
        self._weights_dev = None if self.weights is None else jnp.asarray(self.weights)

    # -- helpers -------------------------------------------------------
    def _apply_weights(self, grad, hess):
        if self._weights_dev is not None:
            return grad * self._weights_dev, hess * self._weights_dev
        return grad, hess

    def get_gradients(self, score):
        raise NotImplementedError

    # -- persistent fused-loop hooks (treelearner/fused.py) ------------
    # Pointwise objectives can run gradients INSIDE the single-dispatch
    # training iteration, where rows live in leaf-permuted lane order.
    # ``persistent_aux`` returns (label_plane, weight_plane_or_None):
    # per-row constants that travel through the partition alongside the
    # score; ``persistent_grads(score, label, weight)`` must be a pure
    # jittable mirror of get_gradients over those planes. None = not
    # supported (ranking and renew-output objectives).
    def persistent_aux(self):
        return None

    def persistent_grads(self, score, label, weight):
        raise NotImplementedError

    def persistent_renew_spec(self):
        """(alpha, weighted) for the in-program leaf refit of
        renew-tree-output objectives (treelearner/fused.py
        _renew_leaf_outputs), or None when the objective has no leaf
        renewal. ``weighted`` must match whether ``persistent_aux``
        carries a weight plane — the refit reads it as the percentile
        weights (reference regression_objective.hpp RenewTreeOutput)."""
        return None

    def boost_from_score(self, class_id: int) -> float:
        return 0.0

    def convert_output(self, raw):
        return raw

    def renew_tree_output(self, pred_leaf: np.ndarray, residuals: np.ndarray,
                          num_leaves: int) -> Optional[np.ndarray]:
        return None

    def to_string(self) -> str:
        return self.name


# ---------------------------------------------------------------------------
# regression family (reference regression_objective.hpp)
# ---------------------------------------------------------------------------

class RegressionL2(ObjectiveFunction):
    name = "regression"

    def __init__(self, config: Config) -> None:
        super().__init__(config)
        self.sqrt = config.reg_sqrt

    def init(self, metadata, num_data):
        super().init(metadata, num_data)
        if self.sqrt and self.label is not None:
            self.label = np.sign(self.label) * np.sqrt(np.abs(self.label))
            self._label_dev = jnp.asarray(self.label)
        self.is_constant_hessian = self.weights is None

    # tpulint: jit-ok(per-objective gradient kernel; static self, stable arity)
    @_gradient_kernel
    def get_gradients(self, score):
        g = score.astype(jnp.float32) - self._label_dev
        h = jnp.ones_like(g)
        return self._apply_weights(g, h)

    def persistent_aux(self):
        return self._label_dev, self._weights_dev

    def persistent_grads(self, score, label, weight):
        g = score - label
        h = jnp.ones_like(g)
        if weight is not None:
            g, h = g * weight, h * weight
        return g, h

    def boost_from_score(self, class_id):
        if self.weights is not None:
            return float(np.sum(self.label * self.weights) / np.sum(self.weights))
        return float(np.mean(self.label))

    def convert_output(self, raw):
        if self.sqrt:
            return jnp.sign(raw) * raw * raw
        return raw

    def to_string(self):
        return self.name + (" sqrt" if self.sqrt else "")


class RegressionL1(RegressionL2):
    name = "regression_l1"
    is_renew_tree_output = True

    # tpulint: jit-ok(per-objective gradient kernel; static self, stable arity)
    @_gradient_kernel
    def get_gradients(self, score):
        diff = score.astype(jnp.float32) - self._label_dev
        g = jnp.sign(diff)
        h = jnp.ones_like(g)
        return self._apply_weights(g, h)

    def persistent_grads(self, score, label, weight):
        g = jnp.sign(score - label)
        h = jnp.ones_like(g)
        if weight is not None:
            g, h = g * weight, h * weight
        return g, h

    def persistent_renew_spec(self):
        return 0.5, getattr(self, "weights", None) is not None

    def boost_from_score(self, class_id):
        return _np_weighted_percentile(self.label, self.weights, 0.5)

    def renew_tree_output(self, pred_leaf, residuals, num_leaves):
        """Median of residuals per leaf (reference
        RegressionL1loss::RenewTreeOutput, regression_objective.hpp:249)."""
        out = np.zeros(num_leaves)
        for leaf in range(num_leaves):
            m = pred_leaf == leaf
            w = None if self.weights is None else self.weights[m]
            out[leaf] = _np_weighted_percentile(residuals[m], w, 0.5)
        return out


class RegressionHuber(RegressionL2):
    name = "huber"

    def __init__(self, config: Config) -> None:
        super().__init__(config)
        self.alpha = config.alpha
        if self.alpha <= 0:
            log.fatal("alpha should be greater than 0 in huber")

    # tpulint: jit-ok(per-objective gradient kernel; static self, stable arity)
    @_gradient_kernel
    def get_gradients(self, score):
        diff = score.astype(jnp.float32) - self._label_dev
        g = jnp.where(jnp.abs(diff) <= self.alpha, diff,
                      jnp.sign(diff) * self.alpha)
        h = jnp.ones_like(g)
        return self._apply_weights(g, h)

    def persistent_grads(self, score, label, weight):
        diff = score - label
        g = jnp.where(jnp.abs(diff) <= self.alpha, diff,
                      jnp.sign(diff) * self.alpha)
        h = jnp.ones_like(g)
        if weight is not None:
            g, h = g * weight, h * weight
        return g, h


class RegressionFair(RegressionL2):
    name = "fair"

    def __init__(self, config: Config) -> None:
        super().__init__(config)
        self.c = config.fair_c

    # tpulint: jit-ok(per-objective gradient kernel; static self, stable arity)
    @_gradient_kernel
    def get_gradients(self, score):
        x = score.astype(jnp.float32) - self._label_dev
        c = self.c
        g = c * x / (jnp.abs(x) + c)
        h = c * c / (jnp.abs(x) + c) ** 2
        return self._apply_weights(g, h)

    def persistent_grads(self, score, label, weight):
        x = score - label
        c = self.c
        g = c * x / (jnp.abs(x) + c)
        h = c * c / (jnp.abs(x) + c) ** 2
        if weight is not None:
            g, h = g * weight, h * weight
        return g, h

    def boost_from_score(self, class_id):
        return 0.0


class RegressionPoisson(RegressionL2):
    name = "poisson"

    def __init__(self, config: Config) -> None:
        super().__init__(config)
        self.max_delta_step = config.poisson_max_delta_step

    def init(self, metadata, num_data):
        super().init(metadata, num_data)
        self.is_constant_hessian = False
        if self.label is not None and np.any(self.label < 0):
            log.fatal("[poisson]: at least one target label is negative")

    # tpulint: jit-ok(per-objective gradient kernel; static self, stable arity)
    @_gradient_kernel
    def get_gradients(self, score):
        s = score.astype(jnp.float32)
        g = jnp.exp(s) - self._label_dev
        h = jnp.exp(s + self.max_delta_step)
        return self._apply_weights(g, h)

    def persistent_grads(self, score, label, weight):
        g = jnp.exp(score) - label
        h = jnp.exp(score + self.max_delta_step)
        if weight is not None:
            g, h = g * weight, h * weight
        return g, h

    def boost_from_score(self, class_id):
        mean = RegressionL2.boost_from_score(self, class_id)
        return float(np.log(max(mean, 1e-20)))

    def convert_output(self, raw):
        return jnp.exp(raw)


class RegressionQuantile(RegressionL2):
    name = "quantile"
    is_renew_tree_output = True

    def __init__(self, config: Config) -> None:
        super().__init__(config)
        self.alpha = config.alpha
        if not (0.0 < self.alpha < 1.0):
            log.fatal("alpha should be in (0, 1) for quantile")

    # tpulint: jit-ok(per-objective gradient kernel; static self, stable arity)
    @_gradient_kernel
    def get_gradients(self, score):
        delta = score.astype(jnp.float32) - self._label_dev
        g = jnp.where(delta >= 0, 1.0 - self.alpha, -self.alpha)
        h = jnp.ones_like(g)
        return self._apply_weights(g, h)

    def persistent_grads(self, score, label, weight):
        delta = score - label
        g = jnp.where(delta >= 0, 1.0 - self.alpha, -self.alpha)
        h = jnp.ones_like(g)
        if weight is not None:
            g, h = g * weight, h * weight
        return g, h

    def persistent_renew_spec(self):
        return self.alpha, getattr(self, "weights", None) is not None

    def boost_from_score(self, class_id):
        return _np_weighted_percentile(self.label, self.weights, self.alpha)

    def renew_tree_output(self, pred_leaf, residuals, num_leaves):
        out = np.zeros(num_leaves)
        for leaf in range(num_leaves):
            m = pred_leaf == leaf
            w = None if self.weights is None else self.weights[m]
            out[leaf] = _np_weighted_percentile(residuals[m], w, self.alpha)
        return out


class RegressionMAPE(RegressionL1):
    name = "mape"
    is_renew_tree_output = True

    def init(self, metadata, num_data):
        super().init(metadata, num_data)
        lw = 1.0 / np.maximum(1.0, np.abs(self.label))
        if self.weights is not None:
            lw = lw * self.weights
        self.label_weight = lw.astype(np.float32)
        self._label_weight_dev = jnp.asarray(self.label_weight)
        self.is_constant_hessian = self.weights is None

    # tpulint: jit-ok(per-objective gradient kernel; static self, stable arity)
    @_gradient_kernel
    def get_gradients(self, score):
        diff = score.astype(jnp.float32) - self._label_dev
        g = jnp.sign(diff) * self._label_weight_dev
        h = jnp.ones_like(g) if self._weights_dev is None else self._weights_dev
        return g, h

    def persistent_aux(self):
        # the weight plane carries label_weight = w / max(1, |label|):
        # it is both the gradient scale and the renewal percentile
        # weight (reference RegressionMAPELOSS::RenewTreeOutput)
        return self._label_dev, self._label_weight_dev

    def persistent_grads(self, score, label, weight):
        g = jnp.sign(score - label) * weight
        # sample weight = label_weight * max(1, |label|)
        h = weight * jnp.maximum(1.0, jnp.abs(label))
        return g, h

    def persistent_renew_spec(self):
        return 0.5, True

    def boost_from_score(self, class_id):
        return _np_weighted_percentile(self.label, self.label_weight, 0.5)

    def renew_tree_output(self, pred_leaf, residuals, num_leaves):
        out = np.zeros(num_leaves)
        for leaf in range(num_leaves):
            m = pred_leaf == leaf
            out[leaf] = _np_weighted_percentile(residuals[m],
                                                self.label_weight[m], 0.5)
        return out


class RegressionGamma(RegressionPoisson):
    name = "gamma"

    # tpulint: jit-ok(per-objective gradient kernel; static self, stable arity)
    @_gradient_kernel
    def get_gradients(self, score):
        s = score.astype(jnp.float32)
        g = 1.0 - self._label_dev / jnp.exp(s)
        h = self._label_dev / jnp.exp(s)
        return self._apply_weights(g, h)

    def persistent_grads(self, score, label, weight):
        g = 1.0 - label / jnp.exp(score)
        h = label / jnp.exp(score)
        if weight is not None:
            g, h = g * weight, h * weight
        return g, h


class RegressionTweedie(RegressionPoisson):
    name = "tweedie"

    def __init__(self, config: Config) -> None:
        super().__init__(config)
        self.rho = config.tweedie_variance_power

    # tpulint: jit-ok(per-objective gradient kernel; static self, stable arity)
    @_gradient_kernel
    def get_gradients(self, score):
        s = score.astype(jnp.float32)
        y = self._label_dev
        rho = self.rho
        g = -y * jnp.exp((1 - rho) * s) + jnp.exp((2 - rho) * s)
        h = (-y * (1 - rho) * jnp.exp((1 - rho) * s)
             + (2 - rho) * jnp.exp((2 - rho) * s))
        return self._apply_weights(g, h)

    def persistent_grads(self, score, label, weight):
        rho = self.rho
        g = -label * jnp.exp((1 - rho) * score) + jnp.exp((2 - rho) * score)
        h = (-label * (1 - rho) * jnp.exp((1 - rho) * score)
             + (2 - rho) * jnp.exp((2 - rho) * score))
        if weight is not None:
            g, h = g * weight, h * weight
        return g, h


# ---------------------------------------------------------------------------
# binary (reference binary_objective.hpp:21)
# ---------------------------------------------------------------------------

class BinaryLogloss(ObjectiveFunction):
    name = "binary"

    def __init__(self, config: Config, is_pos: Optional[Callable] = None) -> None:
        super().__init__(config)
        self.sigmoid = config.sigmoid
        if self.sigmoid <= 0:
            log.fatal("Sigmoid parameter %f should be greater than zero",
                      self.sigmoid)
        self.is_unbalance = config.is_unbalance
        self.scale_pos_weight = config.scale_pos_weight
        self._is_pos = is_pos or (lambda y: y > 0)

    def init(self, metadata, num_data):
        super().init(metadata, num_data)
        is_pos = self._is_pos(self.label)
        cnt_pos = int(np.sum(is_pos))
        cnt_neg = num_data - cnt_pos
        if cnt_pos == 0 or cnt_neg == 0:
            log.warning("Contains only one class")
        w_pos, w_neg = 1.0, 1.0
        if self.is_unbalance and cnt_pos > 0 and cnt_neg > 0:
            if cnt_pos > cnt_neg:
                w_neg = cnt_pos / cnt_neg
            else:
                w_pos = cnt_neg / cnt_pos
        w_pos *= self.scale_pos_weight
        self._sign = jnp.asarray(np.where(is_pos, 1.0, -1.0).astype(np.float32))
        self._lw = jnp.asarray(np.where(is_pos, w_pos, w_neg).astype(np.float32))
        self._cnt_pos, self._cnt_neg = cnt_pos, cnt_neg
        self.is_constant_hessian = False

    # tpulint: jit-ok(per-objective gradient kernel; static self, stable arity)
    @_gradient_kernel
    def get_gradients(self, score):
        s = score.astype(jnp.float32)
        response = -self._sign * self.sigmoid / \
            (1.0 + jnp.exp(self._sign * self.sigmoid * s))
        abs_resp = jnp.abs(response)
        g = response * self._lw
        h = abs_resp * (self.sigmoid - abs_resp) * self._lw
        return self._apply_weights(g, h)

    def persistent_aux(self):
        # one aux plane: signed per-row weight sign*lw*w (sign in {+-1},
        # lw*w > 0) — recovered as sign() / abs() in persistent_grads
        aux = self._sign * self._lw
        if self._weights_dev is not None:
            aux = aux * self._weights_dev
        return aux, None

    def persistent_grads(self, score, label, weight):
        sign = jnp.sign(label)
        lw = jnp.abs(label)
        response = -sign * self.sigmoid / \
            (1.0 + jnp.exp(sign * self.sigmoid * score))
        abs_resp = jnp.abs(response)
        g = response * lw
        h = abs_resp * (self.sigmoid - abs_resp) * lw
        return g, h

    def boost_from_score(self, class_id):
        if self.weights is not None:
            suml = float(np.sum(self._is_pos(self.label) * self.weights))
            sumw = float(np.sum(self.weights))
        else:
            suml = float(np.sum(self._is_pos(self.label)))
            sumw = float(self.num_data)
        pavg = min(max(suml / max(sumw, 1e-15), 1e-15), 1.0 - 1e-15)
        initscore = float(np.log(pavg / (1.0 - pavg)) / self.sigmoid)
        log.info("[%s:BoostFromScore]: pavg=%f -> initscore=%f", self.name,
                 pavg, initscore)
        return initscore

    def convert_output(self, raw):
        return 1.0 / (1.0 + jnp.exp(-self.sigmoid * raw))

    def to_string(self):
        return f"{self.name} sigmoid:{self.sigmoid}"


# ---------------------------------------------------------------------------
# multiclass (reference multiclass_objective.hpp:24/:186)
# ---------------------------------------------------------------------------

class MulticlassSoftmax(ObjectiveFunction):
    name = "multiclass"

    def __init__(self, config: Config) -> None:
        super().__init__(config)
        self.num_class = config.num_class
        self.num_tree_per_iteration = self.num_class
        self.factor = self.num_class / max(self.num_class - 1.0, 1.0)

    def init(self, metadata, num_data):
        super().init(metadata, num_data)
        lab = self.label.astype(np.int32)
        if np.any((lab < 0) | (lab >= self.num_class)):
            log.fatal("Label must be in [0, %d) for multiclass", self.num_class)
        self._onehot = jnp.asarray(
            (lab[None, :] == np.arange(self.num_class)[:, None]).astype(np.float32))
        self.factor = self.num_class / max(self.num_class - 1, 1)

    # tpulint: jit-ok(per-objective gradient kernel; static self, stable arity)
    @_gradient_kernel
    def get_gradients(self, score):
        """score: [num_class, N] raw scores; returns [num_class, N] each."""
        p = jax.nn.softmax(score.astype(jnp.float32), axis=0)
        g = p - self._onehot
        h = self.factor * p * (1.0 - p)
        if self._weights_dev is not None:
            g = g * self._weights_dev[None, :]
            h = h * self._weights_dev[None, :]
        return g, h

    def convert_output(self, raw):
        return jax.nn.softmax(raw, axis=-1)


class MulticlassOVA(ObjectiveFunction):
    name = "multiclassova"

    def __init__(self, config: Config) -> None:
        super().__init__(config)
        self.num_class = config.num_class
        self.num_tree_per_iteration = self.num_class
        self.sigmoid = config.sigmoid
        self._binary: list = []

    def init(self, metadata, num_data):
        super().init(metadata, num_data)
        self._binary = []
        for k in range(self.num_class):
            b = BinaryLogloss(self.config,
                              is_pos=functools.partial(
                                  lambda y, kk: np.abs(y - kk) < 1e-9, kk=k))
            b.init(metadata, num_data)
            self._binary.append(b)

    def get_gradients(self, score):
        gs, hs = [], []
        for k in range(self.num_class):
            g, h = self._binary[k].get_gradients(score[k])
            gs.append(g)
            hs.append(h)
        return jnp.stack(gs), jnp.stack(hs)

    def boost_from_score(self, class_id):
        return self._binary[class_id].boost_from_score(0)

    def convert_output(self, raw):
        return 1.0 / (1.0 + jnp.exp(-self.sigmoid * raw))


# ---------------------------------------------------------------------------
# cross entropy (reference xentropy_objective.hpp)
# ---------------------------------------------------------------------------

class CrossEntropy(ObjectiveFunction):
    name = "cross_entropy"

    def init(self, metadata, num_data):
        super().init(metadata, num_data)
        if np.any((self.label < 0) | (self.label > 1)):
            log.fatal("[%s]: label must be in [0, 1]", self.name)

    # tpulint: jit-ok(per-objective gradient kernel; static self, stable arity)
    @_gradient_kernel
    def get_gradients(self, score):
        z = 1.0 / (1.0 + jnp.exp(-score.astype(jnp.float32)))
        g = z - self._label_dev
        h = z * (1.0 - z)
        return self._apply_weights(g, h)

    def persistent_aux(self):
        return self._label_dev, self._weights_dev

    def persistent_grads(self, score, label, weight):
        z = 1.0 / (1.0 + jnp.exp(-score))
        g = z - label
        h = z * (1.0 - z)
        if weight is not None:
            g, h = g * weight, h * weight
        return g, h

    def boost_from_score(self, class_id):
        if self.weights is not None:
            pavg = float(np.sum(self.label * self.weights) / np.sum(self.weights))
        else:
            pavg = float(np.mean(self.label))
        pavg = min(max(pavg, 1e-15), 1.0 - 1e-15)
        return float(np.log(pavg / (1.0 - pavg)))

    def convert_output(self, raw):
        return 1.0 / (1.0 + jnp.exp(-raw))


class CrossEntropyLambda(ObjectiveFunction):
    name = "cross_entropy_lambda"

    def init(self, metadata, num_data):
        super().init(metadata, num_data)
        if np.any((self.label < 0) | (self.label > 1)):
            log.fatal("[%s]: label must be in [0, 1]", self.name)

    # tpulint: jit-ok(per-objective gradient kernel; static self, stable arity)
    @_gradient_kernel
    def get_gradients(self, score):
        """Reference xentropy_objective.hpp:185-213: unweighted variant
        equals plain cross-entropy; the weighted variant treats the score
        as a log-intensity with prob = 1-(1-z)^w."""
        s = score.astype(jnp.float32)
        if self._weights_dev is None:
            z = 1.0 / (1.0 + jnp.exp(-s))
            g = z - self._label_dev
            h = z * (1.0 - z)
            return g, h
        w = self._weights_dev
        y = self._label_dev
        epf = jnp.exp(s)
        hhat = jnp.log1p(epf)
        z = 1.0 - jnp.exp(-w * hhat)
        enf = 1.0 / epf
        g = (1.0 - y / z) * w / (1.0 + enf)
        c = 1.0 / (1.0 - z)
        d = 1.0 + epf
        a = w * epf / (d * d)
        d = c - 1.0
        b = (c / (d * d)) * (1.0 + w * epf - c)
        h = a * (1.0 + y * b)
        return g, h

    def boost_from_score(self, class_id):
        havg = float(np.mean(self.label)) if self.weights is None else \
            float(np.sum(self.label * self.weights) / np.sum(self.weights))
        initscore = float(np.log(max(np.exp(havg) - 1.0, 1e-15)))
        log.info("[%s:BoostFromScore]: havg=%f -> initscore=%f", self.name,
                 havg, initscore)
        return initscore

    def convert_output(self, raw):
        return jnp.log1p(jnp.exp(raw))


# ---------------------------------------------------------------------------
# factory (reference objective_function.cpp:15)
# ---------------------------------------------------------------------------

_REGISTRY: Dict[str, type] = {
    "regression": RegressionL2,
    "regression_l1": RegressionL1,
    "huber": RegressionHuber,
    "fair": RegressionFair,
    "poisson": RegressionPoisson,
    "quantile": RegressionQuantile,
    "mape": RegressionMAPE,
    "gamma": RegressionGamma,
    "tweedie": RegressionTweedie,
    "binary": BinaryLogloss,
    "multiclass": MulticlassSoftmax,
    "multiclassova": MulticlassOVA,
    "cross_entropy": CrossEntropy,
    "cross_entropy_lambda": CrossEntropyLambda,
}


def create_objective(config: Config) -> Optional[ObjectiveFunction]:
    """CreateObjectiveFunction; returns None for objective=custom (the
    caller must then supply gradients, reference
    objective_function.cpp:49-51)."""
    name = config.objective
    if name == "custom":
        return None
    if name in ("lambdarank", "rank_xendcg"):
        from .rank import LambdarankNDCG, RankXENDCG
        return (LambdarankNDCG if name == "lambdarank" else RankXENDCG)(config)
    cls = _REGISTRY.get(name)
    if cls is None:
        log.fatal("Unknown objective type name: %s", name)
    return cls(config)
