"""Ranking objectives: LambdaRank-NDCG and XE-NDCG.

TPU re-design of the reference per-query scalar loops
(reference: src/objective/rank_objective.hpp — base RankingObjective
:27-96 iterating GetGradientsForOneQuery per query; LambdarankNDCG
:98-286 with pairwise ΔNDCG-weighted lambdas; RankXENDCG :288-360).

Instead of an OpenMP loop over queries with per-pair scalar math, the
gradient of every query is ONE jitted device program an iteration, its
ops under the scope `lgbm.rank_grad`, with no host loop and no scatter:

* the layout is static. Queries are bucketed by padded size M (powers
  of two from 8), every query owns one row of its bucket's `[Q_b, M]`
  slab, documents first and in row order, then pads, and the slabs lie
  one after another in one slot vector (`_bucket_queries`, built once
  on the host, vectorised);
* a query's rows are contiguous in row order, so the way in is a
  gather of WINDOWS, one index a query: slab row q is
  `score[start_q : start_q + M]`, masked behind the query's count;
* a query's documents are ranked by one sort along the slab's rows
  (score descending, ties in row order), which carries each document's
  place in the query along (`lgbm.rank_sort`: the windows and the sort);
* the pair terms (`lgbm.rank_pairs`) are `[C, M, M]` blocks per chunk
  of C queries of a bucket, each document's sum a reduction over the
  partner axis. A bucket is walked chunk by chunk by `lax.map` inside
  the program, sorts and pairs alike, and C follows from M alone:
  what XLA makes of a chunk (and the program's scratch) does not move
  with the data, only the trip counts do;
* the way back (`lgbm.rank_to_rows`) is a second sort along the rows,
  by the place carried along, and ONE gather of the slot vector by the
  host-built slot of every row: every row has exactly one slot, so
  nothing is scattered or added.

The reference's 1M-entry sigmoid lookup table (ConstructSigmoidTable
:245-258) is unnecessary on TPU: transcendentals are vectorized
hardware ops. The truncation level enters only through CalMaxDCGAtK
(rank_objective.hpp:127-129), matching the reference. The equations are
written out in benchmarks/reference/lambdarank_numpy.py, the plain
numpy reference tests and the benchmark hold this file to.
"""
from __future__ import annotations

from typing import Dict, List, Optional

import numpy as np
import jax
import jax.numpy as jnp

from ..config import Config
from ..utils import log
from .functions import ObjectiveFunction

K_MAX_POSITION = 10000


def default_label_gain(max_label: int = 31) -> np.ndarray:
    """2^i - 1 gains (reference DCGCalculator::DefaultLabelGain)."""
    return (np.power(2.0, np.arange(max_label)) - 1.0)


class DCGCalculator:
    """reference include/LightGBM/metric.h:63 + src/metric/dcg_calculator.cpp."""

    def __init__(self, label_gain: Optional[List[float]] = None) -> None:
        if label_gain:
            self.label_gain = np.asarray(label_gain, dtype=np.float64)
        else:
            self.label_gain = default_label_gain()
        self.discount = 1.0 / np.log2(np.arange(K_MAX_POSITION) + 2.0)

    def cal_max_dcg_at_k(self, k: int, labels: np.ndarray) -> float:
        labels = np.asarray(labels)
        srt = np.sort(labels)[::-1]
        k = min(k, len(srt))
        gains = self.label_gain[srt[:k].astype(np.int64)]
        return float(np.sum(gains * self.discount[:k]))

    def cal_dcg_at_k(self, k: int, labels: np.ndarray, scores: np.ndarray) -> float:
        order = np.argsort(-scores, kind="stable")
        k = min(k, len(labels))
        lab = np.asarray(labels)[order[:k]].astype(np.int64)
        return float(np.sum(self.label_gain[lab] * self.discount[:k]))

    def check_label(self, labels: np.ndarray) -> None:
        if np.any(labels < 0) or np.any(labels >= len(self.label_gain)):
            log.fatal("Label excel(%d) in ranking cannot be handled; "
                      "set label_gain", int(np.max(labels)))


PAIR_SLOTS = 1 << 22        # pair terms one chunk of a bucket holds at once
CHUNK_QUERIES = 1024        # and queries, at most (small M)


def _bucket_queries(boundaries: np.ndarray, min_size: int = 8,
                    pair_slots: int = PAIR_SLOTS,
                    chunk_queries: int = CHUNK_QUERIES) -> Dict:
    """The static slot layout of the gradient program, built once and
    without a loop over queries.

    A query of `size` documents goes to the bucket of its padded size M
    (the power of two >= size, at least `min_size`) and owns one row of
    that bucket's `[Q_b, M]` slab: its documents first, in row order,
    then pads. A bucket's queries are cut into chunks of C queries, C a
    function of M alone (C * M * M <= `pair_slots`, C <= `chunk_queries`),
    and the bucket is filled up to whole chunks with queries of no
    document: the program walks a bucket chunk by chunk (`lax.map`), and
    a chunk's shape, so what XLA makes of it, does not move with the
    data; only the number of chunks does. Slabs lie in the slot vector
    in the order of M.

    Returns the buckets (M, their queries as given, chunking and place
    among the slot queries and the slots), every slot query's first row
    and document count, and every row's slot."""
    boundaries = np.asarray(boundaries, np.int64)
    sizes = np.diff(boundaries)
    m_of = np.full(len(sizes), min_size, np.int64)
    while np.any(m_of < sizes):             # log2(largest query) rounds
        m_of = np.where(m_of < sizes, m_of * 2, m_of)
    order = np.argsort(m_of, kind="stable")     # by bucket, then as given
    ms, counts = np.unique(m_of, return_counts=True)

    buckets = []
    slot_query_of = np.empty(len(sizes), np.int64)  # query -> slot query
    slot_of = np.empty(len(sizes), np.int64)        # query -> its first slot
    first_query = first_slot = taken = 0
    for m, q in zip(ms.tolist(), counts.tolist()):
        chunk = max(1, min(chunk_queries, pair_slots // (m * m)))
        padded = -(-q // chunk) * chunk
        queries = order[taken:taken + q]
        slot_query_of[queries] = first_query + np.arange(q)
        slot_of[queries] = first_slot + m * np.arange(q)
        buckets.append({"m": m, "queries": queries, "chunk": chunk,
                        "padded": padded, "first_query": first_query,
                        "first_slot": first_slot})
        taken += q
        first_query += padded
        first_slot += padded * m
    start = np.zeros(first_query, np.int64)
    count = np.zeros(first_query, np.int64)
    start[slot_query_of] = boundaries[:-1]
    count[slot_query_of] = sizes
    # slot of every row: its query's first slot + its place in the query
    row_slot = np.repeat(slot_of - boundaries[:-1], sizes) \
        + np.arange(int(boundaries[-1]))
    return {"buckets": buckets, "slots": first_slot, "start": start,
            "count": count, "row_slot": row_slot}


def _log2(x):
    """log2 of positive float32 to float32's own precision on any
    backend: the exponent plus 2 atanh((m - 1) / (m + 1)) / ln 2 of the
    mantissa m in [sqrt(1/2), sqrt(2)), five terms of the series (the
    sixth is under 2e-10). The TPU's own log2 is good to 1e-4, which
    would be the error of every gradient of a normalised query."""
    m, e = jnp.frexp(x)                             # x = m 2^e, m in [.5, 1)
    low = m < np.float32(np.sqrt(0.5))
    m, e = jnp.where(low, 2.0 * m, m), jnp.where(low, e - 1, e)
    z = (m - 1.0) / (m + 1.0)
    z2 = z * z
    series = 1.0 + z2 * (1 / 3 + z2 * (1 / 5 + z2 * (1 / 7 + z2 * (1 / 9))))
    return e.astype(x.dtype) + z * series * np.float32(2.0 / np.log(2.0))


class RankingObjective(ObjectiveFunction):
    """What both ranking objectives share: the static slot layout, the
    window gather that takes row-order scores to it, the gather back,
    and the one registered program an iteration."""
    need_group = True

    def __init__(self, config: Config) -> None:
        super().__init__(config)
        self.seed = config.objective_seed

    def init(self, metadata, num_data):
        super().init(metadata, num_data)
        if metadata.query_boundaries is None:
            log.fatal("Ranking tasks require query information")
        self.boundaries = np.asarray(metadata.query_boundaries, dtype=np.int64)
        self.num_queries = len(self.boundaries) - 1
        lay = self._layout = _bucket_queries(self.boundaries)
        self._layout_dev = {
            "start": jnp.asarray(lay["start"].astype(np.int32)),
            "count": jnp.asarray(lay["count"].astype(np.int32)),
            "row_slot": jnp.asarray(lay["row_slot"].astype(np.int32))}
        from ..compile import get_manager
        from ..obs import instrument_kernel
        # tpulint: jit-ok(one program an objective, registered on the next line; its shapes are the layout's)
        program = jax.jit(self._gradients_device)
        self._program = instrument_kernel(
            get_manager().jit_entry("objective/rank_grad", program),
            "boost", name="rank_grad")

    def rank_plan(self) -> Dict[str, object]:
        """`execution_plan()["rank_grad"]`: the queries, how many fell
        into each padded size, and the pair terms an iteration
        evaluates (sum of Q_b * M_b^2 over the real queries)."""
        buckets = {b["m"]: len(b["queries"]) for b in self._layout["buckets"]}
        return {"queries": self.num_queries, "buckets": buckets,
                "pair_slots": sum(m * m * q for m, q in buckets.items())}

    def _slot_values(self, rows: np.ndarray, pad) -> np.ndarray:
        """Host-side: a per-row constant in slot order, pads as given."""
        out = np.full(self._layout["slots"], pad, rows.dtype)
        out[self._layout["row_slot"]] = rows
        return out

    def _slabs(self, *slot_vectors):
        """Per bucket: (bucket, its `[padded, M]` view of each vector)."""
        for b in self._layout["buckets"]:
            lo, hi = b["first_slot"], b["first_slot"] + b["padded"] * b["m"]
            yield b, [v[lo:hi].reshape(b["padded"], b["m"])
                      for v in slot_vectors]

    @staticmethod
    def _per_query(b, vector):
        """A bucket's slice of a per-slot-query vector."""
        return vector[b["first_query"]:b["first_query"] + b["padded"]]

    def _end_padded(self, score):
        """The row-order scores with the largest M zeros behind them, so
        that no query's window of M rows is clamped at the end."""
        tail = jnp.zeros(self._layout["buckets"][-1]["m"], score.dtype)
        return jnp.concatenate([score, tail])

    @staticmethod
    def _windows(padded, start, count, m: int):
        """`[Q, M]` slab of the (end-padded) row-order scores and its mask
        of real documents: row q is the window of M rows from query q's
        first; what lies behind the query's count (the next queries'
        rows, the tail's zeros) is masked by the caller."""
        slab = jax.vmap(
            lambda at: jax.lax.dynamic_slice(padded, (at,), (m,)))(start)
        valid = jnp.arange(m, dtype=jnp.int32)[None, :] < count[:, None]
        return slab, valid

    def _to_rows(self, lay, lams, hess):
        """The buckets' `[padded, M]` lambdas and hessians, documents in
        row order, back to `[n]` rows: one gather for both."""
        with jax.named_scope("lgbm.rank_to_rows"):
            both = jnp.stack([
                jnp.concatenate([x.reshape(-1) for x in lams]),
                jnp.concatenate([x.reshape(-1) for x in hess])])
            both = jnp.take(both, lay["row_slot"], axis=1)
            return both[0], both[1]

    def _gradients_device(self, score, lay, *consts):
        with jax.named_scope("lgbm.rank_grad"):
            return self._rank_gradients(score.astype(jnp.float32), lay,
                                        *consts)

    def _rank_gradients(self, score, lay, *consts):
        """(grad [n], hess [n]) from the row-order scores, the layout's
        device arrays and the objective's own constants, all traced."""
        raise NotImplementedError


class LambdarankNDCG(RankingObjective):
    name = "lambdarank"

    def __init__(self, config: Config) -> None:
        super().__init__(config)
        self.sigmoid = config.sigmoid
        self.norm = config.lambdarank_norm
        self.truncation_level = config.lambdarank_truncation_level
        self.dcg = DCGCalculator(config.label_gain)
        if self.sigmoid <= 0:
            log.fatal("Sigmoid param %f should be greater than zero", self.sigmoid)

    def init(self, metadata, num_data):
        super().init(metadata, num_data)
        self.dcg.check_label(self.label)
        label = self.label.astype(np.int32)
        label_slots = self._slot_values(label, -1)
        # max DCG at the truncation level: per bucket, the labels sorted
        # descending along the slab's rows (pads last, gain 0)
        gain = np.concatenate([self.dcg.label_gain, [0.0]])     # [-1] = pad
        inv = np.zeros(len(self._layout["count"]))
        for b, (lab,) in self._slabs(label_slots):
            k = min(self.truncation_level, b["m"])
            top = -np.sort(-lab, axis=1)[:, :k]
            maxdcg = np.sum(gain[top] * self.dcg.discount[:k], axis=1)
            np.divide(1.0, maxdcg, out=self._per_query(b, inv),
                      where=maxdcg > 0)             # stays 0 where it is 0
        self.inverse_max_dcgs = np.empty(self.num_queries)
        for b in self._layout["buckets"]:
            self.inverse_max_dcgs[b["queries"]] = \
                self._per_query(b, inv)[:len(b["queries"])]
        self._inv_dev = jnp.asarray(inv, jnp.float32)
        self._label_slots = jnp.asarray(label_slots)
        # the labels that occur, with their gains: the program looks a
        # gain up by a select per label, not by a [slots]-sized gather
        self._gains = [(int(l), float(self.dcg.label_gain[int(l)]))
                       for l in np.unique(label)]

    def _pair_block(self, s, lab, valid, inv):
        """One chunk of one bucket: `[C, M]` scores and labels in rank
        order (pads last, masked by `valid`) -> each document's lambda
        and hessian.

        Entry (i, j) of a `[C, M, M]` block is what partner i adds to
        document j, so a document's sum runs over axis 1 and no
        transposed sum is needed: with t = +1 where j is the pair's more
        relevant document and -1 where it is the less relevant one,
        sigma * delta = t * sigma * (s_j - s_i), the pair's lambda
        reaches j as -t * A and its hessian as sigma * A * (1 - rho),
        A = sigma * dN * rho; and the query's S, the sum over pairs of
        -2 lambda, is the sum of A over the block."""
        sigma = jnp.float32(self.sigmoid)
        s = jnp.where(valid, s, 0.0)
        gain = jnp.zeros_like(s)
        for l, g in self._gains:
            gain = jnp.where(lab == l, jnp.float32(g), gain)
        # the discounts as a constant from the host's float64: the TPU's
        # float32 log2 is good to 1e-4 only (measured, PR 34), and the
        # pairs weigh by DIFFERENCES of discounts of neighbouring ranks
        disc = jnp.asarray(1.0 / np.log2(np.arange(s.shape[1]) + 2.0),
                           jnp.float32)

        l_i, l_j = lab[:, :, None], lab[:, None, :]
        pair = (l_i != l_j) & valid[:, :, None] & valid[:, None, :]
        j_high = l_j > l_i
        ds = s[:, None, :] - s[:, :, None]                  # s_j - s_i
        dg = gain[:, None, :] - gain[:, :, None]
        d_ndcg = jnp.where(j_high, dg, -dg) \
            * jnp.abs(disc[None, None, :] - disc[None, :, None]) \
            * inv[:, None, None]
        if self.norm:
            # best != worst: the scores of the query are not all equal
            best = jnp.max(jnp.where(valid, s, -jnp.inf), axis=1)
            worst = jnp.min(jnp.where(valid, s, jnp.inf), axis=1)
            d_ndcg = jnp.where((best != worst)[:, None, None],
                               d_ndcg / (0.01 + jnp.abs(ds)), d_ndcg)
        rho = 1.0 / (1.0 + jnp.exp(jnp.where(j_high, ds, -ds) * sigma))
        a = jnp.where(pair, sigma * d_ndcg * rho, 0.0)
        lam = jnp.sum(jnp.where(j_high, -a, a), axis=1)
        hes = jnp.sum(sigma * a * (1.0 - rho), axis=1)
        if self.norm:
            total = jnp.sum(a, axis=(1, 2))
            factor = jnp.where(
                total > 0,
                _log2(1.0 + total) / jnp.maximum(total, 1e-20), 1.0)
            lam, hes = lam * factor[:, None], hes * factor[:, None]
        return lam, hes

    def _chunk_lambdas(self, s, valid, lab, inv):
        """One chunk of C queries of one bucket: the ranks, the pair
        block, and the documents back in row order. `[C, M]` scores,
        mask and labels in row order and `[C]` inverse max DCGs in;
        `[C, M]` lambdas and hessians out."""
        m = s.shape[1]
        with jax.named_scope("lgbm.rank_sort"):
            # score descending, ties in row order: the place is the
            # second key, so no two documents compare equal; pads last
            place = jnp.broadcast_to(jnp.arange(m, dtype=jnp.int32), s.shape)
            neg_s, place, lab = jax.lax.sort(
                (jnp.where(valid, -s, jnp.inf), place, lab),
                dimension=1, num_keys=2, is_stable=False)
        with jax.named_scope("lgbm.rank_pairs"):
            # sorted, a query's real documents are its first `count`
            # slots, as they were before: `valid` serves both orders
            lam, hes = self._pair_block(-neg_s, lab, valid, inv)
        with jax.named_scope("lgbm.rank_to_rows"):
            _, lam, hes = jax.lax.sort((place, lam, hes), dimension=1,
                                       num_keys=1, is_stable=False)
        return lam, hes

    def _rank_gradients(self, score, lay, label_slots, inv):
        padded = self._end_padded(score)
        lams, hess = [], []
        for b, (lab,) in self._slabs(label_slots):
            with jax.named_scope("lgbm.rank_sort"):
                # the way in, a bucket at once (outside the walk: XLA
                # names a gather in a loop body after nothing)
                s, valid = self._windows(
                    padded, self._per_query(b, lay["start"]),
                    self._per_query(b, lay["count"]), b["m"])
            chunks = b["padded"] // b["chunk"]
            xs = tuple(x.reshape((chunks, b["chunk"]) + x.shape[1:])
                       for x in (s, valid, lab, self._per_query(b, inv)))
            lam, hes = jax.lax.map(lambda x: self._chunk_lambdas(*x), xs)
            lams.append(lam.reshape(s.shape))
            hess.append(hes.reshape(s.shape))
        return self._to_rows(lay, lams, hess)

    def get_gradients(self, score):
        return self._program(score, self._layout_dev, self._label_slots,
                             self._inv_dev)


class RankXENDCG(RankingObjective):
    name = "rank_xendcg"

    def init(self, metadata, num_data):
        super().init(metadata, num_data)
        self._rng = np.random.RandomState(self.seed)
        self._label_slots = jnp.asarray(self._slot_values(self.label, 0.0))

    @staticmethod
    def _bucket_lambdas(s, valid, lab, rands):
        """reference RankXENDCG::GetGradientsForOneQuery
        (rank_objective.hpp:304-357): third-order XE-NDCG approximation,
        on one bucket's `[Q, M]` slab."""
        s = jnp.where(valid, s, -jnp.inf)
        cnt = valid.sum(axis=1)
        rho = jax.nn.softmax(s, axis=1)
        rho = jnp.where(valid, rho, 0.0)
        phi = jnp.where(valid, jnp.exp2(jnp.floor(lab)) - rands, 0.0)
        inv_den = 1.0 / jnp.maximum(phi.sum(axis=1, keepdims=True), 1e-15)
        term1 = -phi * inv_den + rho
        params = jnp.where(valid, term1 / jnp.maximum(1.0 - rho, 1e-15), 0.0)
        sum_l1 = params.sum(axis=1, keepdims=True)
        term2 = rho * (sum_l1 - params)
        lam = term1 + term2
        params2 = jnp.where(valid, term2 / jnp.maximum(1.0 - rho, 1e-15), 0.0)
        sum_l2 = params2.sum(axis=1, keepdims=True)
        lam = lam + rho * (sum_l2 - params2)
        hes = rho * (1.0 - rho)
        small = (cnt <= 1)[:, None]
        lam = jnp.where(small | ~valid, 0.0, lam)
        hes = jnp.where(small | ~valid, 0.0, hes)
        return lam, hes

    def _rank_gradients(self, score, lay, label_slots, rands):
        padded = self._end_padded(score)
        with jax.named_scope("lgbm.rank_pairs"):
            out = [self._bucket_lambdas(
                *self._windows(padded, self._per_query(b, lay["start"]),
                               self._per_query(b, lay["count"]), b["m"]),
                lab, r) for b, (lab, r) in self._slabs(label_slots, rands)]
        return self._to_rows(lay, *zip(*out))

    def _draw_rands(self) -> np.ndarray:
        """One uniform draw per slot of every real query, bucket after
        bucket in the order of M (the stream the reference's per-query
        draws are stood in by); 0 in the slots of the filler queries."""
        rands = np.zeros(self._layout["slots"], np.float32)
        for b in self._layout["buckets"]:
            q = len(b["queries"])
            rands[b["first_slot"]:b["first_slot"] + q * b["m"]] = \
                self._rng.rand(q, b["m"]).reshape(-1)
        return rands

    def get_gradients(self, score):
        return self._program(score, self._layout_dev, self._label_slots,
                             jnp.asarray(self._draw_rands()))
