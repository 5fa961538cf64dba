"""Packed-forest batch inference — one dispatch for the whole model.

TPU re-design of the reference prediction stack (reference:
src/boosting/gbdt_prediction.cpp PredictRaw's per-row per-tree node
chasing, src/c_api.cpp:60 SingleRowPredictor, and
src/boosting/prediction_early_stop.cpp margin-based early stop).

The host-side per-tree loop in GBDT.predict_raw costs one device
dispatch per tree (~500 dispatches for a full model). Here every
tree's flat node arrays are
stacked into [T, Nmax] device tensors once, and a single jitted
program either scans over trees (no early stop) or runs a
`lax.while_loop` over boosting iterations with a per-row `done` mask
(early stop: rows whose margin exceeds the threshold stop accumulating
trees, exactly the reference's partial-sum semantics; the loop exits
as soon as EVERY row passed, which is where the compute saving lands).

Categorical splits traverse a single concatenated bitset pool with
per-tree family offsets (same layout trick as the reference's
cat_boundaries_, tree.h).
"""
from __future__ import annotations

import functools
from typing import List, Sequence

import numpy as np
import jax
import jax.numpy as jnp

K_CATEGORICAL_MASK = 1
K_DEFAULT_LEFT_MASK = 2


class PackedForest:
    """Stacked device arrays for a list of materialized Trees."""

    TREE_BLOCK = 64

    def __init__(self, trees: Sequence, num_classes: int) -> None:
        self.num_trees = len(trees)
        self.num_classes = num_classes
        # pad the stack to a TREE_BLOCK multiple with no-op stumps
        # (root -1 -> leaf 0, value 0) for the blocked traversal
        t = -(-max(self.num_trees, 1) // self.TREE_BLOCK) * self.TREE_BLOCK
        nmax = max([max(tr.num_nodes, 1) for tr in trees] or [1])
        lmax = max([max(tr.num_leaves, 1) for tr in trees] or [1])

        split_feature = np.zeros((t, nmax), np.int32)
        threshold = np.zeros((t, nmax), np.float32)
        left = np.full((t, nmax), -1, np.int32)
        right = np.full((t, nmax), -1, np.int32)
        default_left = np.zeros((t, nmax), bool)
        missing_type = np.zeros((t, nmax), np.int32)
        is_cat = np.zeros((t, nmax), bool)
        cat_idx = np.zeros((t, nmax), np.int32)
        leaf_value = np.zeros((t, lmax), np.float32)
        # -1 root => single-leaf tree: rows resolve to leaf 0 immediately
        root = np.zeros(t, np.int32)
        root[self.num_trees:] = -1

        bitset_words: List[np.ndarray] = []
        fam_counts: List[int] = []
        fam_bounds: List[int] = [0]
        word_total = 0
        for i, tr in enumerate(trees):
            n = tr.num_nodes
            if n == 0:
                root[i] = -1
                leaf_value[i, 0] = tr.leaf_value[0]
                fam_counts.append(0)
                continue
            split_feature[i, :n] = tr.split_feature[:n]
            threshold[i, :n] = tr.threshold[:n]
            left[i, :n] = tr.left_child[:n]
            right[i, :n] = tr.right_child[:n]
            dt = tr.decision_type[:n]
            default_left[i, :n] = (dt & K_DEFAULT_LEFT_MASK) != 0
            missing_type[i, :n] = (dt.astype(np.int32) >> 2) & 3
            is_cat[i, :n] = (dt & K_CATEGORICAL_MASK) != 0
            # local cat family index -> global family index
            fam_offset = len(fam_bounds) - 1
            cat_idx[i, :n] = tr.threshold_in_bin[:n] + fam_offset
            bounds = list(tr.cat_boundaries or [0])
            for a, b in zip(bounds[:-1], bounds[1:]):
                fam_bounds.append(fam_bounds[-1] + (b - a))
            if tr.cat_threshold:
                words = np.asarray(tr.cat_threshold, dtype=np.uint32)
                bitset_words.append(words)
                word_total += len(words)
            fam_counts.append(len(bounds) - 1)
            leaf_value[i, :tr.num_leaves] = tr.leaf_value[:tr.num_leaves]

        self.split_feature = jnp.asarray(split_feature)
        self.threshold = jnp.asarray(threshold)
        self.left = jnp.asarray(left)
        self.right = jnp.asarray(right)
        self.default_left = jnp.asarray(default_left)
        self.missing_type = jnp.asarray(missing_type)
        self.is_cat = jnp.asarray(is_cat)
        self.cat_idx = jnp.asarray(cat_idx)
        self.leaf_value = jnp.asarray(leaf_value)
        self.root = jnp.asarray(root)
        # per-row node gathers carry a fixed ~10ns/row toll on TPU, so
        # the traversal packs every node attribute into ONE [T, N, 4]
        # int32 word table: one gather per level instead of eight.
        # w0 = sf | mt<<16 | dl<<18 | is_cat<<19; w1 = threshold bits;
        # w2 = (left & 0xffff) | right<<16 (sign-extended on decode);
        # w3 = cat family index
        self.has_cat = bool(is_cat.any())
        w0 = (split_feature.astype(np.int64)
              | (missing_type.astype(np.int64) << 16)
              | (default_left.astype(np.int64) << 18)
              | (is_cat.astype(np.int64) << 19)).astype(np.int32)
        w1 = threshold.view(np.int32)
        w2 = ((left.astype(np.int64) & 0xffff)
              | ((right.astype(np.int64) & 0xffff) << 16)).astype(np.int32)
        self.node_words = jnp.asarray(
            np.stack([w0, w1, w2, cat_idx], axis=-1))
        self.tree_class = jnp.asarray(
            np.arange(t, dtype=np.int32) % max(num_classes, 1))
        self.cat_bitset = jnp.asarray(
            np.concatenate(bitset_words) if bitset_words
            else np.zeros(1, np.uint32))
        self.cat_boundaries = jnp.asarray(np.asarray(fam_bounds, np.int32))

    # ------------------------------------------------------------------
    def _tree_slices(self):
        return (self.root, self.node_words, self.leaf_value,
                self.tree_class)

    def _leaf_of(self, x, root, node_words):
        """Leaf index of every row of x in ONE tree (depth-step
        while_loop; reference Tree::Predict NumericalDecision chain).
        One packed-word gather + one feature-value gather per level."""
        n = x.shape[0]
        node = jnp.broadcast_to(root, (n,)).astype(jnp.int32)
        K_ZERO = 1e-35

        def cond(node):
            return jnp.any(node >= 0)

        def body(node):
            nid = jnp.maximum(node, 0)
            w = node_words[nid]                       # [n, 4] one gather
            f = w[:, 0] & 0xffff
            mt = (w[:, 0] >> 16) & 3
            dl = ((w[:, 0] >> 18) & 1) == 1
            thr = jax.lax.bitcast_convert_type(w[:, 1], jnp.float32)
            lc = jnp.left_shift(w[:, 2], 16) >> 16    # sign-extend
            rc = w[:, 2] >> 16
            v = jnp.take_along_axis(x, f[:, None], axis=1)[:, 0]
            nan = jnp.isnan(v)
            v_num = jnp.where(nan & (mt != 2), 0.0, v)
            is_zero = jnp.abs(v_num) <= K_ZERO
            is_missing = ((mt == 1) & is_zero) | ((mt == 2) & nan)
            go_left = jnp.where(is_missing, dl, v_num <= thr)
            if self.has_cat:
                ic = ((w[:, 0] >> 19) & 1) == 1
                cat_idx = w[:, 3]
                iv = jnp.where(nan, 0, v).astype(jnp.int32)
                begin = self.cat_boundaries[cat_idx]
                n_words = self.cat_boundaries[cat_idx + 1] - begin
                word_i = iv // 32
                in_range = (word_i < n_words) & (iv >= 0)
                word = self.cat_bitset[begin + jnp.where(in_range, word_i, 0)]
                cat_left = (((word >> (iv % 32).astype(jnp.uint32)) & 1) == 1) \
                    & in_range & ~(jnp.where(nan, False, v < 0)) \
                    & ~(nan & (mt == 2))
                go_left = jnp.where(ic, cat_left, go_left)
            nxt = jnp.where(go_left, lc, rc)
            return jnp.where(node < 0, node, nxt)

        node = jax.lax.while_loop(cond, body, node)
        return -node - 1

    # ------------------------------------------------------------------
    TREE_BLOCK = 64

    def _blocked(self, arr):
        """[T, ...] -> [nblk, TREE_BLOCK, ...] (trees padded at
        construction to a TREE_BLOCK multiple with no-op stumps)."""
        t = arr.shape[0]
        return arr.reshape(t // self.TREE_BLOCK, self.TREE_BLOCK,
                           *arr.shape[1:])

    def _block_leaves(self, x):
        """lax.scan over tree BLOCKS, vmap within a block: a pure scan
        pays (num_trees x depth) sequential while steps (~10k for 500
        trees, measured step-overhead-bound); a full vmap materializes
        [T, N]-shaped gathers per level (OOMs at 500 x 500k). 64-tree
        blocks advance in lockstep: nblk x depth sequential steps and
        [64, N] state."""
        def step(_, blk):
            root, words = blk
            leaf = jax.vmap(lambda r, w: self._leaf_of(x, r, w))(root, words)
            return None, leaf
        _, leaves = jax.lax.scan(
            step, None, (self._blocked(self.root),
                         self._blocked(self.node_words)))
        return leaves.reshape(-1, x.shape[0])          # [Tpad, N]

    # tpulint: jit-ok(predict-time entry; off the training hot path)
    @functools.partial(jax.jit, static_argnums=0)
    def raw_scores(self, x: jax.Array) -> jax.Array:
        """[num_classes, N] raw scores in one dispatch."""
        k = max(self.num_classes, 1)
        leaf = self._block_leaves(x)
        vals = jnp.take_along_axis(self.leaf_value, leaf, axis=1)
        if k == 1:
            return jnp.sum(vals, axis=0, keepdims=True)
        return jnp.zeros((k, x.shape[0]), jnp.float32).at[
            self.tree_class].add(vals)

    # tpulint: jit-ok(predict-time entry; off the training hot path)
    @functools.partial(jax.jit, static_argnums=0)
    def leaf_indices(self, x: jax.Array) -> jax.Array:
        """[N, T] leaf index of every row in every tree (reference
        PredictLeafIndex), one dispatch."""
        return self._block_leaves(x)[:self.num_trees].T

    # tpulint: jit-ok(predict-time entry; off the training hot path)
    @functools.partial(jax.jit, static_argnums=(0, 2))
    def raw_scores_early_stop(self, x: jax.Array, freq: int,
                              margin: float) -> jax.Array:
        """Early-stopped raw scores (reference
        prediction_early_stop.cpp): every ``freq`` boosting iterations,
        rows whose margin exceeds ``margin`` stop accumulating
        (binary margin = 2|score|, multiclass = top1 - top2); the tree
        loop exits once every row has stopped."""
        k = max(self.num_classes, 1)
        n = x.shape[0]
        iters = self.num_trees // k
        slices = self._tree_slices()

        def margin_of(score):
            if k == 1:
                return 2.0 * jnp.abs(score[0])
            top2 = jax.lax.top_k(score.T, 2)[0]
            return top2[:, 0] - top2[:, 1]

        def cond(state):
            it, _, done = state
            return (it < iters) & ~jnp.all(done)

        def body(state):
            it, score, done = state

            def class_tree(c, score):
                tree = tuple(jax.tree_util.tree_map(
                    lambda a: a[it * k + c], slices))
                (root, words, lv, cls) = tree
                leaf = self._leaf_of(x, root, words)
                return score.at[cls].add(jnp.where(done, 0.0, lv[leaf]))

            score = jax.lax.fori_loop(0, k, class_tree, score)
            it = it + 1
            check = (it % freq) == 0
            done = done | (check & (margin_of(score) > margin))
            return it, score, done

        _, score, _ = jax.lax.while_loop(
            cond, body, (jnp.int32(0), jnp.zeros((k, n), jnp.float32),
                         jnp.zeros(n, bool)))
        return score
