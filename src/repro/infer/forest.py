"""Packed forests: stacked node arrays + batched ensemble prediction.

A :class:`Forest` packs one or many :class:`~repro.core.tree.Tree`\\ s into a
padded structure-of-arrays at a common capacity: every node array gains a
leading tree axis, so the whole ensemble is one pytree of ``(T, M, ...)``
tensors.  That shape is what makes inference embarrassingly data-parallel
(the Bayesian-trees line of related work treats prediction over many trees
as *the* parallel unit): batched prediction is a ``vmap`` of the shared
descend step over the tree axis, or the Pallas traversal kernel
(:mod:`repro.kernels.tree_infer`) when the one-hot MXU formulation wins.

The heaviest-child table is precomputed at pack time
(:func:`repro.core.tree.heavy_child_table`), so unknown-value routing is
exact for any split arity in every implementation.

Implementations (all oracle-equal to per-tree :func:`repro.core.tree.predict`):

  ``ref``    — per-tree Python loop over ``tree.predict`` (the oracle);
  ``vmap``   — one jitted vmap over the stacked arrays;
  ``pallas`` — the level-synchronous traversal kernel via
               :func:`repro.kernels.ops.forest_predict`.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.tree import Tree, descend_once, heavy_child_table
from repro.kernels import tree_infer

IMPLS = ("ref", "vmap", "pallas")


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class Forest:
    """T trees stacked at common capacity M (C classes).

    Same per-node fields as :class:`~repro.core.tree.Tree` plus the
    precomputed heavy-child table and a per-tree vote weight.  ``n_nodes``
    is the live prefix per tree; padding past it is leaf-shaped (nchild 0).
    """

    node_attr: jnp.ndarray       # int32 (T, M)
    node_split_bin: jnp.ndarray  # int32 (T, M)
    node_child0: jnp.ndarray     # int32 (T, M)
    node_nchild: jnp.ndarray     # int32 (T, M)
    node_class: jnp.ndarray      # int32 (T, M)
    node_freq: jnp.ndarray       # f32   (T, M, C)
    node_depth: jnp.ndarray      # int32 (T, M)
    node_heavy: jnp.ndarray      # int32 (T, M) sibling rank of heaviest child
    n_nodes: jnp.ndarray         # int32 (T,)
    tree_weight: jnp.ndarray     # f32   (T,) ensemble vote weight

    # ------------------------------------------------------------ properties
    @property
    def n_trees(self) -> int:
        return int(self.node_attr.shape[0])

    @property
    def capacity(self) -> int:
        return int(self.node_attr.shape[1])

    @property
    def n_classes(self) -> int:
        return int(self.node_freq.shape[-1])

    @property
    def n_levels(self) -> int:
        """Descent trip count: 1 + the deepest live node over all trees."""
        nd = np.asarray(self.node_depth)
        nn = np.asarray(self.n_nodes)
        deepest = 0
        for t in range(nd.shape[0]):
            if nn[t]:
                deepest = max(deepest, int(nd[t, : nn[t]].max()))
        return deepest + 1

    # --------------------------------------------------------------- packing
    @staticmethod
    def pack(trees: list[Tree], *, weights=None,
             capacity: int | None = None) -> "Forest":
        """Stack trees' live prefixes at a common (padded) capacity."""
        if not trees:
            raise ValueError("Forest.pack: need at least one tree")
        host = [t.to_numpy() for t in trees]
        n_classes = {t.node_freq.shape[-1] for t in host}
        if len(n_classes) != 1:
            raise ValueError(f"trees disagree on n_classes: {n_classes}")
        c = n_classes.pop()
        sizes = [int(t.n_nodes) for t in host]
        m = max(max(sizes, default=1), 1)
        if capacity is not None:
            if capacity < m:
                raise ValueError(f"capacity {capacity} < largest tree {m}")
            m = capacity
        t_dim = len(host)

        def stack(field, fill, dtype, extra=()):
            out = np.full((t_dim, m, *extra), fill, dtype)
            for i, (tr, n) in enumerate(zip(host, sizes)):
                out[i, :n] = getattr(tr, field)[:n]
            return jnp.asarray(out)

        heavy = np.zeros((t_dim, m), np.int32)
        child0 = stack("node_child0", 0, np.int32)
        nchild = stack("node_nchild", 0, np.int32)
        freq = stack("node_freq", 0.0, np.float32, (c,))
        for i in range(t_dim):
            heavy[i] = np.asarray(
                heavy_child_table(child0[i], nchild[i], freq[i]))
        w = (np.ones(t_dim, np.float32) if weights is None
             else np.asarray(weights, np.float32))
        if w.shape != (t_dim,):
            raise ValueError(f"weights shape {w.shape} != ({t_dim},)")
        return Forest(
            node_attr=stack("node_attr", -1, np.int32),
            node_split_bin=stack("node_split_bin", -1, np.int32),
            node_child0=child0,
            node_nchild=nchild,
            node_class=stack("node_class", 0, np.int32),
            node_freq=freq,
            node_depth=stack("node_depth", 0, np.int32),
            node_heavy=jnp.asarray(heavy),
            n_nodes=jnp.asarray(sizes, jnp.int32),
            tree_weight=jnp.asarray(w),
        )

    def tree(self, i: int) -> Tree:
        """Unpack tree ``i`` (capacity = the forest's common capacity)."""
        return Tree(
            node_attr=self.node_attr[i],
            node_split_bin=self.node_split_bin[i],
            node_child0=self.node_child0[i],
            node_nchild=self.node_nchild[i],
            node_class=self.node_class[i],
            node_freq=self.node_freq[i],
            node_depth=self.node_depth[i],
            n_nodes=self.n_nodes[i],
        )

    def node_table(self) -> jnp.ndarray:
        """(T, NODE_COLS, M) int32 table for the Pallas traversal kernel."""
        rows = jnp.stack(
            [self.node_attr, self.node_split_bin, self.node_child0,
             self.node_nchild, self.node_heavy, self.node_class],
            axis=1).astype(jnp.int32)
        pad = tree_infer.NODE_COLS - rows.shape[1]
        return jnp.pad(rows, ((0, 0), (0, pad), (0, 0)))


# ----------------------------------------------------------------- prediction

@functools.partial(jax.jit, static_argnames=("max_depth",))
def _predict_vmap(forest: Forest, x_bins: jnp.ndarray,
                  attr_is_cont: jnp.ndarray, *, max_depth: int
                  ) -> jnp.ndarray:
    def one_tree(attr, sbin, child0, nchild, cls, heavy):
        def body(_, node):
            return descend_once(attr_is_cont, node, x_bins,
                                node_attr=attr, node_split_bin=sbin,
                                node_child0=child0, node_nchild=nchild,
                                heavy=heavy)
        node = jnp.zeros((x_bins.shape[0],), jnp.int32)
        node = jax.lax.fori_loop(0, max_depth, body, node)
        return cls[node]

    return jax.vmap(one_tree)(
        forest.node_attr, forest.node_split_bin, forest.node_child0,
        forest.node_nchild, forest.node_class, forest.node_heavy)


def predict_per_tree(forest: Forest, x_bins, attr_is_cont, *,
                     impl: str = "vmap", max_depth: int | None = None,
                     block_n: int | None = None) -> jnp.ndarray:
    """(T, N) leaf classes, one row per packed tree."""
    if impl not in IMPLS:
        raise ValueError(f"unknown impl {impl!r} (one of {IMPLS})")
    x_bins = jnp.asarray(x_bins, jnp.int32)
    attr_is_cont = jnp.asarray(attr_is_cont, bool)
    if max_depth is None:
        max_depth = forest.n_levels
    if impl == "ref":
        from repro.core.tree import predict as tree_predict
        return jnp.stack([
            tree_predict(forest.tree(i), x_bins, attr_is_cont,
                         max_depth=max_depth)
            for i in range(forest.n_trees)])
    if impl == "vmap":
        return _predict_vmap(forest, x_bins, attr_is_cont,
                             max_depth=max_depth)
    from repro.kernels import ops
    return ops.forest_predict(forest.node_table(), x_bins, attr_is_cont,
                              max_depth=max_depth, block_n=block_n)


def vote(per_tree: jnp.ndarray, tree_weight: jnp.ndarray, *,
         n_classes: int) -> jnp.ndarray:
    """Aggregate (T, N) per-tree classes into (N,) by weighted vote.

    Majority vote is the ``tree_weight == 1`` special case; ties break to
    the lowest class id (argmax convention, deterministic).
    """
    onehot = jax.nn.one_hot(per_tree, n_classes, dtype=jnp.float32)  # (T,N,C)
    tally = jnp.einsum("tnc,t->nc", onehot, tree_weight)
    return jnp.argmax(tally, axis=-1).astype(jnp.int32)


def predict(forest: Forest, x_bins, attr_is_cont, *, impl: str = "vmap",
            weighted: bool = True, max_depth: int | None = None,
            block_n: int | None = None) -> jnp.ndarray:
    """(N,) ensemble prediction: per-tree descent + weighted majority vote.

    ``weighted=False`` ignores ``tree_weight`` (plain majority).  A 1-tree
    forest returns exactly that tree's predictions for every ``impl``.
    """
    per_tree = predict_per_tree(forest, x_bins, attr_is_cont, impl=impl,
                                max_depth=max_depth, block_n=block_n)
    w = forest.tree_weight if weighted \
        else jnp.ones((forest.n_trees,), jnp.float32)
    return vote(per_tree, w, n_classes=forest.n_classes)
