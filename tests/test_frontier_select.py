"""splitPre's selection is the open id range, and each case's slot its
node's offset in it.

The frontier is stepped by hand, one superstep at a time.  At every step
``split_pre``'s ``ids``, ``valid`` and ``slot`` are checked against a plain
reference that keeps its own FIFO list of open node ids: it takes the first
K, then appends the children it reads from the tree the superstep returned.
Where capacity does not force early leaves, the grown tree must also be the
sequential oracle's.
"""

import collections
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import c45, frontier
from repro.core.config import GrowConfig
from repro.core.tree import trees_equal
from repro.data import datasets

# (stand-in, scale, K, capacity): SyD10M9A's deep binary tree, U.S.
# Census's multiway splits, and a capacity the SyD10M9A tree overflows.
RUNS = {
    "syd10m9a": ("syd10m9a", 2e-5, 16, 4096),
    "us_census": ("us_census", 1e-3, 32, 4096),
    "overflow": ("syd10m9a", 2e-5, 8, 64),
}


def _reference_select(fifo, case_node, k):
    """The first ``k`` open ids, and each case's slot: its node's place
    among them, -1 where its node is not among them."""
    taken = [fifo[i] for i in range(min(k, len(fifo)))]
    place = {node: s for s, node in enumerate(taken)}
    slot = np.array([place.get(int(c), -1) for c in case_node], np.int32)
    return taken, slot


@pytest.mark.parametrize("impl", ["jnp", "pallas"])
@pytest.mark.parametrize("run", sorted(RUNS))
def test_selection_is_the_reference_fifo_at_every_superstep(run, impl):
    name, scale, k, capacity = RUNS[run]
    ds = datasets.load(name, scale=scale, max_bins=16)
    cfg = GrowConfig(max_nodes=capacity, frontier_slots=k,
                     compact_min_bucket=64)
    prob = frontier.FrontierProblem.from_dataset(ds, cfg)
    x, y = jnp.asarray(ds.x), jnp.asarray(ds.y)
    w = jnp.asarray(ds.w, jnp.float32)
    cont = jnp.asarray(ds.attr_is_cont)
    nb = jnp.asarray(ds.n_bins, jnp.int32)
    pre_fn = jax.jit(functools.partial(frontier.split_pre, prob=prob))
    step = jax.jit(frontier._superstep_fn(prob, impl))

    state = frontier.init_state(prob, y, w)
    fifo = collections.deque([0])
    steps = 0
    while fifo:
        assert bool(state.open_nodes < state.n_nodes)
        pre = pre_fn(state)
        taken, slot = _reference_select(fifo, np.asarray(state.case_node), k)
        valid = np.asarray(pre["valid"])
        ids = np.asarray(pre["ids"])
        np.testing.assert_array_equal(valid, np.arange(k) < len(taken))
        np.testing.assert_array_equal(ids[valid], taken)
        np.testing.assert_array_equal(np.asarray(pre["slot"]), slot)

        state, _ = step(state, x, y, w, cont, nb)
        tree = state.tree.to_numpy()
        for _ in taken:
            node = fifo.popleft()
            c0, nch = tree.node_child0[node], tree.node_nchild[node]
            fifo.extend(range(c0, c0 + nch))
        steps += 1
    assert not bool(state.open_nodes < state.n_nodes)
    assert int(state.supersteps) == steps
    assert int(state.open_nodes) == int(state.n_nodes)

    overflowed = bool(state.overflow)
    assert overflowed == (run == "overflow")
    if not overflowed:
        assert trees_equal(c45.build(ds, cfg, capacity=cfg.max_nodes),
                           state.tree)
