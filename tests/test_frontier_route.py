"""``frontier.route`` against a plain numpy statement of the routing rule.

A case in a slot that split goes to the slot's first child plus: its bin for
a discrete split, 0 or 1 by the threshold for a continuous one, and the
heaviest child where its value is unknown (bin -1).  A case in no slot, or
in a slot that did not split, keeps its node.
"""

import numpy as np
import pytest

import jax.numpy as jnp

from repro.core import frontier
from repro.core.config import GrowConfig

N_BINS = 256
# three continuous attributes, then discrete ones of 5, 9 and 20 values
CONT = np.array([True, True, True, False, False, False])
VALUES = np.array([N_BINS, N_BINS, N_BINS, 5, 9, 20])
N_CASES = 3000


def _route_reference(case_node, slot, x, internal, best_attr, is_cont, sb,
                     heaviest, child0):
    out = case_node.copy()
    for i, s in enumerate(slot):
        if s < 0 or not internal[s]:
            continue
        b = x[i, best_attr[s]]
        if b < 0:
            j = heaviest[s]
        elif is_cont[s]:
            j = 0 if b <= sb[s] else 1
        else:
            j = b
        out[i] = child0[s] + j
    return out


def _random_state(rng, k):
    a = len(CONT)
    # every slot's split cycles over the attributes, so each kind of split
    # appears once K passes the attribute count; every third slot is a leaf
    best_attr = (np.arange(k) + rng.integers(a)) % a
    internal = np.arange(k) % 3 != 2
    is_cont = CONT[best_attr]
    # continuous thresholds at 0, at B-1 (every known case goes left) and
    # in between
    sb = np.where(is_cont,
                  np.choose(np.arange(k) % 3,
                            [np.zeros(k, int), np.full(k, N_BINS - 1),
                             rng.integers(0, N_BINS, k)]),
                  -1)
    nch = np.where(is_cont, 2, VALUES[best_attr])
    heaviest = rng.integers(0, nch)
    # children sit at the top of the largest tree routing allows, so the
    # lookup carries ids just under 2**24
    child0 = frontier.MAX_NODES - 1 - np.cumsum(nch[::-1])[::-1]
    slot = rng.integers(-1, k, N_CASES)
    x = np.stack([rng.integers(0, v, N_CASES) for v in VALUES], axis=1)
    x[rng.random(x.shape) < 0.15] = -1                    # unknown values
    case_node = rng.integers(0, 1 << 17, N_CASES)
    return (case_node, slot, x, internal, best_attr, is_cont, sb, heaviest,
            child0)


@pytest.mark.parametrize("k", [2, 7, 64, 256])
def test_route_equals_the_numpy_rule(k):
    rng = np.random.default_rng(k)
    state = _random_state(rng, k)
    case_node, slot, x, internal, best_attr, is_cont, sb, heaviest, \
        child0 = state
    got = frontier.route(
        jnp.asarray(case_node, jnp.int32), jnp.asarray(slot, jnp.int32),
        jnp.asarray(x, jnp.int32),
        attr=jnp.asarray(np.where(internal, best_attr, -1), jnp.int32),
        thr=jnp.asarray(np.where(internal & is_cont, sb, -1), jnp.int32),
        heaviest=jnp.asarray(heaviest, jnp.int32),
        child0=jnp.asarray(child0, jnp.int32))
    want = _route_reference(*state)
    np.testing.assert_array_equal(np.asarray(got), want)
    # the draw reaches every branch of the rule
    moved = (slot >= 0) & internal[np.maximum(slot, 0)]
    assert (got != case_node).any() and (~moved).any()
    assert (x[moved, best_attr[slot[moved]]] == -1).any()


def test_node_ids_past_float32_integers_are_refused():
    # routing carries node ids through float32, exact up to 2**24
    frontier.FrontierProblem(10, 2, 4, 2, 2, GrowConfig(max_nodes=1 << 24))
    with pytest.raises(ValueError, match="max_nodes"):
        frontier.FrontierProblem(10, 2, 4, 2, 2,
                                 GrowConfig(max_nodes=(1 << 24) + 1))
