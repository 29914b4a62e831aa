"""Float64 witness: which f32 tree follows exact C4.5 arithmetic.

    PYTHONPATH=src python benchmarks/exact_witness.py [--cases 50000] [--seed 0]

Grows the SyD10M9A tree (2**18 nodes, 256 slots) with the
sequential ``c45`` oracle, twice on the same data:

  f32   the repository's scorer (``core/entropy.py``) on JAX's default
        backend, as every engine runs it (on a TPU it runs on the chip).
        At each node an independent float64 numpy scorer of the same C4.5
        formulas is also evaluated, and the node is counted where the two
        split decisions differ.
  f64   the same oracle with the float64 scorer deciding every node.  Its
        rounding is about 1e-16 of the gain, far below ``EPS_GAIN``, so it
        is the tree exact arithmetic gives (up to exact ties).

A split is taken only when its gain exceeds ``EPS_GAIN``, and at a node of
weight W the f32 noise of a gain is about ulp(W * log2 W) / W, which is of
the order of ``EPS_GAIN`` for W in the thousands.  The per-node counts show
how many decisions that noise flips; for each flip (up to ``MAX_SHOWN``) the
output gives the node's weight and depth, the f32 and f64 best scores, and
``rel_gap``, how far below the f64 best the f32 choice scores in float64
(near 0: a near-tie that rounding decided).  ``device_log2_max_rel_err``
is the largest relative error of ``entropy.log2`` on the default backend
over the integers 1..2**20, against float64.
"""

from __future__ import annotations

import argparse
import contextlib
import json

import jax
import numpy as np

from repro.core import c45, entropy
from repro.core.config import GrowConfig
from repro.core.tree import trees_equal
from repro.data import quest

EPS_W, EPS_GAIN = entropy.EPS_W, entropy.EPS_GAIN
MAX_SHOWN = 20


def _xlogx(p):
    return np.where(p > 0, p * np.log2(np.where(p > 0, p, 1.0)), 0.0)


def _winfo(c):
    """W * info over the last axis: W log2 W - sum n log2 n."""
    return np.maximum(_xlogx(c.sum(-1)) - _xlogx(c).sum(-1), 0.0)


def _info(c):
    w = c.sum(-1)
    safe = np.where(w > EPS_W, w, 1.0)
    ent = np.log2(safe) - _xlogx(c).sum(-1) / safe
    return np.where(w > EPS_W, np.maximum(ent, 0.0), 0.0)


def scores_f64(hist, total_w, attr_is_cont, n_bins, *, min_objs, criterion):
    """``entropy.gains_from_histogram`` for one node, in float64 numpy, plus
    the (A, B) table of every continuous candidate's score."""
    h = np.asarray(hist, np.float64)                           # (A, B, C)
    n_bins = np.asarray(n_bins)
    bins = np.arange(h.shape[1])
    f_den = total_w if total_w > EPS_W else 1.0
    ratio = criterion == "gain_ratio"

    left = np.cumsum(h, axis=1)                                # continuous
    known = left[:, -1]
    right = known[:, None] - left
    wk = known.sum(-1)
    wl, wr = left.sum(-1), right.sum(-1)
    g = (_winfo(known)[:, None] - (_winfo(left) + _winfo(right))) \
        / np.where(wk > EPS_W, wk, 1.0)[:, None]
    g = (wk / f_den)[:, None] * g
    if ratio:
        d = _info(np.stack([wl, wr], -1))
        g = np.where(d > EPS_W, g / np.where(d > EPS_W, d, 1.0), 0.0)
    ok = (bins < (n_bins - 1)[:, None]) & (wl >= min_objs) & (wr >= min_objs)
    s = np.where(ok, g, -np.inf)
    c_bin, c_score = s.argmax(1), s.max(1)

    hd = np.where((bins < n_bins[:, None])[..., None], h, 0.0)  # discrete
    par = hd.sum(1)
    wkd = par.sum(-1)
    gd = (_winfo(par) - _winfo(hd).sum(-1)) / np.where(wkd > EPS_W, wkd, 1.0)
    gd = np.where(wkd > EPS_W, np.maximum((wkd / f_den) * gd, 0.0), 0.0)
    if ratio:
        d = _info(hd.sum(-1))
        gd = np.where(d > EPS_W, gd / np.where(d > EPS_W, d, 1.0), 0.0)
    d_score = np.where((hd.sum(-1) >= min_objs).sum(-1) >= 2, gd, -np.inf)

    cont = np.asarray(attr_is_cont, bool)
    return np.where(cont, c_score, d_score), np.where(cont, c_bin, -1), s


def pick_f64(score, active):
    """``entropy.pick_best_attribute`` in float64 numpy (batch of one)."""
    masked = np.where(active, score, -np.inf)
    a = masked.argmax(-1)
    best = np.take_along_axis(masked, a[..., None], -1)[..., 0]
    return a, best, best > EPS_GAIN


def _split_att_f64(hist, total_w, ds, cfg):
    return scores_f64(hist, float(total_w), ds.attr_is_cont, ds.n_bins,
                      min_objs=cfg.min_objs, criterion=cfg.criterion)[:2]


@contextlib.contextmanager
def _patched(module, **attrs):
    old = {k: getattr(module, k) for k in attrs}
    for k, v in attrs.items():
        setattr(module, k, v)
    try:
        yield
    finally:
        for k, v in old.items():
            setattr(module, k, v)


def _decision(score, split_bin, active, pick):
    a, best, has = pick(np.asarray(score)[None], np.asarray(active)[None])
    a = int(a[0])
    return (a, int(split_bin[a])) if bool(has[0]) else None, float(best[0])


def _f64_score_of(decision, score64, table64, attr_is_cont):
    """float64 score of an f32 decision (attribute, bin); None for a leaf."""
    if decision is None:
        return None
    a, b = decision
    return float(table64[a, b] if attr_is_cont[a] else score64[a])


def device_log2_error() -> float:
    """Largest relative error of ``entropy.log2`` on the default backend
    over the integers 1..2**20, against float64."""
    x = np.arange(1, 2 ** 20 + 1, dtype=np.float64)
    got = np.asarray(jax.jit(entropy.log2)(x.astype(np.float32)), np.float64)
    want = np.log2(x)
    ok = want > 0
    return float(np.max(np.abs(got[ok] - want[ok]) / want[ok]))


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--cases", type=int, default=50_000)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    cfg = GrowConfig(max_nodes=1 << 18, frontier_slots=256)
    ds = quest.syd(args.cases, seed=args.seed)

    flips = {"f32_split_f64_leaf": 0, "f32_leaf_f64_split": 0,
             "other_split": 0}
    shown = []                          # the first MAX_SHOWN flipped nodes
    split_node = c45.split_node

    def shadowed(ds_, cfg_, *, idx, w, active, depth, freq, cls):
        dec = split_node(ds_, cfg_, idx=idx, w=w, active=active, depth=depth,
                         freq=freq, cls=cls)
        if c45.split_pre(freq, depth, cfg_):
            return dec
        hist = c45.node_histogram(ds_, idx, w)
        tw = float(w.sum())
        s32, b32 = c45.split_att(hist, tw, ds_, cfg_)
        d32, g32 = _decision(s32, b32, active, entropy.pick_best_attribute)
        s64, b64, table64 = scores_f64(
            hist, tw, ds_.attr_is_cont, ds_.n_bins, min_objs=cfg_.min_objs,
            criterion=cfg_.criterion)
        d64, g64 = _decision(s64, b64, active, pick_f64)
        if d32 != d64:
            key = ("f32_split_f64_leaf" if d64 is None else
                   "f32_leaf_f64_split" if d32 is None else "other_split")
            flips[key] += 1
            if len(shown) < MAX_SHOWN:
                c64 = _f64_score_of(d32, s64, table64, ds_.attr_is_cont)
                shown.append(dict(
                    kind=key, weight=tw, depth=depth, f32=d32, f64=d64,
                    f32_best=g32, f64_best=g64,
                    rel_gap=(None if c64 is None or d64 is None
                             else (g64 - c64) / abs(g64))))
        return dec

    with _patched(c45, split_node=shadowed):
        t32 = c45.build(ds, cfg)
    with _patched(c45, split_att=_split_att_f64), \
            _patched(entropy, pick_best_attribute=pick_f64):
        t64 = c45.build(ds, cfg)
    print(json.dumps(dict(
        backend=jax.default_backend(), cases=ds.n_cases, seed=args.seed,
        f32_equals_f64=bool(trees_equal(t32, t64)),
        f32_nodes=t32.size, f32_depth=t32.depth,
        f64_nodes=t64.size, f64_depth=t64.depth,
        nodes_where_f32_and_f64_decide_differently=flips,
        first_flips=shown, eps_gain=EPS_GAIN,
        device_log2_max_rel_err=device_log2_error())))


if __name__ == "__main__":
    main()
