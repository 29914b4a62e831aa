"""Plain float64 C4.5 reference for the grow cells' ``correct``.

The program's tree is read back from the device and checked node by node
against the training data the benchmark made, the way a served model's
tokens are checked against a reference's logits: the cases are routed down
the program's own splits (teacher forcing), and at every node the reference
recomputes, in float64 from the raw cases, what C4.5 (information gain,
``min_objs``, ``max_depth``, discrete attributes used once per path) would
decide there.  Nothing here imports the program.

Numbers read from one tree:

* ``freq_mismatch_nodes``: nodes whose stored class counts differ from the
  counts of the cases the program's splits route there (exact; checks the
  histogram's class axis and splitPost's routing);
* ``structure_faults``: nodes that break the tree's layout or C4.5's rules
  (breadth-first ids, child counts, depths, majority classes, a split on a
  node C4.5 must stop at, an invalid or reused split);
* ``gain_gap_bits``: the widest gap, in bits of information gain, by which
  the split the program took at a node lies below the best split the
  reference finds there; a leaf the program made where C4.5 could split
  counts its best gain above ``eps_gain``.

``control_dtype`` reads the same gap for the split that the reference
itself would take when it scores in that precision (the control).
"""

from __future__ import annotations

import numpy as np

LOG2_E = 1.4426950408889634
NODE_BLOCK = 2048           # tested nodes scored together (bounds memory)


def _xlogx(v: np.ndarray) -> np.ndarray:
    if v.dtype == np.float64:
        return np.where(v > 0, v * np.log2(np.maximum(v, 1)), 0.0)
    one = np.asarray(1, v.dtype)
    zero = np.asarray(0, v.dtype)
    lg = np.log(np.maximum(v, one)) * np.asarray(LOG2_E, v.dtype)
    return np.where(v > zero, v * lg, zero).astype(v.dtype)


def _winfo(counts: np.ndarray) -> np.ndarray:
    """W log2 W - sum_c n_c log2 n_c over the last axis (>= 0)."""
    w = counts.sum(axis=-1, dtype=counts.dtype)
    s = _xlogx(counts).sum(axis=-1, dtype=counts.dtype)
    out = _xlogx(w) - s
    return np.maximum(out, np.asarray(0, counts.dtype)).astype(counts.dtype)


def attr_gains(hist: np.ndarray, cont: bool, n_bins: int, min_objs: float,
               dtype) -> np.ndarray:
    """Information gain of every candidate split of one attribute.

    hist: (T, B, C) class counts of the cases at T nodes.  Returns (T, B-1)
    for a continuous attribute (threshold after bin b) and (T, 1) for a
    discrete one (one child per value); -inf marks an invalid candidate.
    """
    h = hist.astype(dtype)
    tot = h.sum(axis=1, dtype=dtype)                           # (T, C)
    w = tot.sum(axis=-1, dtype=dtype)                          # (T,)
    safe_w = np.where(w > 0, w, np.asarray(1, dtype)).astype(dtype)
    parent = _winfo(tot)
    if cont:
        if n_bins < 2:
            return np.full((h.shape[0], 1), -np.inf)
        left = np.cumsum(h, axis=1, dtype=dtype)[:, : n_bins - 1]
        right = (tot[:, None, :] - left).astype(dtype)
        wl = left.sum(axis=-1, dtype=dtype)
        wr = right.sum(axis=-1, dtype=dtype)
        gain = ((parent[:, None] - (_winfo(left) + _winfo(right)))
                / safe_w[:, None])
        valid = (wl >= min_objs) & (wr >= min_objs)
    else:
        kids = h[:, :n_bins]
        gain = ((parent - _winfo(kids).sum(axis=-1, dtype=dtype))
                / safe_w)[:, None]
        wk = kids.sum(axis=-1, dtype=dtype)
        valid = ((wk >= min_objs).sum(axis=-1) >= 2)[:, None]
    return np.where(valid, gain.astype(np.float64), -np.inf)


def _gaps(best, took_split, took_gain, eps_gain):
    """Gap of each node's decision below its best split: a split taken is
    worth its float64 gain (nothing where C4.5 does not allow it); a leaf
    forgoes the best gain above ``eps_gain``."""
    best = np.where(np.isfinite(best), best, 0.0)
    took = np.where(np.isfinite(took_gain), took_gain, 0.0)
    return np.where(took_split, np.maximum(best - took, 0.0),
                    np.maximum(best - eps_gain, 0.0))


class _Faults:
    def __init__(self):
        self.n = 0
        self.first: list[str] = []

    def add(self, count: int, what: str) -> None:
        if count:
            self.n += int(count)
            if len(self.first) < 8:
                self.first.append(f"{what} ({int(count)})")


def check_tree(ds: dict, tree: dict, grow: dict, *,
               control_dtype=None) -> dict:
    """Readings of one program tree against the data (see module doc).

    ds: x (N, A) int32 bins (no unknown values), y (N,), attr_is_cont (A,),
    n_bins (A,), n_classes.  tree: node_attr, node_split_bin, node_child0,
    node_nchild, node_class, node_freq, node_depth (live prefix) and
    n_nodes.  grow: min_objs, max_depth, eps_gain.
    """
    x, y = ds["x"], ds["y"].astype(np.int64)
    cont_a, nb = ds["attr_is_cont"], ds["n_bins"]
    c_dim = int(ds["n_classes"])
    n_cases, a_dim = x.shape
    if (x < 0).any():
        raise ValueError("the reference handles no unknown values")
    n = int(tree["n_nodes"])
    attr = tree["node_attr"][:n].astype(np.int64)
    sbin = tree["node_split_bin"][:n].astype(np.int64)
    child0 = tree["node_child0"][:n].astype(np.int64)
    nchild = tree["node_nchild"][:n].astype(np.int64)
    ncls = tree["node_class"][:n].astype(np.int64)
    freq = tree["node_freq"][:n].astype(np.float64)
    depth = tree["node_depth"][:n].astype(np.int64)
    min_objs = float(grow["min_objs"])
    eps_gain = float(grow["eps_gain"])

    faults = _Faults()
    freq_mismatch = 0
    gap_max = 0.0
    ctl_gap_max = 0.0
    gap_node = -1

    faults.add(int(np.sum(np.diff(depth) < 0)), "depth decreases with id")
    active = np.ones((n, a_dim), bool)
    parent_cls = np.zeros(n, np.int64)
    node_of = np.zeros(n_cases, np.int64)
    next_id, lo = 1, 0
    while lo < n:
        hi = lo + max(1, int(np.searchsorted(depth[lo:], depth[lo],
                                             side="right")))
        d = int(depth[lo])
        span = hi - lo
        at = (node_of >= lo) & (node_of < hi)
        loc = node_of[at] - lo
        ya = y[at]
        counts = np.bincount(loc * c_dim + ya, minlength=span * c_dim
                             ).reshape(span, c_dim).astype(np.float64)
        freq_mismatch += int(np.any(counts != freq[lo:hi], axis=1).sum())
        tot = counts.sum(axis=1)
        want_cls = np.where(tot > 0, np.argmax(counts, axis=1),
                            parent_cls[lo:hi])
        faults.add(int(np.sum(want_cls != ncls[lo:hi])), "majority class")
        pre_leaf = (((counts > 1e-7).sum(axis=1) <= 1)
                    | (tot < 2 * min_objs) | (d >= grow["max_depth"]))
        internal = nchild[lo:hi] > 0
        faults.add(int(np.sum(pre_leaf & internal)), "split a stop node")
        faults.add(int(np.sum(~internal & (attr[lo:hi] != -1))),
                   "leaf with an attribute")

        # ---- layout of the children this level emits --------------------
        for i in np.nonzero(internal)[0] + lo:
            a = int(attr[i])
            if not 0 <= a < a_dim:
                faults.add(1, "attribute out of range")
                continue
            want_n = 2 if cont_a[a] else int(nb[a])
            ok_bin = (0 <= sbin[i] <= nb[a] - 2) if cont_a[a] \
                else sbin[i] == -1
            faults.add(int(nchild[i] != want_n), "child count")
            faults.add(int(not ok_bin), "split bin")
            faults.add(int(not active[i, a]), "discrete attribute reused")
            faults.add(int(child0[i] != next_id), "child ids out of order")
            kids = slice(next_id, min(next_id + int(nchild[i]), n))
            next_id += int(nchild[i])
            faults.add(int(np.sum(depth[kids] != d + 1)), "child depth")
            active[kids] = active[i]
            if not cont_a[a]:
                active[kids, a] = False
            parent_cls[kids] = ncls[i]

        # ---- split scores at the nodes C4.5 tests ------------------------
        tested = np.nonzero(~pre_leaf)[0]
        for b0 in range(0, len(tested), NODE_BLOCK):
            blk = tested[b0:b0 + NODE_BLOCK]
            t_dim = len(blk)
            rank = np.full(span, -1, np.int64)
            rank[blk] = np.arange(t_dim)
            sel = rank[loc] >= 0
            r_case, y_case = rank[loc[sel]], ya[sel]
            x_case = x[at][sel]
            ids = blk + lo
            best = np.full(t_dim, -np.inf)
            chosen = np.full(t_dim, np.nan)
            ctl_best = np.full(t_dim, -np.inf)
            ctl_f64 = np.full(t_dim, -np.inf)
            for a in range(a_dim):
                bins = int(nb[a])
                flat = (r_case * bins + x_case[:, a]) * c_dim + y_case
                hist = np.bincount(flat, minlength=t_dim * bins * c_dim
                                   ).reshape(t_dim, bins, c_dim)
                g = attr_gains(hist, bool(cont_a[a]), bins, min_objs,
                               np.float64)
                g[~active[ids, a]] = -np.inf
                best = np.maximum(best, g.max(axis=1))
                mine = (nchild[ids] > 0) & (attr[ids] == a)
                col = np.where(cont_a[a], sbin[ids], 0)
                col = np.clip(col, 0, g.shape[1] - 1)
                chosen[mine] = g[mine, col[mine]]
                if control_dtype is not None:
                    gc = attr_gains(hist, bool(cont_a[a]), bins, min_objs,
                                    control_dtype)
                    gc[~active[ids, a]] = -np.inf
                    j = np.argmax(gc, axis=1)
                    v = gc[np.arange(t_dim), j]
                    better = v > ctl_best
                    ctl_best = np.where(better, v, ctl_best)
                    ctl_f64 = np.where(better, g[np.arange(t_dim), j],
                                       ctl_f64)
            split = nchild[ids] > 0
            faults.add(int(np.sum(split & ~np.isfinite(chosen))),
                       "invalid split taken")
            gap = _gaps(best, split, chosen, eps_gain)
            if gap.size and gap.max() > gap_max:
                gap_max = float(gap.max())
                gap_node = int(ids[np.argmax(gap)])
            if control_dtype is not None and t_dim:
                ctl_gap_max = max(ctl_gap_max, float(_gaps(
                    best, ctl_best > eps_gain, ctl_f64, eps_gain).max()))

        # ---- route the cases down the program's splits -------------------
        a_of = attr[node_of[at]]
        go = nchild[node_of[at]] > 0
        if go.any():
            idx = np.nonzero(at)[0][go]
            nodes = node_of[idx]
            a_sel = np.clip(a_of[go], 0, a_dim - 1)
            b = x[idx, a_sel]
            j = np.where(cont_a[a_sel], (b > sbin[nodes]).astype(np.int64), b)
            node_of[idx] = child0[nodes] + j
        lo = hi
    faults.add(int(next_id != n), "node count differs from emitted children")
    out = dict(freq_mismatch_nodes=freq_mismatch,
               structure_faults=faults.n, gain_gap_bits=gap_max,
               gain_gap_node=gap_node, fault_kinds=faults.first)
    if control_dtype is not None:
        out["control_gain_gap_bits"] = ctl_gap_max
    return out
