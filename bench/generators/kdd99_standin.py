"""The schema-matched stand-in for KDD Cup 99 (the UCI KDD Archive's full
``kddcup.data``; "KDD Cup 99" in the paper's Table 1): 41 attributes in
the published order, 34 continuous on their published domains and 7
discrete with the published cardinalities, and 23 classes at exactly the
published counts scaled to ``n_cases``.

Labels come first.  Each class then has a profile drawn from the data
seed: a small support on each discrete attribute and a location on each
continuous one.  A case draws each attribute from its class's profile, or,
with probability ``p_camouflage`` and independently per attribute, from
``normal``'s, which keeps the rare attacks mixed with ``normal`` deep into
the tree.  Continuous columns are binned by :func:`bench.data.bin_continuous`
to at most ``n_bins`` bins; a discrete value the draw leaves unused stays an
empty bin.
"""

from __future__ import annotations

import numpy as np

from bench import data

# (name, kind) in the order of kddcup.names.  Kinds: a discrete attribute's
# cardinality as an int; "rate" two decimals in [0, 1]; "count" integers
# 0-511; "host" integers 0-255; "heavy" heavy-tailed integers, a lognormal
# whose log-location lies in [0, LOG_MAX]; "small" a Poisson counter that
# is mostly 0; "zero" the constant 0 (num_outbound_cmds, as in the data).
COLUMNS = (
    ("duration", "heavy"), ("protocol_type", 3), ("service", 70),
    ("flag", 11), ("src_bytes", "heavy"), ("dst_bytes", "heavy"),
    ("land", 2), ("wrong_fragment", "small"), ("urgent", "small"),
    ("hot", "small"), ("num_failed_logins", "small"), ("logged_in", 2),
    ("num_compromised", "small"), ("root_shell", "small"),
    ("su_attempted", "small"), ("num_root", "small"),
    ("num_file_creations", "small"), ("num_shells", "small"),
    ("num_access_files", "small"), ("num_outbound_cmds", "zero"),
    ("is_host_login", 2), ("is_guest_login", 2), ("count", "count"),
    ("srv_count", "count"), ("serror_rate", "rate"),
    ("srv_serror_rate", "rate"), ("rerror_rate", "rate"),
    ("srv_rerror_rate", "rate"), ("same_srv_rate", "rate"),
    ("diff_srv_rate", "rate"), ("srv_diff_host_rate", "rate"),
    ("dst_host_count", "host"), ("dst_host_srv_count", "host"),
    ("dst_host_same_srv_rate", "rate"), ("dst_host_diff_srv_rate", "rate"),
    ("dst_host_same_src_port_rate", "rate"),
    ("dst_host_srv_diff_host_rate", "rate"), ("dst_host_serror_rate", "rate"),
    ("dst_host_srv_serror_rate", "rate"), ("dst_host_rerror_rate", "rate"),
    ("dst_host_srv_rerror_rate", "rate"),
)
NAMES = tuple(n for n, _ in COLUMNS)
IS_CONT = tuple(not isinstance(k, int) for _, k in COLUMNS)
CARDINALITIES = tuple(k for _, k in COLUMNS if isinstance(k, int))
#: Largest support a class's profile takes on a discrete attribute, by its
#: cardinality (protocol 1, service 1-8, flag 1-3, a flag bit 1-2).
SUPPORT_MAX = {3: 1, 70: 8, 11: 3, 2: 2}
#: Upper end of a class's log-location on each heavy-tailed column.
LOG_MAX = {"duration": 10.0, "src_bytes": 14.0, "dst_bytes": 14.0}
TOP = {"count": 511, "host": 255}


def scaled_counts(counts, n_cases: int, least: int = 2):
    """Class counts at ``n_cases`` by largest remainder, ties to the lower
    class index; every class is lifted to ``least``, the cases taken from
    the largest class.  Returns the counts and the lifted classes."""
    counts = np.asarray(counts, np.int64)
    quota = counts * n_cases
    got = quota // counts.sum()
    rest = quota - got * counts.sum()
    order = np.lexsort((np.arange(counts.size), -rest))
    got[order[: n_cases - got.sum()]] += 1
    lifted = np.flatnonzero(got < least)
    big = int(np.argmax(got))
    got[big] -= int((least - got[lifted]).sum())
    got[lifted] = least
    return got, lifted


def _profiles(rng, n_classes: int):
    """Per attribute, per class: a discrete support (a list of codes) or a
    continuous location."""
    prof = []
    for name, kind in COLUMNS:
        if isinstance(kind, int):
            top = SUPPORT_MAX[kind]
            prof.append([rng.choice(kind, int(rng.integers(1, top + 1)),
                                    replace=False)
                         for _ in range(n_classes)])
        elif kind == "rate":
            prof.append(rng.uniform(0.0, 1.0, n_classes))
        elif kind in TOP:
            prof.append(rng.uniform(0.0, TOP[kind], n_classes))
        elif kind == "heavy":
            prof.append(rng.uniform(0.0, LOG_MAX[name], n_classes))
        elif kind == "small":
            prof.append(np.exp(rng.uniform(-6.0, 0.5, n_classes)))
        else:
            prof.append(np.zeros(n_classes))
    return prof


def kdd99_standin(n_cases: int, *, seed: int, class_names, class_counts,
                  p_camouflage: float, spread: dict, max_bins: int) -> dict:
    rng = np.random.default_rng(seed)
    n_classes = len(class_counts)
    normal = list(class_names).index("normal")
    counts, _ = scaled_counts(class_counts, n_cases)
    y = rng.permutation(np.repeat(np.arange(n_classes), counts))
    prof = _profiles(rng, n_classes)
    src = np.where(rng.random((n_cases, len(COLUMNS))) < p_camouflage,
                   normal, y[:, None])
    cols = []
    for j, (name, kind) in enumerate(COLUMNS):
        who = src[:, j]
        if isinstance(kind, int):
            sizes = np.array([len(s) for s in prof[j]])
            pick = (rng.random(n_cases) * sizes[who]).astype(np.int64)
            table = np.full((n_classes, max(sizes)), -1)
            for c, s in enumerate(prof[j]):
                table[c, : len(s)] = s
            cols.append(table[who, pick])
            continue
        loc = prof[j][who]
        z = rng.standard_normal(n_cases)
        if kind == "rate":
            v = np.round(np.clip(loc + spread["rate"] * z, 0.0, 1.0), 2)
        elif kind in TOP:
            v = np.clip(np.rint(loc + spread[kind] * TOP[kind] * z), 0,
                        TOP[kind])
        elif kind == "heavy":
            v = np.floor(np.exp(np.maximum(loc + spread["heavy"] * z, 0.0))
                         ) - 1.0
        elif kind == "small":
            v = rng.poisson(loc).astype(np.float64)
        else:
            v = np.zeros(n_cases)
        cols.append(v)
    return data.binned(cols, IS_CONT, y, n_classes, max_bins,
                       n_bins_disc=[k if isinstance(k, int) else 0
                                    for _, k in COLUMNS])


def make(spec: dict) -> dict:
    return kdd99_standin(spec["n_cases"], seed=spec["data_seed"],
                         class_names=list(spec["class_counts"]),
                         class_counts=list(spec["class_counts"].values()),
                         p_camouflage=spec["p_camouflage"],
                         spread=spec["spread"], max_bins=spec["n_bins"])
