"""The ``kddcup99`` configuration: its stand-in makes KDD Cup 99's schema in
the published order with the published class counts scaled exactly, the
same bytes from the same seed, and at a small size the frontier grows its
tree as the float64 reference decides it, 70-way ``service`` split and
all."""

import numpy as np
import pytest

from bench import c45_ref, data
from bench import run as harness
from bench.generators import kdd99_standin as kdd
from bench.modes.grow import _host_tree
from bench.tests.test_generators import digest

CONFIG = harness.load(harness.BENCH / "configs" / "kddcup99.json")
SPEC = CONFIG["data"]
SMALL = 3000
#: sha256 of the dataset at the configured count, taken with ``digest``.
PINNED = (153076,
          "816d440c64c24b2ae2722930a4d0bb05c4061c45c5eda499b594956f0db69fd3")


def small_spec(**kw):
    return dict(SPEC, n_cases=SMALL, **kw)


@pytest.mark.parametrize("n_cases", [SPEC["n_cases"], SMALL])
def test_standin_makes_the_published_schema_and_counts(n_cases):
    ds = data.make(dict(SPEC, n_cases=n_cases))
    pub = CONFIG["published"]
    assert len(kdd.NAMES) == ds["x"].shape[1] == pub["n_attrs"] == 41
    assert kdd.NAMES[:6] == ("duration", "protocol_type", "service", "flag",
                             "src_bytes", "dst_bytes")
    assert kdd.NAMES[-1] == "dst_host_srv_rerror_rate"
    is_cont = ds["attr_is_cont"]
    assert is_cont.tolist() == list(kdd.IS_CONT)
    assert int(is_cont.sum()) == pub["n_continuous"] == 34
    assert [kdd.NAMES[j] for j in np.flatnonzero(~is_cont)] == [
        "protocol_type", "service", "flag", "land", "logged_in",
        "is_host_login", "is_guest_login"]
    assert ds["n_bins"][~is_cont].tolist() == pub["discrete_values"] == [
        3, 70, 11, 2, 2, 2, 2] == list(kdd.CARDINALITIES)
    assert ds["n_bins"][is_cont].max() <= SPEC["n_bins"]
    assert (ds["x"] < ds["n_bins"]).all() and (ds["x"] >= 0).all()
    assert ds["n_bins"][kdd.NAMES.index("num_outbound_cmds")] == 1
    # labels: the published counts by largest remainder, each class >= 2
    got = np.bincount(ds["y"], minlength=23)
    want, _ = kdd.scaled_counts(list(pub["class_counts"].values()), n_cases)
    assert got.tolist() == want.tolist()
    assert got.sum() == n_cases and got.min() >= 2
    assert ds["n_classes"] == pub["n_classes"] == 23


def test_scaled_counts_are_the_largest_remainders():
    counts = list(CONFIG["published"]["class_counts"].values())
    full, lifted = kdd.scaled_counts(counts, sum(counts))
    assert full.tolist() == counts and lifted.size == 0
    got, lifted = kdd.scaled_counts([5, 3, 2], 5, least=1)
    assert got.tolist() == [3, 1, 1] and lifted.size == 0   # tie: lower index
    got, lifted = kdd.scaled_counts([100, 1, 1], 10)   # quotas 9.8 .1 .1
    assert got.tolist() == [6, 2, 2] and lifted.tolist() == [1, 2]


def test_configuration_lists_the_lifted_classes():
    names = list(SPEC["class_counts"])
    _, lifted = kdd.scaled_counts(list(SPEC["class_counts"].values()),
                                  SPEC["n_cases"])
    assert SPEC["class_counts"] == CONFIG["published"]["class_counts"]
    assert SPEC["n_cases"] == CONFIG["n_cases"]
    assert CONFIG["assumed"]["lifted_classes"] == [names[i] for i in lifted]


def test_same_seed_same_bytes():
    assert digest(data.make(small_spec())) == digest(data.make(small_spec()))
    assert digest(data.make(small_spec())) != digest(
        data.make(small_spec(data_seed=1)))


def test_dataset_is_bit_identical_to_the_pinned_one():
    n_cases, want = PINNED
    assert SPEC["n_cases"] == n_cases
    assert digest(data.make(SPEC)) == want


@pytest.mark.parametrize("impl", ["jnp", "pallas"])
def test_frontier_grows_the_reference_tree(impl):
    """All 23 classes at a few thousand cases; 32 bins only so that the
    kernels' interpret mode stays quick."""
    from repro.core import frontier
    from repro.core.binning import BinnedDataset
    from repro.core.config import GrowConfig

    ds = data.make(small_spec(n_bins=32))
    assert np.bincount(ds["y"], minlength=23).min() >= 2
    grow = CONFIG["grow"]
    cfg = GrowConfig(**dict(grow["config"], max_nodes=8192,
                            frontier_slots=32, compact_min_bucket=256))
    bds = BinnedDataset(
        x=ds["x"], y=ds["y"], w=np.ones(SMALL, np.float32),
        attr_is_cont=ds["attr_is_cont"], n_bins=ds["n_bins"],
        bin_edges=tuple(np.arange(b, dtype=np.float64)
                        for b in ds["n_bins"]),
        n_classes=ds["n_classes"])
    tree = _host_tree(frontier.build(bds, cfg, impl=impl))
    got = c45_ref.check_tree(ds, tree, dict(
        min_objs=cfg.min_objs, max_depth=cfg.max_depth,
        eps_gain=grow["eps_gain"]))
    assert got["freq_mismatch_nodes"] == 0
    assert got["structure_faults"] == 0, got["fault_kinds"]
    assert got["gain_gap_bits"] < CONFIG["limits"]["gain_gap_bits"]
    service = kdd.NAMES.index("service")
    split = tree["node_nchild"] > 0
    assert np.any(split & (tree["node_attr"] == service)
                  & (tree["node_nchild"] == 70))
