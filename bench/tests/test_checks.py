"""``correct`` on the CPU at a small size: a sound run passes, and the
control and each fault planted under the timed path make it fail.

These drive whole runs of the harness (set-up, window, reference) with its
look for a chip skipped and the v5e row of the peak table given; the
kernels run in interpret mode."""

import dataclasses

import numpy as np
import pytest

from bench import run as harness

CASES = 3000
PEAK_KIND = "TPU v5 lite"


@pytest.fixture
def small(monkeypatch):
    """Shrink every configuration to a size the CPU grows in seconds."""
    load = harness.load

    def small_load(path):
        d = load(path)
        if path.parent.name == "configs":
            d["data"]["n_cases"] = CASES
        return d

    monkeypatch.setattr(harness, "load", small_load)
    return monkeypatch


def execute(cell, *, control=False, seed=2**31 + 5):
    return harness.execute(["--workload", cell, "--seed", str(seed),
                            "--seconds", "1", "--trace", "0"],
                           peak_kind=PEAK_KIND, control=control)


def test_sound_run_is_correct(small):
    res = execute("syd10m9a.grow")
    assert res["correct"]
    assert res["failed"] == 0
    assert res["metrics"]["tree_s"]["value"] > 0


def test_grow_control_fails_the_gain_gap(small):
    res = execute("syd10m9a.grow", control=True)
    gap = res["checks"]["gain_gap_bits"]
    assert not res["correct"]
    assert gap["value"] > gap["limit"]
    assert res["program"]["gain_gap_bits"] <= gap["limit"]
    assert res["failed"] == res["attempted"]


def test_unknown_device_kind_is_an_error(small):
    with pytest.raises(harness.BenchError, match="no row"):
        harness.execute(["--workload", "syd10m9a.grow", "--seed", "1",
                         "--seconds", "1"], peak_kind="TPU v0")


def _alter_split(tree):
    t = tree.to_numpy()
    n = int(t.n_nodes)
    at = np.nonzero((t.node_nchild[:n] == 2) & (t.node_split_bin[:n] > 0))[0]
    if len(at):                 # the warm-up's one-node tree has none
        t.node_split_bin = t.node_split_bin.copy()
        t.node_split_bin[at[0]] -= 1
    return t


GROW_FAULTS = {
    # an answer altered where it is produced: one threshold moved
    "split_altered": lambda build, ds, cfg, **kw: _alter_split(
        build(ds, cfg, **kw)),
    # half of the cases left out of the counts
    "half_cases_left_out": lambda build, ds, cfg, **kw: build(
        ds, cfg, case_w=np.where(np.arange(ds.n_cases) % 2, 0.0, ds.w)
        .astype(np.float32), **kw),
    # no superstep changes the state: the root stays the only node
    "state_unchanged": lambda build, ds, cfg, **kw: build(
        ds, dataclasses.replace(cfg, max_depth=0), **kw),
}


@pytest.mark.parametrize("fault", sorted(GROW_FAULTS))
def test_grow_fault_is_not_correct(small, fault):
    from repro.core import frontier
    build = frontier.build
    small.setattr(frontier, "build", lambda ds, cfg, **kw:
                  GROW_FAULTS[fault](build, ds, cfg, **kw))
    res = execute("syd10m9a.grow")
    assert not res["correct"]
    assert res["failed"] == res["attempted"]
