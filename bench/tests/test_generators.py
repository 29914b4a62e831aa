"""Each configuration's training set comes from the generator file its
``data`` block names, found by that name alone."""

import hashlib
import sys
from pathlib import Path

import numpy as np
import pytest

from bench import data
from bench import run as harness

ROOT = Path(__file__).resolve().parents[2]
CONFIGS = [c["name"]
           for c in harness.load(ROOT / "BENCHMARK.json")["configs"]]

# sha256 of each configuration's dataset at its configured size, taken with
# :func:`digest` from the generators as they were before they moved into
# bench/generators/: a cell's tree and readings follow from these bytes.
PINNED = {
    "syd10m9a": (500_000, "100994acd03e78c4aa3684c4fcc26da9"
                          "1dcc059f1621fee1283c6a4b3519f6ce"),
    "us_census": (245_828, "8a119fd6f52ca159d5b5efe6ee539d1f"
                           "b1279776fd2e21ca24122b0c804af8ce"),
}


def digest(ds: dict) -> str:
    h = hashlib.sha256()
    for key in ("x", "y", "n_bins", "attr_is_cont"):
        a = np.ascontiguousarray(ds[key])
        h.update(f"{key} {a.dtype.str} {a.shape}\n".encode())
        h.update(a.tobytes())
    return h.hexdigest()


def config(name: str) -> dict:
    return harness.load(ROOT / "bench" / "configs" / f"{name}.json")


@pytest.mark.parametrize("name", sorted(PINNED))
def test_dataset_is_bit_identical_to_the_pinned_one(name):
    n_cases, want = PINNED[name]
    spec = config(name)["data"]
    assert spec["n_cases"] == n_cases
    assert digest(data.make(spec)) == want


@pytest.mark.parametrize("name", CONFIGS)
def test_every_configuration_makes_its_published_schema(name):
    """At the configured size: a stand-in's drawn cardinalities depend on
    the case count, and its configuration pins them at that count."""
    cfg = config(name)
    spec = cfg["data"]
    assert (data.GENERATORS / f"{spec['generator']}.py").is_file()
    ds = data.make(spec)
    pub = cfg["published"]
    n_bins, is_cont = ds["n_bins"], ds["attr_is_cont"]
    assert ds["x"].shape == (spec["n_cases"], pub["n_attrs"])
    assert ds["y"].shape == (spec["n_cases"],)
    assert int(is_cont.sum()) == pub["n_continuous"]
    assert ds["n_classes"] == pub["n_classes"]
    assert (ds["x"] >= 0).all() and (ds["x"] < n_bins).all()
    assert ((ds["y"] >= 0) & (ds["y"] < ds["n_classes"])).all()
    if "discrete_values" in pub:
        assert n_bins[~is_cont].tolist() == pub["discrete_values"]


def test_unknown_generator_is_a_bench_error():
    with pytest.raises(harness.BenchError,
                       match=r"missing bench/generators/no_such_gen\.py"):
        data.make({"generator": "no_such_gen", "n_cases": 4})


GENERATOR = '''
import numpy as np
from bench import data


def make(spec):
    n = spec["n_cases"]
    return data.binned([np.arange(n) % 3], [False], np.arange(n) % 2, 2, 0)
'''


def test_new_generator_file_is_found_by_name(tmp_path, monkeypatch):
    (tmp_path / "three_codes.py").write_text(GENERATOR)
    monkeypatch.setattr(data, "GENERATORS", tmp_path)
    monkeypatch.delitem(sys.modules, "bench_generator_three_codes",
                        raising=False)
    ds = data.make({"generator": "three_codes", "n_cases": 6})
    assert ds["x"][:, 0].tolist() == [0, 1, 2, 0, 1, 2]
    assert ds["y"].tolist() == [0, 1, 0, 1, 0, 1]
    assert ds["n_bins"].tolist() == [3]
    assert ds["n_classes"] == 2
