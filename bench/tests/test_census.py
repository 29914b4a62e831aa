"""The ``us_census`` configuration: its cardinalities are the stand-in's
draw at its case count, and a small run of its cell is ``correct``."""

import json
from pathlib import Path

import pytest

from bench import run as harness
from bench.generators.table1_standin import standin_columns

CONFIG = Path(__file__).resolve().parents[1] / "configs" / "us_census.json"
CASES = 3000
PEAK_KIND = "TPU v5 lite"


def test_census_config_pins_the_drawn_cardinalities():
    spec = json.loads(CONFIG.read_text())["data"]
    assert spec["n_cases"] == 245_828
    _, drawn, _ = standin_columns(spec["n_cases"], name="us_census",
                                  seed=spec["data_seed"], n_attrs=67)
    assert spec["cardinalities"] == drawn
    assert max(drawn) == 11


@pytest.fixture
def small(monkeypatch):
    """The census cell at a size the CPU grows in seconds; the stand-in's
    cardinalities depend on the case count, so they are drawn again."""
    load = harness.load

    def small_load(path):
        d = load(path)
        if path == CONFIG:
            d["data"]["n_cases"] = CASES
            _, d["data"]["cardinalities"], _ = standin_columns(
                CASES, name="us_census", seed=d["data"]["data_seed"],
                n_attrs=67)
        return d

    monkeypatch.setattr(harness, "load", small_load)


def test_census_run_is_correct(small):
    res = harness.execute(["--workload", "us_census.grow",
                           "--seed", str(2**31 + 7), "--seconds", "1",
                           "--trace", "0"], peak_kind=PEAK_KIND)
    assert res["correct"]
    assert res["failed"] == 0
    assert all(c["value"] <= c["limit"] for c in res["checks"].values())
