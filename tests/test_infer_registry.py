"""Model registry: atomic publish, fault-injected crashes, hot-swap/canary."""

import os

import numpy as np
import pytest
from conftest import make_tree_dataset

from repro.core import c45
from repro.core.config import GrowConfig
from repro.infer import forest as F
from repro.infer import registry
from repro.infer.forest import Forest


@pytest.fixture
def ds(rng):
    return make_tree_dataset(rng, n=250)


@pytest.fixture
def fo(ds, rng):
    trees = [c45.build(ds.subset(rng.choice(ds.n_cases, ds.n_cases)),
                       GrowConfig()) for _ in range(2)]
    return Forest.pack(trees)


def test_publish_versions_monotonically(tmp_path, fo):
    p1 = registry.publish(str(tmp_path), "m", fo)
    p2 = registry.publish(str(tmp_path), "m", fo)
    assert p1.endswith("v00000001") and p2.endswith("v00000002")
    assert registry.latest_valid(str(tmp_path), "m") == p2
    assert [os.path.basename(v)
            for v in registry.list_versions(str(tmp_path), "m")] \
        == ["v00000001", "v00000002"]


def test_publish_accepts_bare_tree(tmp_path, ds):
    tree = c45.build(ds, GrowConfig())
    path = registry.publish(str(tmp_path), "m", tree)
    loaded, manifest = registry.load(path)
    assert manifest["n_trees"] == 1
    got = np.asarray(F.predict(loaded, ds.x, ds.attr_is_cont))
    from repro.core.tree import predict
    np.testing.assert_array_equal(
        got, np.asarray(predict(tree, ds.x, ds.attr_is_cont)))


def test_crash_between_tmp_write_and_rename(tmp_path, fo, monkeypatch):
    """The acceptance fault: a publisher dying after staging but before the
    atomic rename must leave latest_valid() serving the prior version."""
    v1 = registry.publish(str(tmp_path), "m", fo)

    real_replace = os.replace

    def crash(src, dst):
        raise RuntimeError("injected: killed before rename")

    monkeypatch.setattr(registry.os, "replace", crash)
    with pytest.raises(RuntimeError, match="injected"):
        registry.publish(str(tmp_path), "m", fo)
    monkeypatch.setattr(registry.os, "replace", real_replace)

    # the torn tmp.* staging dir exists, but readers never see it
    leftovers = [d for d in os.listdir(tmp_path / "m")
                 if d.startswith("tmp.")]
    assert leftovers
    assert registry.latest_valid(str(tmp_path), "m") == v1
    handle = registry.ModelHandle(str(tmp_path), "m")
    assert handle.stable_path == v1

    # once stale, the torn staging dir is garbage-collected
    stale = tmp_path / "m" / leftovers[0]
    os.utime(stale, (1.0, 1.0))
    registry.latest_valid(str(tmp_path), "m")
    assert not stale.exists()


def test_latest_valid_gc_collects_stale_tmp_dirs(tmp_path, fo):
    v1 = registry.publish(str(tmp_path), "m", fo)
    # a crashed publisher's leftover: tmp dir that never reached os.replace
    stale = tmp_path / "m" / "tmp.9.1234.0"
    stale.mkdir()
    (stale / "model.npz").write_bytes(b"partial")
    os.utime(stale, (1.0, 1.0))             # epoch 1970: definitely stale
    fresh = tmp_path / "m" / "tmp.10.1234.1"   # a live publisher: survives
    fresh.mkdir()
    assert registry.latest_valid(str(tmp_path), "m") == v1
    assert not stale.exists()
    assert fresh.exists()
    # gc_tmp_age=None keeps even stale staging dirs
    stale.mkdir()
    os.utime(stale, (1.0, 1.0))
    assert registry.latest_valid(str(tmp_path), "m", gc_tmp_age=None) == v1
    assert stale.exists()


def test_corrupt_newest_falls_back(tmp_path, fo):
    registry.publish(str(tmp_path), "m", fo)
    v2 = registry.publish(str(tmp_path), "m", fo)
    with open(os.path.join(v2, "model.npz"), "r+b") as f:
        f.seek(-8, 2)
        f.write(b"\xff" * 8)
    assert not registry.verify(v2)
    assert registry.latest_valid(str(tmp_path), "m").endswith("v00000001")


def test_handle_hot_swap(tmp_path, fo):
    registry.publish(str(tmp_path), "m", fo)
    handle = registry.ModelHandle(str(tmp_path), "m")
    assert not handle.refresh()            # nothing newer yet
    v2 = registry.publish(str(tmp_path), "m", fo)
    assert handle.refresh()                # swapped in place
    assert handle.stable_path == v2
    assert not handle.refresh()


def test_handle_requires_published_model(tmp_path):
    with pytest.raises(FileNotFoundError):
        registry.ModelHandle(str(tmp_path), "ghost")


class TestCanaryRouting:
    def test_fraction_is_deterministic_and_close(self, tmp_path, fo):
        registry.publish(str(tmp_path), "m", fo)
        v2 = registry.publish(str(tmp_path), "m", fo)
        handle = registry.ModelHandle(str(tmp_path), "m")
        handle.set_canary(v2, 0.25)
        arms = [handle.route(uid) for uid in range(4000)]
        again = [handle.route(uid) for uid in range(4000)]
        assert arms == again               # same uid -> same arm, always
        frac = arms.count("canary") / len(arms)
        assert 0.2 < frac < 0.3
        handle.clear_canary()
        assert all(handle.route(u) == "stable" for u in range(100))

    def test_shadow_never_shifts_traffic(self, tmp_path, fo):
        registry.publish(str(tmp_path), "m", fo)
        v2 = registry.publish(str(tmp_path), "m", fo)
        handle = registry.ModelHandle(str(tmp_path), "m")
        handle.set_canary(v2, 0.5, shadow=True)
        assert all(handle.route(u) == "stable" for u in range(500))
        assert handle.shadow_model() is not None

    def test_promote_canary(self, tmp_path, fo):
        registry.publish(str(tmp_path), "m", fo)
        v2 = registry.publish(str(tmp_path), "m", fo)
        handle = registry.ModelHandle(str(tmp_path), "m")
        handle.set_canary(v2, 0.1)
        handle.promote_canary()
        assert handle.stable_path == v2
        assert handle.canary is None
        with pytest.raises(ValueError):
            handle.promote_canary()

    def test_canary_must_verify(self, tmp_path, fo):
        registry.publish(str(tmp_path), "m", fo)
        v2 = registry.publish(str(tmp_path), "m", fo)
        with open(os.path.join(v2, "model.npz"), "r+b") as f:
            f.seek(-8, 2)
            f.write(b"\xff" * 8)
        handle = registry.ModelHandle(str(tmp_path), "m")
        with pytest.raises(ValueError, match="verification"):
            handle.set_canary(v2, 0.5)


class TestRollback:
    def test_rollback_repoints_latest_valid(self, tmp_path, fo):
        v1 = registry.publish(str(tmp_path), "m", fo)
        v2 = registry.publish(str(tmp_path), "m", fo)
        assert registry.latest_valid(str(tmp_path), "m") == v2
        assert registry.rollback(str(tmp_path), "m") == v1
        assert registry.latest_valid(str(tmp_path), "m") == v1
        # the retired dir keeps the bits but is invisible to readers
        assert registry.list_versions(str(tmp_path), "m") == [v1]
        retired = registry.list_retired(str(tmp_path), "m")
        assert [os.path.basename(p) for p in retired] == ["retired.v00000002"]
        loaded, manifest = registry.load(retired[0])
        assert manifest["version"] == 2

    def test_rollback_never_reuses_version_numbers(self, tmp_path, fo):
        registry.publish(str(tmp_path), "m", fo)
        registry.publish(str(tmp_path), "m", fo)
        registry.rollback(str(tmp_path), "m")
        v3 = registry.publish(str(tmp_path), "m", fo)
        assert v3.endswith("v00000003")     # v2 is retired, not recycled
        assert registry.latest_valid(str(tmp_path), "m") == v3

    def test_rollback_to_empty_returns_none(self, tmp_path, fo):
        registry.publish(str(tmp_path), "m", fo)
        assert registry.rollback(str(tmp_path), "m") is None
        assert registry.latest_valid(str(tmp_path), "m") is None

    def test_rollback_without_versions_raises(self, tmp_path, fo):
        with pytest.raises(FileNotFoundError):
            registry.rollback(str(tmp_path), "ghost")
        registry.publish(str(tmp_path), "m", fo)
        registry.rollback(str(tmp_path), "m")
        with pytest.raises(FileNotFoundError):
            registry.rollback(str(tmp_path), "m")

    def test_handle_survives_rollback_of_pinned_version(self, tmp_path, fo):
        registry.publish(str(tmp_path), "m", fo)
        v2 = registry.publish(str(tmp_path), "m", fo)
        handle = registry.ModelHandle(str(tmp_path), "m")
        assert handle.stable_path == v2
        registry.rollback(str(tmp_path), "m")
        assert handle.stable is not None    # keeps serving from memory
        assert handle.refresh()             # ...and refresh repoints below
        assert handle.stable_path.endswith("v00000001")


class TestRetention:
    def test_keep_last_on_publish(self, tmp_path, fo):
        for _ in range(5):
            registry.publish(str(tmp_path), "m", fo, keep_last=3)
        names = [os.path.basename(v)
                 for v in registry.list_versions(str(tmp_path), "m")]
        assert names == ["v00000003", "v00000004", "v00000005"]
        assert registry.latest_valid(str(tmp_path), "m").endswith("v00000005")

    def test_gc_versions_reports_removed(self, tmp_path, fo):
        paths = [registry.publish(str(tmp_path), "m", fo) for _ in range(4)]
        removed = registry.gc_versions(str(tmp_path), "m", keep_last=2)
        assert removed == paths[:2]
        assert not any(os.path.exists(p) for p in removed)
        assert registry.gc_versions(str(tmp_path), "m", keep_last=2) == []

    def test_gc_also_prunes_retired(self, tmp_path, fo):
        for _ in range(4):
            registry.publish(str(tmp_path), "m", fo)
            registry.rollback(str(tmp_path), "m")
        assert len(registry.list_retired(str(tmp_path), "m")) == 4
        registry.publish(str(tmp_path), "m", fo, keep_last=2)
        retired = [os.path.basename(p)
                   for p in registry.list_retired(str(tmp_path), "m")]
        assert retired == ["retired.v00000003", "retired.v00000004"]

    def test_keep_last_must_be_positive(self, tmp_path, fo):
        registry.publish(str(tmp_path), "m", fo)
        with pytest.raises(ValueError):
            registry.gc_versions(str(tmp_path), "m", keep_last=0)
