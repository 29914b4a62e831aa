"""End-to-end behaviour tests for the whole system."""

import numpy as np


def test_paper_pipeline_end_to_end(rng):
    """QUEST data -> SPMD tree growth -> farm replay: the paper's full loop."""
    from repro.core import GrowConfig, predict, trees_equal
    from repro.core import c45, frontier, simulate
    from repro.data import quest

    ds = quest.generate(3_000, function=5, seed=0, perturbation=0.02)
    cfg = GrowConfig(max_nodes=1 << 13, frontier_slots=64)
    trace = []
    t_seq = c45.build(ds, cfg, task_trace=trace, capacity=cfg.max_nodes)
    t_ff = frontier.build(ds, cfg)
    assert trees_equal(t_seq, t_ff)
    acc = (np.asarray(predict(t_ff, ds.x, ds.attr_is_cont)) == ds.y).mean()
    assert acc > 0.9

    cm = simulate.calibrate(trace, measured_seq_seconds=1.0)
    nap = simulate.simulate(trace, n_workers=8, strategy="nap",
                            policy="ws", cost=cm)
    np_ = simulate.simulate(trace, n_workers=8, strategy="np",
                            policy="ws", cost=cm)
    assert nap.speedup > np_.speedup          # the paper's headline result
    assert nap.speedup > 2.0
