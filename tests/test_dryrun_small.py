"""Small-mesh dry-run in a subprocess (so the fake device count never leaks
into this test process).  Proves lower+compile coherence of the sharding
config on a miniature (2, 4) mesh for representative cells."""

import json
import subprocess
import sys
import textwrap

import pytest

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import dataclasses
    import jax
    import jax.numpy as jnp

    from repro.configs import base as cfgbase
    from repro.launch.mesh import make_auto_mesh
    from repro.launch.specs import make_cell, lower_cell
    from repro.launch import roofline as rl

    mesh = make_auto_mesh((2, 4), ("data", "model"))

    # shrink shapes so the tiny mesh compiles in seconds
    cfgbase.SHAPES = {
        "train_4k": cfgbase.ShapeSpec("train_4k", 128, 8, "train"),
        "prefill_32k": cfgbase.ShapeSpec("prefill_32k", 256, 4, "prefill"),
        "decode_32k": cfgbase.ShapeSpec("decode_32k", 256, 8, "decode"),
        "long_500k": cfgbase.ShapeSpec("long_500k", 512, 1, "decode"),
    }
    reduced = {a: cfgbase.reduced(cfgbase.get_config(a))
               for a in cfgbase.ARCH_IDS if a != "yadt"}
    cfgbase.get_config = lambda a: reduced[a]

    out = {}
    for arch, shape in [("yi_6b", "train_4k"), ("phi35_moe", "train_4k"),
                        ("gemma2_9b", "decode_32k"),
                        ("rwkv6_3b", "long_500k"),
                        ("recurrentgemma_2b", "prefill_32k")]:
        cell = make_cell(arch, shape, mesh)
        compiled = lower_cell(cell, mesh).compile()
        r = rl.analyze(compiled, arch=arch, shape=shape, mesh_desc="2x4",
                       n_devices=8)
        out[f"{arch}/{shape}"] = dict(
            flops=r.device_flops, coll=r.device_coll_bytes,
            mem=compiled.memory_analysis().temp_size_in_bytes)
    print("RESULT" + json.dumps(out))
""")


@pytest.mark.slow
def test_small_mesh_cells_compile():
    proc = subprocess.run(
        [sys.executable, "-c", SCRIPT], capture_output=True, text=True,
        timeout=1200, env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
                           "HOME": "/root",
                           # skip the libtpu probe (60 s timeout when the
                           # host has the plugin but no TPU attached)
                           "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 0, proc.stderr[-4000:]
    line = [l for l in proc.stdout.splitlines() if l.startswith("RESULT")][0]
    results = json.loads(line[len("RESULT"):])
    assert len(results) == 5
    for key, r in results.items():
        assert r["flops"] > 0, key
        assert r["coll"] > 0, f"{key}: sharded step must communicate"
