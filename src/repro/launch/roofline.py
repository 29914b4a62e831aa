"""Roofline term extraction from compiled dry-run artifacts.

Hardware model (TPU v5e, per chip): 197 TFLOP/s bf16, 819 GB/s HBM,
~50 GB/s/link ICI.

  compute    = device_flops   / peak_flops
  memory     = device_bytes   / hbm_bw
  collective = device_coll_bytes / ici_bw

``compiled.cost_analysis()`` reports **per-device** flops / bytes on
partitioned modules (verified empirically), so the terms above divide by
per-chip peaks directly — algebraically identical to the brief's
``global / (chips x peak)`` form.

Collective bytes are not in cost_analysis: we parse the post-partitioning
HLO (``compiled.as_text()``) and sum the per-device volume of every
all-gather / all-reduce / reduce-scatter / all-to-all / collective-permute,
scaled by the ring factor of its replica-group size g:

  all-gather       result x (g-1)/g      (result = gathered local tensor)
  all-reduce       2 x result x (g-1)/g  (reduce-scatter + all-gather)
  reduce-scatter   result x (g-1)       (input = g x result shards)
  all-to-all       result x (g-1)/g
  collective-permute  result
"""

from __future__ import annotations

import dataclasses
import re
from typing import Any

PEAK_FLOPS = 197e12        # bf16 / chip
HBM_BW = 819e9             # bytes/s / chip
ICI_BW = 50e9              # bytes/s / link

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "tuple": 0, "token": 0,
}

_COLL_RE = re.compile(
    r"=\s*(?:\()?\s*(?P<dtype>\w+)\[(?P<dims>[\d,]*)\][^=]*?"
    r"\b(?P<op>all-reduce|all-gather|reduce-scatter|all-to-all|"
    r"collective-permute)(?:-start)?\(")
_GROUPS_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_GROUPS_BRACE_RE = re.compile(r"replica_groups=\{\{([^}]*)\}")


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * _DTYPE_BYTES.get(dtype, 4)


def _group_size(line: str, default: int) -> int:
    m = _GROUPS_RE.search(line)
    if m:
        return int(m.group(2))
    m = _GROUPS_BRACE_RE.search(line)
    if m:
        return len(m.group(1).split(","))
    return default


def collective_bytes(hlo_text: str, *, n_devices: int
                     ) -> tuple[float, dict[str, float]]:
    """Per-device communicated bytes (see module docstring for the model)."""
    total = 0.0
    by_op: dict[str, float] = {}
    for line in hlo_text.splitlines():
        m = _COLL_RE.search(line)
        if not m or "-done" in line.split("=")[-1][:40]:
            continue
        op = m.group("op")
        size = _shape_bytes(m.group("dtype"), m.group("dims"))
        g = _group_size(line, n_devices)
        if g <= 1:
            continue
        ring = (g - 1) / g
        vol = {"all-gather": size * ring,
               "all-reduce": 2 * size * ring,
               "reduce-scatter": size * (g - 1),
               "all-to-all": size * ring,
               "collective-permute": float(size)}[op]
        total += vol
        by_op[op] = by_op.get(op, 0.0) + vol
    return total, by_op


@dataclasses.dataclass
class Roofline:
    arch: str
    shape: str
    mesh: str
    device_flops: float
    device_bytes: float
    device_coll_bytes: float
    coll_by_op: dict[str, float]
    peak_mem_bytes: float
    arg_bytes: float
    model_flops: float        # 6*N*D (dense) / 6*N_active*D (MoE), global

    @property
    def t_compute(self) -> float:
        return self.device_flops / PEAK_FLOPS

    @property
    def t_memory(self) -> float:
        return self.device_bytes / HBM_BW

    @property
    def t_collective(self) -> float:
        return self.device_coll_bytes / ICI_BW

    @property
    def bottleneck(self) -> str:
        terms = {"compute": self.t_compute, "memory": self.t_memory,
                 "collective": self.t_collective}
        return max(terms, key=terms.get)

    @property
    def roofline_seconds(self) -> float:
        return max(self.t_compute, self.t_memory, self.t_collective)

    def useful_flops_ratio(self, n_devices: int) -> float:
        hlo_global = self.device_flops * n_devices
        return self.model_flops / hlo_global if hlo_global else 0.0

    def as_dict(self, n_devices: int) -> dict[str, Any]:
        return dict(
            arch=self.arch, shape=self.shape, mesh=self.mesh,
            device_flops=self.device_flops, device_bytes=self.device_bytes,
            device_coll_bytes=self.device_coll_bytes,
            coll_by_op=self.coll_by_op,
            t_compute=self.t_compute, t_memory=self.t_memory,
            t_collective=self.t_collective, bottleneck=self.bottleneck,
            peak_mem_gb=self.peak_mem_bytes / 1e9,
            arg_gb=self.arg_bytes / 1e9,
            model_flops=self.model_flops,
            useful_flops_ratio=self.useful_flops_ratio(n_devices),
        )


def model_flops_for(arch: str, shape_name: str) -> float:
    """6*N*D with N = (active) params, D = tokens processed by the step."""
    from repro.configs import base as cfgbase
    if arch == "yadt":
        return 0.0
    cfg = cfgbase.get_config(arch)
    shape = cfgbase.SHAPES[shape_name]
    n = cfg.active_param_count() if cfg.is_moe else cfg.param_count()
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * n * tokens
    if shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * n * tokens
    return 2.0 * n * shape.global_batch     # decode: one token per row


def analyze(compiled, *, arch: str, shape: str, mesh_desc: str,
            n_devices: int) -> Roofline:
    cost = compiled.cost_analysis()
    mem = compiled.memory_analysis()
    coll, by_op = collective_bytes(compiled.as_text(), n_devices=n_devices)
    return Roofline(
        arch=arch, shape=shape, mesh=mesh_desc,
        device_flops=float(cost.get("flops", 0.0)),
        device_bytes=float(cost.get("bytes accessed", 0.0)),
        device_coll_bytes=coll, coll_by_op=by_op,
        peak_mem_bytes=float(mem.temp_size_in_bytes
                             + mem.argument_size_in_bytes),
        arg_bytes=float(mem.argument_size_in_bytes),
        model_flops=model_flops_for(arch, shape),
    )
