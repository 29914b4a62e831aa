"""Step builders + ShapeDtypeStruct input specs for every (arch x shape) cell.

This is the single source the dry-run, the roofline analysis and the tests
lower from.  Nothing here allocates device memory: parameters/caches are
``jax.eval_shape`` trees, inputs are ShapeDtypeStructs, and shardings come
from :mod:`repro.sharding.partitioning`.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs import base as cfgbase
from repro.configs.base import ModelConfig, ShapeSpec
from repro.models.frontends import frontend_embeds_spec
from repro.models.model import build_model
from repro.sharding import partitioning as part
from repro.train import optimizer as opt
from repro.train.train_step import TrainState, make_train_step


@dataclasses.dataclass
class Cell:
    """Everything needed to lower one (arch x shape x mesh) cell."""
    arch: str
    shape: ShapeSpec
    step_fn: Callable
    args: tuple            # ShapeDtypeStruct pytrees
    in_shardings: tuple
    out_shardings: Any
    static_kwargs: dict


def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(tuple(shape), jnp.dtype(dtype))


def _state_specs(model, cfg: ModelConfig):
    params = jax.eval_shape(model.init, jax.random.key(0))
    m, v = jax.eval_shape(opt.init_moments, params)
    return TrainState(params=params, m=m, v=v,
                      step=_sds((), jnp.int32))


def _state_shardings(state: TrainState, mesh: Mesh) -> TrainState:
    ps = part.param_shardings(state.params, mesh)
    return TrainState(
        params=ps,
        m=part.param_shardings(state.m, mesh),
        v=part.param_shardings(state.v, mesh),
        step=part.replicated(mesh))


def train_batch_specs(cfg: ModelConfig, shape: ShapeSpec) -> dict:
    b, s = shape.global_batch, shape.seq_len
    batch = {"tokens": _sds((b, s), jnp.int32),
             "labels": _sds((b, s), jnp.int32)}
    fe = frontend_embeds_spec(cfg, b)
    if fe is not None:
        batch["frontend_embeds"] = fe
    return batch


def input_specs(arch: str, shape_name: str) -> dict:
    """Public helper: ShapeDtypeStruct stand-ins for every model input."""
    cfg = cfgbase.get_config(arch)
    shape = cfgbase.SHAPES[shape_name]
    if shape.kind == "train":
        return train_batch_specs(cfg, shape)
    if shape.kind == "prefill":
        out = {"tokens": _sds((shape.global_batch, shape.seq_len), jnp.int32)}
        fe = frontend_embeds_spec(cfg, shape.global_batch)
        if fe is not None:
            out["frontend_embeds"] = fe
        return out
    # decode: one new token against a seq_len cache
    model = build_model(cfg)
    cache = jax.eval_shape(
        lambda: model.init_cache(shape.global_batch, shape.seq_len))
    return {"token": _sds((shape.global_batch, 1), jnp.int32),
            "pos": _sds((shape.global_batch,), jnp.int32),
            "cache": cache}


# --------------------------------------------------------------------------
# cell builders per step kind
# --------------------------------------------------------------------------


def make_cell(arch: str, shape_name: str, mesh: Mesh) -> Cell:
    if arch == "yadt":
        return _yadt_cell(shape_name, mesh)
    cfg = cfgbase.get_config(arch)
    shape = cfgbase.SHAPES[shape_name]
    model = build_model(cfg)

    if shape.kind == "train":
        state = _state_specs(model, cfg)
        state_sh = _state_shardings(state, mesh)
        batch = train_batch_specs(cfg, shape)
        batch_sh = part.batch_shardings(mesh, batch)
        # Microbatching: 4 accumulation steps => per-device microbatch 4,
        # which bounds the remat carry stack + flash working set to ~1/4
        # (the production memory/batch trade at this scale).
        grad_accum = 4 if shape.global_batch >= 64 else 1
        step = make_train_step(
            lambda p, b: model.loss_fn(p, b), opt.AdamWConfig(),
            grad_accum=grad_accum)
        metrics_sh = {k: part.replicated(mesh) for k in
                      ("loss", "n_tokens", "grad_norm", "lr")}
        if cfg.is_moe:
            metrics_sh.update(moe_aux=part.replicated(mesh),
                              moe_dropped=part.replicated(mesh))
        return Cell(arch, shape, step, (state, batch),
                    (state_sh, batch_sh), (state_sh, metrics_sh), {})

    params = jax.eval_shape(model.init, jax.random.key(0))
    params_sh = part.param_shardings(params, mesh)

    if shape.kind == "prefill":
        tokens = _sds((shape.global_batch, shape.seq_len), jnp.int32)
        fe = frontend_embeds_spec(cfg, shape.global_batch)
        args = [params, tokens] + ([fe] if fe is not None else [])
        cache_shape = jax.eval_shape(
        lambda: model.init_cache(shape.global_batch, shape.seq_len))
        cache_sh = part.cache_shardings(cfg, mesh, cache_shape)
        in_sh = [params_sh,
                 list(part.batch_shardings(mesh, {"t": tokens}).values())[0]]
        if fe is not None:
            in_sh.append(
                list(part.batch_shardings(mesh, {"f": fe}).values())[0])
        out_sh = (part.logits_sharding(cfg, mesh, shape.global_batch),
                  cache_sh)

        def prefill_step(p, t, *rest):
            return model.prefill(p, t, *(rest or (None,)),
                                 max_seq=shape.seq_len)

        return Cell(arch, shape, prefill_step, tuple(args), tuple(in_sh),
                    out_sh, {})

    # decode
    long = shape.name == "long_500k"
    cache = jax.eval_shape(
        lambda: model.init_cache(shape.global_batch, shape.seq_len))
    cache_sh = part.cache_shardings(cfg, mesh, cache, long=long)
    token = _sds((shape.global_batch, 1), jnp.int32)
    pos = _sds((shape.global_batch,), jnp.int32)
    tok_sh = list(part.batch_shardings(mesh, {"t": token}).values())[0]
    pos_sh = list(part.batch_shardings(mesh, {"p": pos}).values())[0]
    out_sh = (part.logits_sharding(cfg, mesh, shape.global_batch), cache_sh)

    def decode(p, c, t, pv):
        return model.decode_step(p, c, t, pv)

    return Cell(arch, shape, decode, (params, cache, token, pos),
                (params_sh, cache_sh, tok_sh, pos_sh), out_sh, {})


# --------------------------------------------------------------------------
# the paper's own workload (arch == "yadt"): one frontier superstep
# --------------------------------------------------------------------------


def _yadt_cell(shape_name: str, mesh: Mesh) -> Cell:
    from repro.configs.yadt import WORKLOAD
    from repro.core import frontier
    from repro.core.config import GrowConfig

    wl = WORKLOAD
    # shape cells scale the case count: train_4k = full 10M-case superstep;
    # others reuse the seq_len as a case-count proxy (documented).
    shape = cfgbase.SHAPES[shape_name]
    n_cases = {"train_4k": wl.n_cases,
               "prefill_32k": wl.n_cases // 4,
               "decode_32k": wl.n_cases // 8,
               "long_500k": wl.n_cases // 16}[shape_name]
    n_cases = -(-n_cases // 512) * 512     # shardable on either mesh
    prob = frontier.FrontierProblem(
        n_cases=n_cases, n_attrs=wl.n_attrs, n_bins_max=wl.n_bins,
        n_classes=wl.n_classes, max_children=wl.max_children, cfg=wl.grow)

    state = jax.eval_shape(
        lambda: frontier.init_state(prob,
                                    jnp.zeros((n_cases,), jnp.int32),
                                    jnp.ones((n_cases,), jnp.float32)))
    x = _sds((n_cases, wl.n_attrs), jnp.int32)
    y = _sds((n_cases,), jnp.int32)
    w = _sds((n_cases,), jnp.float32)
    cont = _sds((wl.n_attrs,), jnp.bool_)
    nb = _sds((wl.n_attrs,), jnp.int32)

    dp = part.batch_axes(mesh) + ("model",)   # cases over every axis (WS limit)
    case_sh = NamedSharding(mesh, P(dp))
    case2_sh = NamedSharding(mesh, P(dp, None))
    rep = part.replicated(mesh)
    state_sh = jax.tree.map(lambda _: rep, state)
    # case->node assignment lives with the cases
    state_sh = dataclasses.replace(state_sh, case_node=case_sh)

    def superstep(state, x, y, w, cont, nb):
        new_state, stats = frontier.superstep(state, x, y, w, cont, nb,
                                              prob=prob)
        return new_state, stats

    stats_sh = {k: rep for k in ("n_processed", "n_active", "n_hist",
                                 "n_tested", "n_internal", "n_children",
                                 "max_r", "nap_nodes")}
    return Cell("yadt", shape, superstep,
                (state, x, y, w, cont, nb),
                (state_sh, case2_sh, case_sh, case_sh, rep, rep),
                (state_sh, stats_sh), {})


def lower_cell(cell: Cell, mesh: Mesh, *, unroll: bool = False, **knobs):
    import contextlib

    from repro.sharding import act
    from repro.utils import scan as uscan
    jitted = jax.jit(cell.step_fn, in_shardings=cell.in_shardings,
                     out_shardings=cell.out_shardings)
    ctx = uscan.unrolled() if unroll else contextlib.nullcontext()
    with mesh, act.from_mesh(mesh, **knobs), ctx:
        return jitted.lower(*cell.args)


def make_analysis_cells(arch: str, shape_name: str, mesh: Mesh
                        ) -> list[tuple[Cell, float]]:
    """Cells to lower *unrolled* for exact cost accounting + their scales.

    cost_analysis counts loop bodies once (see utils/scan.py).  Unrolling
    the whole train step is too slow to compile (>9 min/cell on this host),
    so costs are **composed from small unrolled pieces**, each compiling in
    seconds, scaled analytically:

      train:  n_cycles x [cycle_grad + cycle_fwd(remat recompute)]
              + tail_grad + tail_fwd + embed_grad + ce_grad + ce_fwd(remat)
              — all x grad_accum — + one optimizer step.
      prefill: n_cycles x cycle_fwd + tail_fwd + embed_fwd.
      decode / yadt: the production step itself (scan-free already).

    ZeRO all-gathers / grad reduce-scatters happen inside each piece, so the
    collective term composes identically.
    """
    from repro.models import layers as L
    from repro.models import transformer as T

    if arch == "yadt":
        return [(make_cell(arch, shape_name, mesh), 1.0)]   # scan-free step
    cfg = cfgbase.get_config(arch)
    shape = cfgbase.SHAPES[shape_name]
    if shape.kind == "decode":
        return [(make_cell(arch, shape_name, mesh), 1.0)]   # python loop

    model = build_model(cfg)
    pattern = cfg.block_pattern
    nc, rem = T.n_cycles(cfg)
    grad_accum = (4 if shape.kind == "train" and shape.global_batch >= 64
                  else 1)
    b_mb = shape.global_batch // grad_accum
    s = shape.seq_len
    dt = jnp.dtype(cfg.dtype)

    params = jax.eval_shape(model.init, jax.random.key(0))
    params_sh = part.param_shardings(params, mesh)
    x_spec = _sds((b_mb, s, cfg.d_model), dt)
    x_sh = list(part.batch_shardings(mesh, {"x": x_spec}).values())[0]
    tokens = _sds((b_mb, s), jnp.int32)
    tok_sh = list(part.batch_shardings(mesh, {"t": tokens}).values())[0]
    labels_sh = tok_sh

    cells: list[tuple[Cell, float]] = []

    def group_cells(kinds, gparams, gparams_sh, tag):
        """fwd + (train-only) grad cells for a group of layers."""
        def fwd(cp, x):
            for j, kind in enumerate(kinds):
                x, _, _ = T._layer_full(cp[j], x, jnp.arange(s), cfg, kind,
                                        False)
            return x

        def grad(cp, x):
            return jax.grad(
                lambda c, xx: jnp.sum(fwd(c, xx).astype(jnp.float32)),
                argnums=(0, 1))(cp, x)

        out = [(Cell(arch, shape, fwd, (gparams, x_spec),
                     (gparams_sh, x_sh), x_sh, {}), None)]
        if shape.kind == "train":
            out.append((Cell(arch, shape, grad, (gparams, x_spec),
                             (gparams_sh, x_sh), (gparams_sh, x_sh), {}),
                        None))
        return out

    if nc:
        cyc_params = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape[1:], a.dtype),
            params["scan"])
        cyc_sh = part.param_shardings(cyc_params, mesh)
        for cell, _ in group_cells(pattern, cyc_params, cyc_sh, "cycle"):
            cells.append((cell, float(grad_accum * nc)))
    if rem:
        tail_sh = part.param_shardings(params["tail"], mesh)
        for cell, _ in group_cells(pattern[:rem], params["tail"], tail_sh,
                                   "tail"):
            cells.append((cell, float(grad_accum)))

    # embedding (gather fwd + scatter-add bwd)
    fe = frontend_embeds_spec(cfg, b_mb)

    def embed_fwd(p, t, *rest):
        emb = T.embed_tokens(p, cfg, t, rest[0] if rest else None)
        return jnp.sum(emb.astype(jnp.float32))

    emb_args = [params, tokens] + ([fe] if fe is not None else [])
    emb_in_sh = [params_sh, tok_sh] + ([x_sh] if fe is not None else [])
    if shape.kind == "train":
        def embed_grad(p, t, *rest):
            return jax.grad(embed_fwd)(p, t, *rest)
        cells.append((Cell(arch, shape, embed_grad, tuple(emb_args),
                           tuple(emb_in_sh), params_sh, {}),
                      float(grad_accum)))
    else:
        cells.append((Cell(arch, shape, embed_fwd, tuple(emb_args),
                           tuple(emb_in_sh), part.replicated(mesh), {}),
                      float(grad_accum)))

    # final norm + chunked CE (train only; prefill's last-token unembed is
    # negligible next to the stack)
    if shape.kind == "train":
        from repro.models.model import chunked_cross_entropy

        def ce_loss(p, x, lab):
            h = L.norm_apply(p["final_norm"], x, cfg.norm)
            loss, _ = chunked_cross_entropy(
                h, lambda hh: T.unembed(p, cfg, hh), lab)
            return loss

        def ce_grad(p, x, lab):
            return jax.grad(ce_loss, argnums=(0, 1))(p, x, lab)

        rep = part.replicated(mesh)
        cells.append((Cell(arch, shape, ce_loss, (params, x_spec, tokens),
                           (params_sh, x_sh, labels_sh), rep, {}),
                      float(grad_accum)))          # remat recompute
        cells.append((Cell(arch, shape, ce_grad, (params, x_spec, tokens),
                           (params_sh, x_sh, labels_sh),
                           (params_sh, x_sh), {}),
                      float(grad_accum)))

        # optimizer step
        from repro.train import optimizer as optmod
        state = _state_specs(model, cfg)
        state_sh = _state_shardings(state, mesh)

        def opt_step(state, grads):
            p, m, v, _ = optmod.adamw_update(
                grads, state.m, state.v, state.params, state.step,
                optmod.AdamWConfig())
            return p, m, v

        cells.append((Cell(arch, shape, opt_step, (state, params),
                           (state_sh, params_sh),
                           (state_sh.params, state_sh.m, state_sh.v), {}),
                      1.0))
    return cells
