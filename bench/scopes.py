"""The fused build's phases and counters, for the per-layer readers.

Device time by phase: the trace names each device operation by its HLO
instruction (``device_ops`` keys such as ``fusion.241 = s32[500000]``; the
instruction is the text before ``" = "``), and the program's
``frontier.build_scopes()`` maps each instruction of the build it ran to
the innermost ``frontier.*`` scope that owns it, and the rule that found
it.  :func:`ms_per_tree` sums the self time of the operations by scope, a
scope including the scopes nested in it, over the builds of the window.
The share of device time in no scope, the largest such operations, the
share whose scope was inferred (rules 3 and 4: an operation with no
metadata of its own given a neighbour's scope, or set-up's) and the seconds
the map took go to the run's notes.

Counters: the program writes the last build's totals to the ``frontier_*``
gauges of its metrics registry; :func:`counts` reads them.  A program
without the map or the gauges gives ``None`` and a note, never an error.
"""

from __future__ import annotations

import time

#: Each nested scope and the phase it lies in.
PARENT = {"frontier.select": "frontier.split_pre",
          "frontier.compact": "frontier.split_att",
          "frontier.route": "frontier.split_post"}
COUNTS = ("frontier_supersteps", "frontier_open_nodes",
          "frontier_live_case_steps", "frontier_hist_case_steps",
          "frontier_cases", "frontier_slots")
#: The map's rules that infer a scope the operation's metadata lacks.
INFERRED = (3, 4)


def by_scope(device_ops, scopes: dict) -> tuple[dict, float, list, dict]:
    """(seconds by scope with nested scopes included, seconds in no scope,
    the [name, seconds] of the operations in no scope, largest first,
    seconds by innermost scope of the operations whose scope was
    inferred).  ``scopes`` maps an instruction to its ``(scope, rule)``."""
    got: dict[str, float] = {}
    unmapped = []
    inferred: dict[str, float] = {}
    for key, seconds in device_ops:
        found = scopes.get(key.split(" = ", 1)[0])
        if found is None:
            unmapped.append([key, seconds])
            continue
        scope, rule = found
        if rule in INFERRED:
            inferred[scope] = inferred.get(scope, 0.0) + seconds
        while scope is not None:
            got[scope] = got.get(scope, 0.0) + seconds
            scope = PARENT.get(scope)
    unmapped.sort(key=lambda kv: -kv[1])
    return got, sum(s for _, s in unmapped), unmapped, inferred


def _scope_ms(ctx) -> dict | None:
    units = ctx.get("units")
    if not units:
        return None
    try:
        from repro.core.frontier import build_scopes
    except ImportError:
        ctx["notes"].append("scopes: the program has no "
                            "frontier.build_scopes; no phase times")
        return None
    t0 = time.perf_counter()
    scopes = build_scopes()
    took = time.perf_counter() - t0
    if not scopes:
        ctx["notes"].append("scopes: frontier.build_scopes gave no map; no "
                            "phase times")
        return None
    got, none_s, unmapped, inferred = by_scope(
        ctx["trace"]["device_ops"], scopes)
    total = sum(s for _, s in ctx["trace"]["device_ops"])
    inferred_s = sum(inferred.values())
    share = 100.0 * none_s / total if total > 0 else 0.0
    inferred_share = 100.0 * inferred_s / total if total > 0 else 0.0
    top = ", ".join(f"{k} {1e3 * s / units:.3f} ms" for k, s in unmapped[:3])
    where = ", ".join(f"{k} {1e3 * s / units:.3f} ms" for k, s in
                      sorted(inferred.items(), key=lambda kv: -kv[1]))
    ctx["notes"].append(
        f"scopes: {share:.4f}% of device op time in no frontier.* scope "
        f"({1e3 * none_s / units:.3f} ms per tree; largest: {top or 'none'}); "
        f"{inferred_share:.4f}% in a scope inferred by rule 3 or 4 "
        f"({1e3 * inferred_s / units:.3f} ms per tree: {where or 'none'}); "
        f"scope map of {len(scopes)} instructions made in {took:.3f} s")
    return {k: 1e3 * s / units for k, s in got.items()}


def ms_per_tree(ctx, scope: str) -> float | None:
    """Device ms per tree of ``scope`` and the scopes nested in it."""
    if "scope_ms" not in ctx:
        ctx["scope_ms"] = _scope_ms(ctx)
    got = ctx["scope_ms"]
    return None if got is None else got.get(scope, 0.0)


def counts(ctx) -> dict | None:
    """The last build's ``frontier_*`` gauges, as numbers."""
    if "frontier_counts" not in ctx:
        from repro.obs.metrics import REGISTRY
        got = {}
        for name in COUNTS:
            gauge = REGISTRY.get(name)
            if gauge is None or not gauge.labels_of():
                ctx["notes"].append(f"counts: the program's registry has "
                                    f"no {name}; no superstep counters")
                got = None
                break
            got[name] = gauge.value()
        ctx["frontier_counts"] = got
    return ctx["frontier_counts"]
