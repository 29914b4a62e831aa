"""From a profiler trace to busy and idle time, kernel time and idle gaps.

The JAX profiler writes an ``.xplane.pb``; :func:`load` turns it into plain
planes, lines and events (name, start and duration in ns, one clock for the
host and the device).  :func:`reduce_planes` then takes, inside the host
span ``bench.window`` (the whole trace when there is none):

* ``busy_s``: the union of the intervals in which an operation ran on a
  device (its "XLA Ops" and "Async XLA Ops" lines), averaged over the chips
  used; ``window_s``: the window's length;
* ``kernel_s``: device seconds by operation name, the HLO instruction name
  without its ``%`` and numeric suffix (a Pallas kernel's is the name it
  was given, such as ``frontier_histogram`` or ``split_gain``);
* ``device_ops``: [name, self seconds], the operations that took the most
  time, an operation's time less that of the operations nested in it;
* ``idle_gaps``: [host span, seconds], the device's idle time inside the
  window by what the host was doing: the innermost ``bench.*`` span around
  the gap's middle, and the innermost other host event inside it.
"""

from __future__ import annotations

import glob
import re
from pathlib import Path

DEVICE = re.compile(r"^/device:TPU:(\d+)$")
OP_LINES = ("XLA Ops", "Async XLA Ops")
WINDOW = "bench.window"


def load(path) -> list[dict]:
    """Planes of an ``.xplane.pb`` (or of the one file under a directory)."""
    from jax.profiler import ProfileData
    path = Path(path)
    if path.is_dir():
        found = sorted(glob.glob(str(path / "**" / "*.xplane.pb"),
                                 recursive=True))
        if not found:
            raise FileNotFoundError(f"no .xplane.pb under {path}")
        path = Path(found[-1])
    pd = ProfileData.from_file(str(path))
    return [{"name": pl.name,
             "lines": [{"name": ln.name,
                        "events": [(e.name, e.start_ns, e.duration_ns)
                                   for e in ln.events]}
                       for ln in pl.lines]}
            for pl in pd.planes]


def op_name(hlo: str) -> str:
    """``%frontier_histogram.23 = f32[...] custom-call(...)`` ->
    ``frontier_histogram``."""
    head = hlo.split(" = ", 1)[0].lstrip("%")
    return re.sub(r"(\.\d+)+$", "", head)


def _union(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def _self_times(events):
    """Self time of each event of one line whose events nest."""
    order = sorted(range(len(events)),
                   key=lambda i: (events[i][1], -events[i][2]))
    self_t = [float(e[2]) for e in events]
    stack: list[int] = []
    for i in order:
        start, end = events[i][1], events[i][1] + events[i][2]
        while stack and events[stack[-1]][1] + events[stack[-1]][2] <= start:
            stack.pop()
        if stack:
            self_t[stack[-1]] -= end - start
        stack.append(i)
    return self_t


def _host_line(planes):
    """Events of the host thread that carries the ``bench.*`` spans (its
    events nest), as (name, start, end)."""
    for pl in planes:
        if not pl["name"].startswith("/host:"):
            continue
        for ln in pl["lines"]:
            if any(n.startswith("bench.") for n, _, _ in ln["events"]):
                return [(n, s, s + d) for n, s, d in ln["events"]]
    return []


def _labels(spans, times):
    """What the host was doing at each time: the innermost ``bench.*`` span
    (not the window) and the innermost other event inside it."""
    spans = sorted(spans, key=lambda sp: (sp[1], -sp[2]))
    out, stack, k = {}, [], 0
    for t in sorted(times):
        while k < len(spans) and spans[k][1] <= t:
            while stack and stack[-1][2] <= spans[k][1]:
                stack.pop()
            stack.append(spans[k])
            k += 1
        while stack and stack[-1][2] <= t:
            stack.pop()
        bench = [i for i, sp in enumerate(stack)
                 if sp[0].startswith("bench.") and sp[0] != WINDOW]
        if not bench:
            out[t] = "outside bench spans"
            continue
        i = bench[-1]
        inner = stack[i + 1:]
        out[t] = stack[i][0] + (f" / {inner[-1][0]}" if inner else "")
    return out


def reduce_planes(planes: list[dict], *, chips: int = 1) -> dict:
    spans = _host_line(planes)
    windows = [sp for sp in spans if sp[0] == WINDOW]
    devices = sorted((int(DEVICE.match(pl["name"]).group(1)), pl)
                     for pl in planes if DEVICE.match(pl["name"]))[:chips]
    if not devices:
        raise ValueError("the trace has no TPU device plane")
    if windows:
        lo, hi = windows[0][1], windows[0][2]
    else:
        every = [(s, s + d) for pl in planes for ln in pl["lines"]
                 for _, s, d in ln["events"]]
        lo, hi = min(s for s, _ in every), max(e for _, e in every)

    busy, kernel_ns, self_ns = [], {}, {}
    gaps_of_first = None
    for _, pl in devices:
        intervals = []
        for ln in pl["lines"]:
            if ln["name"] not in OP_LINES:
                continue
            evs = [e for e in ln["events"] if e[1] < hi and e[1] + e[2] > lo]
            intervals += [(s, s + d) for _, s, d in evs]
            if ln["name"] == "XLA Ops":
                for (n, _, d), st in zip(evs, _self_times(evs)):
                    k = op_name(n)
                    kernel_ns[k] = kernel_ns.get(k, 0.0) + d
                    head = n.split("{", 1)[0].split("(", 1)[0].lstrip("%")
                    self_ns[head] = self_ns.get(head, 0.0) + st
        merged = _clip(_union(intervals), lo, hi)
        busy.append(sum(e - s for s, e in merged))
        if gaps_of_first is None:
            edges = [lo] + [t for iv in merged for t in iv] + [hi]
            gaps_of_first = [(edges[i], edges[i + 1])
                             for i in range(0, len(edges), 2)
                             if edges[i + 1] > edges[i]]

    idle: dict[str, float] = {}
    labels = _labels(spans, [(s + e) / 2 for s, e in gaps_of_first])
    for s, e in gaps_of_first:
        k = labels[(s + e) / 2]
        idle[k] = idle.get(k, 0.0) + (e - s) * 1e-9
    n_dev = len(devices)
    return {
        "busy_s": sum(busy) / n_dev * 1e-9,
        "window_s": (hi - lo) * 1e-9,
        "kernel_s": {k: v / n_dev * 1e-9 for k, v in kernel_ns.items()},
        "device_ops": sorted(([k, v / n_dev * 1e-9]
                              for k, v in self_ns.items()),
                             key=lambda kv: -kv[1]),
        "idle_gaps": sorted(([k, v] for k, v in idle.items()),
                            key=lambda kv: -kv[1]),
    }


def reduce(path, *, chips: int = 1) -> dict:
    return reduce_planes(load(path), chips=chips)
