"""Thread-safe span tracer with Chrome-trace-event JSON export.

Produces the `Trace Event Format`_ consumed by Perfetto
(https://ui.perfetto.dev) and ``chrome://tracing``:

  * :meth:`Tracer.span` — nestable duration spans (``ph="X"``); nesting is
    per-thread, so farm workers show up as separate lanes;
  * :meth:`Tracer.instant` — point events (retries, evictions, deaths);
  * :meth:`Tracer.counter` — numeric time series (per-worker queued
    weight), rendered by Perfetto as a stacked timeline;
  * :meth:`Tracer.begin` / :meth:`Tracer.end` — async spans that may cross
    threads and overlap (one per serving request, keyed by uid).

An enabled span also enters a :class:`jax.profiler.TraceAnnotation` of the
same name, so farm, ensemble and service spans land in a JAX profiler trace
on the profiler's own clock, beside the device's operations.
:func:`annotation` is the always-on form for program code that has no
tracer (``frontier.build``): it costs next to nothing while no profiler
runs.  :func:`hlo_scopes` reads a compiled module's text back to the
``jax.named_scope`` that owns each instruction, which is how a device
trace's operations (named by HLO instruction) are given to those scopes.

Zero-cost when disabled: every method checks ``self.enabled`` first and
returns a shared no-op, so instrumented hot paths (the farm worker loop,
the engine tick) pay one attribute load + branch.  :data:`NULL` is the
process-wide disabled tracer used as the default everywhere.

.. _Trace Event Format:
   https://docs.google.com/document/d/1CvAClvFfyA5R-PhYUmn5OOQtYMH4h6I0nSsKchNAySU
"""

from __future__ import annotations

import json
import os
import re
import threading
import time
from typing import Any, NamedTuple

from jax.profiler import TraceAnnotation


def annotation(name: str) -> TraceAnnotation:
    """A host span in the JAX profiler's trace (a no-op while none runs)."""
    return TraceAnnotation(name)


_HLO_COMPUTATION = re.compile(r"^(?:ENTRY )?%(\S+) .*\{$")
_HLO_INSTR = re.compile(r"^\s*(?:ROOT )?%(\S+) = .*? ([a-z][a-z0-9-]*)\((.*)$")
_HLO_CALLED = re.compile(r"(?:calls|to_apply)=%([^\s,)}]+)")
_HLO_OP_NAME = re.compile(r'metadata=\{[^}]*op_name="([^"]*)"')
# Instructions that only hold the program together: they take a scope from
# their own metadata alone.
_HLO_SCAFFOLDING = frozenset((
    "parameter", "get-tuple-element", "tuple", "while", "conditional",
    "call"))


class HloScope(NamedTuple):
    """The scope an instruction runs under, and the rule of
    :func:`hlo_scopes` (1-4) that found it: 1 and 2 read the program's own
    metadata, 3 and 4 infer the scope of an instruction that has none."""

    scope: str
    rule: int


def hlo_scopes(hlo_text: str, prefix: str,
               entry_scope: str | None = None) -> dict[str, HloScope]:
    """``{instruction name: innermost scope}`` of a compiled HLO module.

    ``hlo_text`` is ``jax.stages.Compiled.as_text()``.  A scope is a path
    element of an ``op_name`` that starts with ``prefix``; ``jax.named_scope``
    puts it there.  An instruction's scope is, by the first rule that finds
    one:

    1. the innermost one in its own ``op_name``;
    2. the first found in what it calls (a fusion's fused instructions,
       root first);
    3. for an instruction the compiler made without metadata (a layout
       copy, a broadcast of a constant, a piece of a decomposed scan), the
       scope of an instruction of its computation that uses its result
       (through a tuple: the loop or branch it is packed for), else of one
       it reads;
    4. in the entry computation, ``entry_scope``: for a program that is
       set-up and one loop, what the compiler made to fill the loop's
       initial state is set-up.

    Loop scaffolding (parameters, tuples, ``while``, ``conditional``) takes
    only a scope of its own; instructions that find none are left out.
    """
    comps: dict[str, list[tuple]] = {}
    entry = None
    cur: list | None = None
    for line in hlo_text.splitlines():
        if cur is None or not line.startswith(" "):
            m = _HLO_COMPUTATION.match(line)
            cur = comps.setdefault(m.group(1), []) if m else None
            if m and line.startswith("ENTRY "):
                entry = m.group(1)
            continue
        m = _HLO_INSTR.match(line)
        if m is None:
            continue
        name, opcode, rest = m.groups()
        op_name = _HLO_OP_NAME.search(rest)
        own = [p for p in (op_name.group(1) if op_name else "").split("/")
               if p.startswith(prefix)]
        called = ([] if opcode in _HLO_SCAFFOLDING
                  else _HLO_CALLED.findall(rest))
        cur.append((name, opcode, re.findall(r"%([^\s,)}]+)", rest), called,
                    own[-1] if own else None))

    first: dict[str, str | None] = {}

    def called_scope(called: list[str]) -> str | None:
        return next(filter(None, map(first_scope, called)), None)

    def first_scope(comp: str) -> str | None:
        if comp not in first:
            first[comp] = None                   # a cycle finds nothing
            first[comp] = next(filter(None, (
                own or called_scope(called)
                for _, _, _, called, own in reversed(comps.get(comp, ())))),
                None)
        return first[comp]

    out: dict[str, HloScope] = {}
    for comp, instrs in comps.items():
        scope: dict[str, HloScope] = {}
        for name, _, _, called, own in instrs:
            if own:
                scope[name] = HloScope(own, 1)
            elif found := called_scope(called):
                scope[name] = HloScope(found, 2)
        users: dict[str, list[str]] = {}
        for name, _, operands, _, _ in instrs:
            for o in operands:
                users.setdefault(o, []).append(name)
        tuples = {n for n, opcode, _, _, _ in instrs if opcode == "tuple"}
        for used_by in users.values():
            used_by += [u2 for u in used_by if u in tuples
                        for u2 in users.get(u, ())]
        loose = [(n, users.get(n, []) + operands)
                 for n, opcode, operands, _, _ in instrs
                 if n not in scope and opcode not in _HLO_SCAFFOLDING]
        while loose:                             # until nothing changes
            still = []
            for name, near in loose:
                found = next((scope[n].scope for n in near if n in scope),
                             None)
                if found:
                    scope[name] = HloScope(found, 3)
                else:
                    still.append((name, near))
            if len(still) == len(loose):
                break
            loose = still
        if comp == entry and entry_scope:
            scope.update((name, HloScope(entry_scope, 4)) for name, _ in loose)
        out.update(scope)
    return out


class _NullSpan:
    """Shared no-op context manager returned by a disabled tracer."""

    __slots__ = ()

    def __enter__(self) -> "_NullSpan":
        return self

    def __exit__(self, *exc: Any) -> bool:
        return False


_NULL_SPAN = _NullSpan()


class _Span:
    """One open duration span; emits a single complete ("X") event on exit."""

    __slots__ = ("_tracer", "_name", "_args", "_t0", "_annotation")

    def __init__(self, tracer: "Tracer", name: str, args: dict | None):
        self._tracer = tracer
        self._name = name
        self._args = args

    def __enter__(self) -> "_Span":
        self._annotation = TraceAnnotation(self._name)
        self._annotation.__enter__()
        self._t0 = self._tracer._now_us()
        return self

    def __exit__(self, *exc: Any) -> bool:
        tr = self._tracer
        t1 = tr._now_us()
        self._annotation.__exit__(*exc)
        ev = {"name": self._name, "ph": "X", "ts": self._t0,
              "dur": t1 - self._t0, "pid": tr._pid, "tid": tr._tid()}
        if self._args:
            ev["args"] = self._args
        tr._emit(ev)
        return False


class Tracer:
    """Collects trace events in memory; thread-safe; export via :meth:`save`.

    ``enabled=False`` turns every call into a cheap no-op — construct one
    tracer per run you want to inspect and pass it down; the default
    everywhere is the disabled :data:`NULL`.
    """

    def __init__(self, enabled: bool = True):
        self.enabled = enabled
        self._events: list[dict] = []
        self._lock = threading.Lock()
        self._epoch = time.perf_counter()
        self._pid = os.getpid()
        self._tid_map: dict[int, int] = {}

    # ----------------------------------------------------------- internals
    def _now_us(self) -> float:
        return (time.perf_counter() - self._epoch) * 1e6

    def _tid(self) -> int:
        ident = threading.get_ident()
        tid = self._tid_map.get(ident)
        if tid is None:
            with self._lock:
                tid = self._tid_map.setdefault(ident, len(self._tid_map) + 1)
                self._events.append({
                    "name": "thread_name", "ph": "M", "pid": self._pid,
                    "tid": tid,
                    "args": {"name": threading.current_thread().name}})
        return tid

    def _emit(self, ev: dict) -> None:
        with self._lock:
            self._events.append(ev)

    # ------------------------------------------------------------- emitters
    def span(self, name: str, **args: Any):
        """Context manager timing a nested duration span on this thread."""
        if not self.enabled:
            return _NULL_SPAN
        return _Span(self, name, args or None)

    def instant(self, name: str, **args: Any) -> None:
        """A point event (``ph="i"``): retries, evictions, deaths, ..."""
        if not self.enabled:
            return
        ev = {"name": name, "ph": "i", "s": "t", "ts": self._now_us(),
              "pid": self._pid, "tid": self._tid()}
        if args:
            ev["args"] = args
        self._emit(ev)

    def counter(self, name: str, **values: float) -> None:
        """A counter sample (``ph="C"``): Perfetto draws a value timeline."""
        if not self.enabled:
            return
        self._emit({"name": name, "ph": "C", "ts": self._now_us(),
                    "pid": self._pid, "tid": self._tid(), "args": values})

    def begin(self, name: str, id: int, **args: Any) -> None:
        """Open an async span (``ph="b"``) — may overlap and cross threads."""
        if not self.enabled:
            return
        ev = {"name": name, "cat": "async", "ph": "b", "id": id,
              "ts": self._now_us(), "pid": self._pid, "tid": self._tid()}
        if args:
            ev["args"] = args
        self._emit(ev)

    def end(self, name: str, id: int, **args: Any) -> None:
        """Close the async span opened by :meth:`begin` with the same id."""
        if not self.enabled:
            return
        ev = {"name": name, "cat": "async", "ph": "e", "id": id,
              "ts": self._now_us(), "pid": self._pid, "tid": self._tid()}
        if args:
            ev["args"] = args
        self._emit(ev)

    # ------------------------------------------------------------ consumers
    @property
    def events(self) -> list[dict]:
        with self._lock:
            return list(self._events)

    def clear(self) -> None:
        with self._lock:
            self._events.clear()

    def to_chrome(self) -> dict:
        """The JSON-object trace form Perfetto/chrome://tracing load."""
        return {"traceEvents": self.events, "displayTimeUnit": "ms"}

    def save(self, path: str) -> str:
        with open(path, "w") as f:
            json.dump(self.to_chrome(), f)
        return path

    def span_summary(self) -> dict[str, dict[str, float]]:
        """Aggregate duration spans by name: count/total/mean/max (us)."""
        out: dict[str, dict[str, float]] = {}
        for ev in self.events:
            if ev.get("ph") != "X":
                continue
            s = out.setdefault(ev["name"],
                               {"count": 0, "total_us": 0.0, "max_us": 0.0})
            s["count"] += 1
            s["total_us"] += ev["dur"]
            s["max_us"] = max(s["max_us"], ev["dur"])
        for s in out.values():
            s["mean_us"] = s["total_us"] / max(s["count"], 1)
        return out

    def counter_series(self) -> dict[str, list[tuple[float, dict]]]:
        """Counter samples grouped by name as ``[(ts_us, values), ...]``."""
        out: dict[str, list[tuple[float, dict]]] = {}
        for ev in self.events:
            if ev.get("ph") == "C":
                out.setdefault(ev["name"], []).append((ev["ts"], ev["args"]))
        for series in out.values():
            series.sort(key=lambda p: p[0])
        return out


#: Process-wide disabled tracer — the default for every instrumented path.
NULL = Tracer(enabled=False)
