"""On-chip benchmark of the tree system: one cell, one run.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Run from the root of a checkout.  The cell is looked up in
``BENCHMARK.json``; everything that belongs to it is found by name:

  bench/configs/<config>.json   the deployment: data, grow config, limits
  bench/generators/<generator>.py   the training set a configuration's
                                    data block names
  bench/traffic/<traffic>.json  the mix; its ``mode`` names the driver
  bench/modes/<mode>.py         the driver: set-up, window, correctness
  bench/metrics/<metric>.py     one reader per per-layer metric
  bench/peaks.json              the chip's peaks, keyed by device_kind

With ``--trace 0`` the result carries the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics, read from a profiler trace of the
window.  Every run checks what the window produced against a plain
reference that imports nothing of the program, prints each compared number
beside its limit on standard error, and ends its standard output with one
JSON line.  It exits non-zero, printing no result, when JAX finds no TPU or
fewer chips than the cell asks for, when the device kind has no row in the
peak table, or when the program (``src/repro``) is not beside it.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()       # set-up is timed from here

import argparse  # noqa: E402
import contextlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
OUT = ROOT / "chiprun_out" / "bench"     # records and raw traces
CACHE = ROOT / ".jax_cache"              # JAX's persistent compile cache


class BenchError(RuntimeError):
    """A run that cannot produce a result (no chip, bad cell, ...)."""


def load(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path, name: str):
    """Import a mode, generator or metric file once, by its path."""
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or not path.is_file():
        raise BenchError(f"missing {path.relative_to(ROOT)}")
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def cell_metrics(spec: dict, cell: str) -> tuple[list[dict], list[dict]]:
    """The end-to-end and per-layer metrics this cell reports."""
    e2e = [m for m in spec["end_to_end"]
           if cell in m.get("workloads", [cell])]
    names = {m["name"] for m in e2e}
    layer = [m for m in spec["per_layer"]
             if cell in m.get("workloads", [])
             or ("workloads" not in m and m["moves"] in names)]
    return e2e, layer


class Run:
    """What a mode needs from the harness: the cell's files, the seed, the
    window, spans on the profiler's clock, and the record of the checks."""

    def __init__(self, args, cell: dict, config: dict, traffic: dict):
        self.seed = args.seed % (1 << 64)
        self.seconds = float(args.seconds)
        self.trace = bool(args.trace)
        self.control = False            # set by bench/control.py only
        self.program_readings: dict = {}  # what the control's stood in for
        self.cell, self.config, self.traffic = cell, config, traffic
        self.checks: list[tuple[str, float, float]] = []
        self.notes: list[str] = []
        self.layer_inputs: dict = {}
        self.setup_s: float | None = None
        self.memory_peak_bytes: int | None = None
        self.trace_dir: Path | None = None
        self.compiles = {"in_window": 0, "total": 0}
        self._in_window = False

    # ---- spans on the profiler's clock -----------------------------------
    def span(self, name: str):
        import jax
        return jax.profiler.TraceAnnotation(f"bench.{name}")

    # ---- the measured window ---------------------------------------------
    @contextlib.contextmanager
    def window(self):
        """Set-up ends where this block starts; the profiler, when asked
        for, runs around the block and nothing else."""
        import jax
        self.setup_s = time.perf_counter() - T_START
        if self.trace:
            self.trace_dir = OUT / self.cell["name"] / "trace"
            shutil.rmtree(self.trace_dir, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(str(self.trace_dir),
                                     profiler_options=opts)
        self._in_window = True
        try:
            with self.span("window"):
                yield
        finally:
            self._in_window = False
            if self.trace:
                jax.profiler.stop_trace()

    def on_compile(self, key: str, *_a, **_kw) -> None:
        if key in ("/jax/core/compile/backend_compile_duration",
                   "/jax/compilation_cache/cache_retrieval_time_sec"):
            self.compiles["total"] += 1
            self.compiles["in_window"] += int(self._in_window)

    def read_memory(self) -> None:
        """Peak device memory of the fullest chip; read once the window
        has closed and before the reference runs."""
        import jax
        self.memory_peak_bytes = max(
            int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
            for d in jax.devices()[: self.cell["chips"]])

    # ---- correctness -------------------------------------------------------
    def check(self, name: str, value: float, limit: float) -> None:
        """Record one compared number; it passes at or below its limit."""
        self.checks.append((name, float(value), float(limit)))

    @property
    def correct(self) -> bool:
        return bool(self.checks) and all(v <= lim for _, v, lim in
                                         self.checks)


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def prepare(args) -> tuple[dict, Run, object]:
    """Find the cell, its files and its driver; point JAX's compile cache
    into the checkout.  Touches no device."""
    spec = load(ROOT / "BENCHMARK.json")
    cells = {c["name"]: c for c in spec["workloads"]}
    if args.workload not in cells:
        raise BenchError(f"no cell {args.workload!r} in BENCHMARK.json "
                         f"(cells: {sorted(cells)})")
    cell = cells[args.workload]
    config = load(BENCH / "configs" / f"{cell['config']}.json")
    traffic = load(BENCH / "traffic" / f"{cell['traffic']}.json")
    if not (ROOT / "src" / "repro").is_dir():
        raise BenchError(f"no program under {ROOT / 'src'}")
    for p in (str(ROOT / "src"), str(ROOT)):
        if p not in sys.path:
            sys.path.insert(0, p)
    mode = load_module(BENCH / "modes" / f"{traffic['mode']}.py",
                       f"bench_mode_{traffic['mode']}")
    # The program keeps its compile cache where this variable says; the
    # benchmark gives it a fixed directory inside the checkout.
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE)
    return spec, Run(args, cell, config, traffic), mode


def device_info(chips: int, *, peak_kind: str | None) -> tuple[dict, dict]:
    """The device line and the chip's row of the peak table.  With
    ``peak_kind`` the look for a chip is skipped and that row is used."""
    import jax
    devs = jax.devices()
    dev = devs[0]
    if peak_kind is None:
        if dev.platform != "tpu":
            raise BenchError(f"JAX found no TPU (platform {dev.platform!r})")
        if len(devs) < chips:
            raise BenchError(f"the cell asks for {chips} chips, JAX found "
                             f"{len(devs)}")
    kind = peak_kind or dev.device_kind
    peaks = load(BENCH / "peaks.json")
    if kind not in peaks:
        raise BenchError(f"device kind {kind!r} has no row in "
                         "bench/peaks.json")
    info = {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devs)}
    return info, peaks[kind]


def execute(argv=None, *, peak_kind: str | None = None,
            control: bool = False) -> dict:
    """One run; returns the result line's object.  ``peak_kind`` lets the
    tests drive everything but the look for a chip on the CPU, with that
    row of the peak table; ``control=True`` puts the control in the
    program's place in the compared numbers (``bench/control.py``)."""
    args = parse(argv)
    spec, run, mode = prepare(args)
    run.control = control
    e2e, layer = cell_metrics(spec, run.cell["name"])
    if not e2e or not layer:
        raise BenchError(f"cell {run.cell['name']} reports no end-to-end "
                         "or no per-layer metric")
    import jax
    import jax.monitoring
    compile_cache = importlib.import_module("repro.compile_cache")
    compile_cache.enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.monitoring.register_event_duration_secs_listener(run.on_compile)
    device, peak = device_info(run.cell["chips"], peak_kind=peak_kind)

    values = mode.run(run)                      # set-up, window, checks
    values["setup_s"] = run.setup_s
    if run.memory_peak_bytes is None:
        run.read_memory()
    device["memory_peak_bytes"] = run.memory_peak_bytes
    result: dict = {"correct": run.correct,
                    "attempted": values.pop("attempted"),
                    "failed": values.pop("failed")}
    if run.trace:
        from bench import trace_reduce
        red = trace_reduce.reduce(run.trace_dir, chips=run.cell["chips"])
        device["busy_s"] = red["busy_s"]
        device["window_s"] = red["window_s"]
        ctx = dict(run.layer_inputs, trace=red, peak=peak, notes=run.notes)
        metrics = {}
        for m in layer:
            reader = load_module(BENCH / "metrics" / f"{m['name']}.py",
                                 f"bench_metric_{m['name']}")
            v = reader.read(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        result["metrics"] = metrics
        result["breakdown"] = {"device_ops": red["device_ops"][:10],
                               "idle_gaps": red["idle_gaps"][:10]}
        shutil.rmtree(run.trace_dir, ignore_errors=True)
    else:
        missing = [m["name"] for m in e2e if m["name"] not in values]
        if missing:
            raise BenchError(f"mode {run.traffic['mode']} gave no {missing}")
        result["metrics"] = {m["name"]: {"value": values[m["name"]],
                                         "unit": m["unit"]} for m in e2e}
    result["device"] = device
    run.notes.append(f"compiles or cache loads: {run.compiles['in_window']} "
                     f"in the window, {run.compiles['total']} in all")
    result["notes"] = run.notes
    if control:
        result["program"] = run.program_readings
    result["checks"] = {n: {"value": v, "limit": lim}
                        for n, v, lim in run.checks}
    for line in run.notes:
        print(f"note: {line}", file=sys.stderr)
    for n, v, lim in run.checks:
        print(f"check {n}: {v!r} <= {lim!r} {'ok' if v <= lim else 'FAIL'}",
              file=sys.stderr)
    return result


def main(argv=None) -> int:
    try:
        result = execute(argv)
    except BenchError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    OUT.mkdir(parents=True, exist_ok=True)
    line = json.dumps(result)
    with open(OUT / "runs.jsonl", "a") as f:
        f.write(line + "\n")
    sys.stderr.flush()
    print(line, flush=True)
    return 0


if __name__ == "__main__":
    # Files loaded by path import the harness as ``bench.run``: let that be
    # this module, so that the BenchError they raise is the one main() catches.
    sys.modules["bench.run"] = sys.modules[__name__]
    sys.exit(main())
