"""One run of one benchmark cell.

    python chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration file, its traffic mix, the kind module that
mix names (``kinds/<kind>.py``) and its metrics are found by name from
``BENCHMARK.json``. A run:

1. requires JAX's first device to be a TPU, with as many devices as the
   cell asks for, and its ``device_kind`` in ``peaks.json``; otherwise
   it exits non-zero and prints no result;
2. points JAX's persistent compilation cache at ``.jax_cache`` in the
   checkout, unless ``JAX_COMPILATION_CACHE_DIR`` names one;
3. sets up: builds the traffic from the seed, refuses a cell whose
   calls the kind's reference does not model, and makes one call of the
   window's own shape whose answer is thrown away, so every executable
   the window uses is compiled or loaded from the cache (set-up ends at
   the window's first call);
4. drives ``Scenario.run()`` calls back to back, one client, until the
   window's seconds have passed; the last call is not cut, so the window
   ends where it ends;
5. reads the device's peak memory, then checks every answer of the
   window against the plain reference (the kind's ``check``), prints each number
   compared beside its limit, and prints the result as one JSON line.

With ``--trace 1`` the same window runs under the profiler, the
harness's spans (``chipbench.window``, ``chipbench.call``,
``chipbench.between``) mark what the host was doing, and the result
carries the per-layer metrics and the trace's breakdown in place of the
end-to-end metrics.
"""

from __future__ import annotations

import argparse
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
import traceback
from dataclasses import dataclass, field
from pathlib import Path

from chipbench import generator, kinds

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
CACHE_DIR = ROOT / ".jax_cache"
HLO_TRACE_OFF = "--xla_enable_hlo_trace=false"


@dataclass
class Run:
    """What the metric readers see: one record per window call, the
    window's bounds on the host clock, set-up seconds, and the reduced
    trace (None without one)."""

    records: list = field(default_factory=list)
    window_start: float = 0.0
    window_end: float = 0.0
    setup_s: float = 0.0
    trace: dict | None = None


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def parse(argv):
    ap = argparse.ArgumentParser(prog="chipbench/run.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def load_metric(name: str):
    path = HERE / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"chipbench_metric_{name}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def cell_metrics(bench: dict, cell: str, trace: bool) -> list[dict]:
    """The metrics this cell reports in a run of this kind."""
    group = bench["per_layer"] if trace else bench["end_to_end"]
    return [m for m in group if "workloads" not in m or cell in m["workloads"]]


def peaks_of(kind: str, peaks: dict) -> dict:
    """The peaks of a ``device_kind``; an unknown kind is an error, never
    a default."""
    if kind not in peaks["devices"]:
        raise SystemExit(f"device_kind {kind!r} is not in peaks.json")
    return peaks["devices"][kind]


def find_device(chips: int, peaks: dict):
    """The first device if it is a TPU the peaks table knows and the
    process sees at least ``chips`` devices; otherwise SystemExit."""
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        raise SystemExit(
            f"no TPU: JAX's first device is {dev.platform!r} ({dev.device_kind}); "
            "this benchmark runs on a TPU only"
        )
    if len(devices) < chips:
        raise SystemExit(f"the cell needs {chips} chips, JAX sees {len(devices)}")
    peaks_of(dev.device_kind, peaks)
    return dev, len(devices)


def use_cache() -> str:
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)


def drive(traffic, kind, seconds: float, run: Run, log_failure) -> tuple[list, int]:
    """The window: calls back to back until ``seconds`` have passed.
    Returns the (call, report) pairs and the count of calls that
    raised."""
    from jax.profiler import TraceAnnotation

    done, failed = [], 0
    with TraceAnnotation("chipbench.window"):
        run.window_start = time.perf_counter()
        while True:
            with TraceAnnotation("chipbench.between"):
                call = traffic.next_call()
            with TraceAnnotation("chipbench.call"):
                t0 = time.perf_counter()
                try:
                    rep = call.scenario.run()
                except Exception:  # a failed call is counted, not fatal
                    rep = None
                    log_failure(traceback.format_exc())
                t1 = time.perf_counter()
            with TraceAnnotation("chipbench.between"):
                if rep is None:
                    failed += 1
                else:
                    run.records.append({"t0": t0, "t1": t1, **kind.record(rep)})
                    done.append((call, rep))
            if t1 - run.window_start >= seconds:
                break
        run.window_end = t1
    return done, failed


def read_trace(trace_dir: str) -> dict | None:
    from chipbench import trace_reduce

    paths = sorted(Path(trace_dir).rglob("*.xplane.pb"))
    if not paths:
        return None
    return trace_reduce.reduce(trace_reduce.load(str(paths[-1])))


def run_cell(
    name: str,
    seed: int,
    seconds: float,
    trace: bool,
    t_start: float,
    *,
    config: dict | None = None,
    require_chip: bool = True,
) -> dict:
    """One run; returns the result object. ``config`` defaults to the
    cell's file; the tests pass a smaller one and ``require_chip=False``
    to skip the device look."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; cells: {sorted(cells)}")
    cell = cells[name]
    config = config or generator.load("configs", cell["config"])
    mix = generator.load("traffic", cell["traffic"])
    kind = kinds.load(mix["kind"])
    readers = {m["name"]: load_metric(m["name"]) for m in cell_metrics(bench, name, trace)}

    import jax

    peaks = json.loads((HERE / "peaks.json").read_text())
    if require_chip:
        dev, count = find_device(int(cell["chips"]), peaks)
    else:
        dev, count = jax.devices()[0], len(jax.devices())
    log(f"device: platform={dev.platform} kind={dev.device_kind} count={count}")
    log(f"compile cache: {use_cache()}")
    from chipbench.compile_log import CompileLog

    compiles = CompileLog()

    traffic = generator.Traffic(config, mix, seed)
    warmup = traffic.warmup_call()
    missing = kind.unsupported(warmup.spec)
    if missing:
        raise SystemExit(f"the {mix['kind']} reference does not model {missing}")
    warmup.scenario.run()
    log(
        f"set-up: compile cache hits={compiles.hits} misses={compiles.misses} "
        f"backend_compiles={compiles.backend_compiles} "
        f"backend_compile_s={compiles.seconds:.6f}"
    )
    run = Run()
    trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-") if trace else None
    try:
        if trace:
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0  # host spans only, no per-call tracing
            options.enable_hlo_proto = False
            jax.profiler.start_trace(trace_dir, profiler_options=options)
        before = compiles.compiles
        run.setup_s = time.perf_counter() - t_start
        done, failed = drive(traffic, kind, seconds, run, log)
        in_window = compiles.compiles - before
        if trace:
            jax.profiler.stop_trace()
            run.trace = read_trace(trace_dir)
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
    log(f"window: calls={len(done) + failed} compiles_in_window={in_window}")
    if run.records:
        slowest = max(run.records, key=lambda r: r["t1"] - r["t0"])
        log(f"window: slowest call {slowest['t1'] - slowest['t0']:.6f} s {slowest}")
    stats = dev.memory_stats() or {}
    memory_peak = int(stats.get("peak_bytes_in_use", 0))

    numbers = kind.check(config, done)
    limits = config["limits"]
    checks = {k: {"value": v, "limit": limits[k]} for k, v in numbers.items()}
    correct = bool(done) and failed == 0 and all(
        c["value"] <= c["limit"] for c in checks.values()
    )

    metrics = {}
    units = {m["name"]: m["unit"] for m in bench["end_to_end"] + bench["per_layer"]}
    for metric, read in readers.items():
        value = read(run)
        if value is not None:
            metrics[metric] = {"value": value, "unit": units[metric]}
    device = {
        "platform": dev.platform,
        "kind": dev.device_kind,
        "device_kind": dev.device_kind,
        "count": count,
        "memory_peak_bytes": memory_peak,
    }
    result = {
        "correct": correct,
        "attempted": len(done) + failed,
        "failed": failed,
        "metrics": metrics,
        "device": device,
    }
    if trace and run.trace is not None:
        device["busy_s"] = run.trace["busy_s"]
        device["window_s"] = run.trace["window_s"]
        result["breakdown"] = {
            "device_ops": run.trace["device_ops"],
            "idle_gaps": run.trace["idle_gaps"],
        }
    for key, c in checks.items():
        log(f"check {key} = {c['value']!r} (limit {c['limit']!r})")
    result["checks"] = checks
    return result


def trace_executables_only() -> None:
    """Have the TPU runtime trace executables and not each op inside
    them. The drive loops run one loop step per simulated request, and
    op-level tracing of a window of them collects tens of millions of
    events; the flag is added to what ``LIBTPU_INIT_ARGS`` holds, before
    JAX starts the runtime."""
    flags = os.environ.get("LIBTPU_INIT_ARGS", "")
    os.environ["LIBTPU_INIT_ARGS"] = f"{flags} {HLO_TRACE_OFF}".strip()


def main(argv, t_start: float) -> int:
    args = parse(argv)
    if args.trace:
        trace_executables_only()
    try:
        result = run_cell(args.workload, args.seed, args.seconds, bool(args.trace), t_start)
    except SystemExit as e:
        log(f"chipbench: {e}")
        return 2
    print(json.dumps(result), flush=True)
    return 0
