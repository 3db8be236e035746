"""Run one benchmark cell once and print its result line.

    python3 benchmarks/chip/run_cell.py --workload <name> --seed <n> \
        --seconds <s> --trace <0|1>

The cell's entry in ``BENCHMARK.json`` names a configuration and a
traffic mix; ``configs/<config>.json`` holds the model's sizes and
``traffic/<traffic>.json`` the mix's parameters and the name of the
driver (``drivers/<driver>.py``) that plays it.  Each metric is read by
``metrics/<metric>.py`` from what the run measured.  A cell, a
configuration, a mix or a metric is added by adding files.

A run: set-up (imports, data and weights from the seed, the compiled
program loaded or compiled, the driver's first rounds), then the
measured window of ``--seconds``, then the check of what the window
produced against the plain reference.  ``--trace 0`` reports the cell's
end-to-end metrics; ``--trace 1`` traces the window with the JAX
profiler and reports its per-layer metrics.  The last line of standard
output is one JSON object; the numbers the check compared, each beside
its limit, are the last lines of standard error and the last key of
that object.

Without a TPU, or with fewer chips than the cell asks for, the run
prints no result and exits with code 2.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
CACHE_DIR = os.path.join(ROOT, ".jax_cache")
TRACE_DIR = os.path.join(ROOT, "benchmarks", "chip", ".trace")

__all__ = ["main", "load_cell", "load_module"]


def load_module(kind: str, name: str):
    """``<kind>/<name>.py`` under the benchmark, imported by path."""
    path = os.path.join(HERE, kind, name + ".py")
    if not os.path.isfile(path):
        raise FileNotFoundError(f"no {kind[:-1]} file {path}")
    spec = importlib.util.spec_from_file_location(f"{kind}.{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_cell(workload: str) -> dict:
    """→ the cell, its configuration, traffic and metric entries."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"unknown workload {workload!r}; BENCHMARK.json has "
                       f"{sorted(cells)}")
    cell = cells[workload]
    conf = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    with open(os.path.join(ROOT, conf["file"])) as f:
        config = json.load(f)
    with open(os.path.join(HERE, "traffic", cell["traffic"] + ".json")) as f:
        traffic = json.load(f)

    def mine(m):
        return workload in m.get("workloads", [workload])

    return {"cell": cell, "config": config, "traffic": traffic,
            "end_to_end": [m for m in bench["end_to_end"] if mine(m)],
            "per_layer": [m for m in bench["per_layer"] if mine(m)]}


class CompileClock:
    """Backend compilations and their seconds, from JAX's own events."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self, jax):
        self.count = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_):
        if event == self.EVENT:
            self.count += 1
            self.seconds += duration


class Context:
    """What a driver and a metric reader may use."""

    def __init__(self, jax):
        self.jax = jax
        self.seconds = None
        self.setup_s = None
        self.window = None
        self.trace = None
        self.counts = {}
        self.peaks = None

    def span(self, name: str):
        return self.jax.profiler.TraceAnnotation(name)


def _merge(base: dict, over: dict | None) -> dict:
    out = dict(base)
    for k, v in (over or {}).items():
        out[k] = _merge(out[k], v) if isinstance(v, dict) and isinstance(
            out.get(k), dict) else v
    return out


def _device_check(jax, chips: int, allow_cpu: bool):
    devs = jax.devices()
    if devs[0].platform != "tpu" and not allow_cpu:
        return None, (f"run_cell.py: no TPU; JAX found {devs[0].platform} "
                      f"devices only, and this benchmark runs on the chip")
    if len(devs) < chips:
        return None, (f"run_cell.py: the cell needs {chips} chips, JAX found "
                      f"{len(devs)}")
    return devs[:chips], None


def main(argv=None, *, allow_cpu: bool = False,
         overrides: dict | None = None) -> int:
    """``allow_cpu`` and ``overrides`` (tiny sizes merged over the cell's
    files) are for the CPU rehearsals in ``tests/``; the command has
    neither."""
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    # One fixed cache directory in the checkout, whatever the caller set.
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    for p in (os.path.join(ROOT, "src"), HERE):
        if p not in sys.path:
            sys.path.insert(0, p)
    spec = load_cell(args.workload)
    spec["config"] = _merge(spec["config"], (overrides or {}).get("config"))
    spec["traffic"] = _merge(spec["traffic"], (overrides or {}).get("traffic"))

    import jax

    devices, why = _device_check(jax, spec["cell"]["chips"], allow_cpu)
    if devices is None:
        print(why, file=sys.stderr)
        return 2
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    clock = CompileClock(jax)
    ctx = Context(jax)
    driver = load_module("drivers", spec["traffic"]["driver"])
    kind = devices[0].device_kind
    ctx.seconds = args.seconds

    st = driver.setup(spec["config"], spec["traffic"], args.seed, ctx)
    ctx.setup_s = time.perf_counter() - T_START
    compiles0, compile_s0 = clock.count, clock.seconds

    if args.trace:
        from trace_reduce import find_xplane, reduce_file

        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(TRACE_DIR, profiler_options=opts)
    try:
        with ctx.span("window") if args.trace else contextlib.nullcontext():
            ctx.window = driver.window(st, args.seconds, ctx.span)
    finally:
        if args.trace:
            t_stop = time.perf_counter()
            jax.profiler.stop_trace()
            trace_io = {"stop_s": time.perf_counter() - t_stop}
    window_compiles = clock.count - compiles0
    window_compile_s = clock.seconds - compile_s0
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)
    failed = driver.failed_rounds(st)
    if args.trace:
        path = find_xplane(TRACE_DIR)
        t_read = time.perf_counter()
        ctx.trace = reduce_file(path) if path else None
        trace_io["read_s"] = time.perf_counter() - t_read
        trace_io["bytes"] = os.path.getsize(path) if path else 0
    ctx.counts = getattr(driver, "counts", lambda s: {})(st)
    if devices[0].platform == "tpu":
        from counts import load_peaks

        ctx.peaks = load_peaks(kind)

    w = ctx.window
    print(json.dumps({"rounds": w["rounds"], "attempted": w["attempted"],
                      "failed": failed,
                      "uploads_applied": w.get("uploads"),
                      "uploads_lost_in_channel": w.get("lost_in_channel"),
                      "uploads_left_out_by_quorum": w.get("left_out_by_quorum"),
                      "window_s": w["elapsed_s"],
                      "compiles_in_window": window_compiles,
                      "compile_s_in_window": window_compile_s,
                      "setup_compiles": compiles0,
                      "setup_compile_s": compile_s0,
                      "trace": trace_io if args.trace else None,
                      "driver": w.get("notes")}), flush=True)

    metrics = {}
    for m in spec["per_layer" if args.trace else "end_to_end"]:
        value = load_module("metrics", m["name"]).read(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    checks = driver.check(st)
    checks["compiles_in_window"] = (float(window_compiles), 0.0)
    correct = failed == 0 and all(v <= lim for v, lim in checks.values())

    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices), "memory_peak_bytes": peak}
    result = {"correct": correct, "attempted": w["attempted"],
              "failed": failed, "metrics": metrics, "device": device}
    if args.trace and ctx.trace:
        from trace_reduce import breakdown

        device["busy_s"] = ctx.trace["busy_s"]
        device["window_s"] = ctx.trace["window_s"]
        result["breakdown"] = breakdown(ctx.trace)
    result["checks"] = {k: {"value": v, "limit": lim}
                        for k, (v, lim) in checks.items()}
    for k, (v, lim) in checks.items():
        print(f"check {k}: {v!r} (limit {lim!r})", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
