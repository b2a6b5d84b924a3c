#!/usr/bin/env python3
"""Run one benchmark cell once, on the chips of this machine.

    python3 benchmarks/run.py --workload <cell> --seed <n> \\
        --seconds <s> --trace <0|1>

The cell is a `workloads` entry of BENCHMARK.json at the root of the
checkout. It names a configuration (benchmarks/configs/<config>.json)
and a traffic mix (benchmarks/traffic/<traffic>.json), whose
"generator" names the module under benchmarks/traffic/ that drives the
program. Each per-layer metric is read by benchmarks/metrics/<name>.py.
So a new configuration, mix, metric or cell is new files and entries.

One process holds the chips; it starts no child. It fails unless JAX's
devices are TPUs, as many as the cell asks for. It keeps JAX's compile
cache in the checkout (mythril_tpu/support/devices.enable_compile_cache,
or where JAX_COMPILATION_CACHE_DIR says), warms up the cell's shapes,
then measures for --seconds: the window ends at the first completed
unit of work (an analysis, a storm) at or after that which ends a whole
pass over the mix (every contract of a corpus once). --trace 0
reports the cell's end-to-end metrics; --trace 1 records the program's
spans and a profiler trace of the window (or of its first
"trace_seconds", where the mix sets that) and reports its per-layer
metrics. After the window the generator compares what the window
produced with the configuration's plain reference.

The last line of standard output is one JSON object: correct,
attempted, failed, metrics, device, breakdown (traced runs) and, last,
checks: each number compared with its limit. The checks are also the
last lines of standard error.
"""

import time

#: set-up is timed from here, before any import
T_START = time.monotonic()

import argparse  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

#: telemetry ring size in traced runs: every span of a window
SPAN_CAPACITY = 1 << 21
#: harness annotations in the profiler trace
WINDOW, CLOCK = "bench.window", "bench.clock"


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def merged(base: dict, override: dict) -> dict:
    """base with override's keys laid over it, nested dicts key by key."""
    out = dict(base)
    for k, v in override.items():
        out[k] = (merged(out[k], v) if isinstance(v, dict)
                  and isinstance(out.get(k), dict) else v)
    return out


def for_cell(entries: list, cell: str) -> list:
    return [m for m in entries if cell in m.get("workloads", [cell])]


def metric_reader(name: str, root: Path):
    path = root / "benchmarks" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "benchmarks.metrics." + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def cell_inputs(bench: dict, cell: str, root: Path):
    """(workload entry, configuration, traffic mix) of a cell."""
    work = {w["name"]: w for w in bench["workloads"]}
    if cell not in work:
        raise KeyError(f"no workload {cell!r} in BENCHMARK.json")
    w = work[cell]
    config = load_json(root / "benchmarks" / "configs" / f"{w['config']}.json")
    mix = load_json(root / "benchmarks" / "traffic" / f"{w['traffic']}.json")
    return w, config, mix


def _device_block(chips: int, require_tpu: bool) -> dict:
    from benchmarks.device import require_chips

    if require_tpu:
        return require_chips(chips)
    import jax

    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind, "count": chips}


def _window(driver, seconds: float, annotate):
    """Closed loop until the first completion at or after `seconds` that
    ends a whole pass over the mix (driver.period units of work), so
    that a rate covers every kind of work in its share; (start, end) on
    the monotonic clock."""
    t0 = time.monotonic()
    while True:
        with annotate(driver.annotation):
            driver.run_one()
        if (time.monotonic() - t0 >= seconds
                and driver.attempted % driver.period == 0):
            return t0, time.monotonic()


def run_cell(bench: dict, cell: str, seed: int, seconds: float,
             trace: bool, root: Path = ROOT, require_tpu: bool = True,
             config_override: dict = None, t_start: float = None) -> dict:
    """One run of a cell: the result line as a dict."""
    w, config, mix = cell_inputs(bench, cell, root)
    if config_override:
        config = merged(config, config_override)
    # libtpu's logs go under TMPDIR, not to a fixed /tmp path
    os.environ.setdefault(
        "TPU_LOG_DIR", str(Path(tempfile.gettempdir()) / "tpu_logs"))
    device = _device_block(w["chips"], require_tpu)
    import jax

    from benchmarks.device import CompileClock, memory_peak_bytes
    from benchmarks.traffic import counters
    from mythril_tpu.support.devices import enable_compile_cache

    enable_compile_cache()
    clock = CompileClock()
    gen = importlib.import_module(f"benchmarks.traffic.{mix['generator']}")
    driver = gen.Driver(config, mix, seed, root)
    driver.warm_up(clock)
    setup_s = time.monotonic() - (T_START if t_start is None else t_start)

    profile_dir = None
    if trace:
        from benchmarks import spans

        spans.enable(SPAN_CAPACITY)
        profile_dir = tempfile.mkdtemp(prefix="bench_profile_")
        # the Python tracer would record every Python call the program
        # makes: it slows the host and swells the trace; the harness's
        # TraceAnnotations are kept at host level 1
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 1
        jax.profiler.start_trace(profile_dir, profiler_options=options)
        clock_mark = time.monotonic()
        with jax.profiler.TraceAnnotation(CLOCK):
            pass
        # a mix may trace a shorter window than it measures, to keep the
        # trace, and the run, short
        seconds = min(seconds, mix.get("trace_seconds", seconds))
    annotate = jax.profiler.TraceAnnotation
    counters0, compile0 = counters.read(), clock.seconds
    try:
        with annotate(WINDOW):
            t0, t1 = _window(driver, seconds, annotate)
        counters1, compile1 = counters.read(), clock.seconds
    finally:
        if trace:
            jax.profiler.stop_trace()
    window_s = t1 - t0
    device["memory_peak_bytes"] = memory_peak_bytes(w["chips"])

    harness = {"setup_compile_s": compile0,
               "cache_hits": clock.cache_hits,
               "cache_misses": clock.cache_misses,
               "window_compile_s": compile1 - compile0,
               "units": driver.walls, "warmup": driver.warmup}
    result = {"correct": None, "attempted": driver.attempted,
              "failed": driver.failed, "metrics": {}, "device": device,
              "harness": harness}
    if trace:
        t_reduce = time.monotonic()
        try:
            record, reduced = _traced_record(
                driver, profile_dir, (t0, t1), clock_mark,
                {k: counters1[k] - counters0[k] for k in counters1},
                compile1 - compile0, require_tpu)
        finally:
            shutil.rmtree(profile_dir, ignore_errors=True)
        harness.update(trace_reduce_s=time.monotonic() - t_reduce,
                       trace_events=record["trace_events"],
                       spans_dropped=record["spans_dropped"])
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        from benchmarks.trace_reduce import top_ops

        result["breakdown"] = {"device_ops": top_ops(reduced),
                               "idle_gaps": reduced["idle_gaps"]}
        for m in for_cell(bench["per_layer"], cell):
            value = metric_reader(m["name"], root)(record)
            if value is not None:
                result["metrics"][m["name"]] = {"value": value,
                                                "unit": m["unit"]}
    else:
        values = driver.end_to_end(window_s)
        values["setup_s"] = setup_s
        for m in for_cell(bench["end_to_end"], cell):
            result["metrics"][m["name"]] = {"value": values[m["name"]],
                                            "unit": m["unit"]}
    t_ref = time.monotonic()
    checks = driver.checks()
    harness["reference_s"] = time.monotonic() - t_ref
    harness.update(getattr(driver, "notes", {}))
    result["correct"] = all(v <= limit for _, v, limit in checks)
    result["checks"] = {name: {"value": v, "limit": limit}
                        for name, v, limit in checks}
    return result


def _traced_record(driver, profile_dir, window, clock_mark, counters,
                   compile_s, require_tpu):
    """What the per-layer readers read, and the trace's reduction."""
    from benchmarks import spans, trace_reduce

    t0, t1 = window
    pd = trace_reduce.load(profile_dir)
    marks = trace_reduce.host_events(pd, {WINDOW, CLOCK})
    trace_window = next((s, e) for s, e, n in marks if n == WINDOW)
    clock_ns = next(s for s, e, n in marks if n == CLOCK)
    offset = clock_ns - clock_mark * 1e9
    program = spans.recorded()
    on_trace = [(s * 1e9 + offset, e * 1e9 + offset, n)
                for s, e, n in program]
    on_trace += trace_reduce.host_events(pd, {driver.annotation})
    devices = (trace_reduce.tpu_devices(pd) if require_tpu
               else trace_reduce.cpu_devices(pd))
    reduced = trace_reduce.reduce(devices, trace_window, on_trace)
    by_name = {}
    for s, e, n in trace_reduce.clip(program, t0, t1):
        by_name.setdefault(n, []).append((s - t0, e - t0))
    record = dict(driver.record(), window_s=t1 - t0, spans=by_name,
                  counters=counters, compile_s=compile_s, trace=reduced,
                  spans_dropped=spans.dropped(),
                  trace_events=sum(len(d["ops"]) for d in devices.values()))
    return record, reduced


def emit(result: dict) -> None:
    """The result line on stdout; the checks last on stderr too."""
    print(json.dumps(result), flush=True)
    for name, c in result["checks"].items():
        print(f"check {name} = {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    print(f"correct = {result['correct']}", file=sys.stderr, flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    a = ap.parse_args(argv)
    from benchmarks.device import NoAccelerator

    bench = load_json(ROOT / "BENCHMARK.json")
    try:
        result = run_cell(bench, a.workload, a.seed, a.seconds,
                          bool(a.trace))
    except NoAccelerator as e:
        print(f"run.py: {e}", file=sys.stderr)
        return 2
    emit(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
