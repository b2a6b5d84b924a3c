"""Profile one BASELINE config (2 or 3) lane + host run.

Usage: python tools/profile_config.py [2|3] [--host] [--cprofile]

Prints the _analyze_fixture detail dict, lane-engine RUN_STATS_TOTAL,
and (with --cprofile) the top-40 cumulative-time functions.
"""

import cProfile
import io
import json
import pstats
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import bench  # noqa: E402


def _trace_compiles():
    """Print a Python stack at every XLA compile (--trace-compiles)."""
    import traceback

    from jax._src import compiler

    orig = compiler.backend_compile_and_load

    def wrapped(*a, **k):
        print("=== COMPILE at ===", file=sys.stderr)
        traceback.print_stack(file=sys.stderr)
        return orig(*a, **k)

    compiler.backend_compile_and_load = wrapped


def _log_queries():
    """Log every get_model call: sizes, objectives, wall (--log-queries)."""
    import mythril_tpu.support.model as sm
    from mythril_tpu.smt import terms as T

    orig = sm.get_model.__wrapped__

    def wrapped(constraints, minimize=(), maximize=(), *a, **k):
        t0 = time.perf_counter()
        err = ""
        try:
            return orig(constraints, minimize, maximize, *a, **k)
        except Exception as e:
            err = type(e).__name__
            raise
        finally:
            wall = time.perf_counter() - t0
            n = len(constraints) if isinstance(constraints, tuple) else -1
            seen = set()
            nodes = 0
            work = [c.raw for c in constraints if hasattr(c, "raw")]
            while work:
                t = work.pop()
                if t.tid in seen:
                    continue
                seen.add(t.tid)
                nodes += 1
                work.extend(t.args)
            print(f"QUERY n={n} dag={nodes} min={len(minimize)} "
                  f"max={len(maximize)} wall={wall:.3f} {err}",
                  file=sys.stderr, flush=True)

    import functools
    patched = functools.lru_cache(maxsize=2**23)(wrapped)
    sm.get_model = patched
    import mythril_tpu.analysis.solver as asolver
    import mythril_tpu.laser.plugin.plugins.mutation_pruner as mp

    asolver.get_model = patched
    mp.get_model = patched


def main():
    if "--trace-compiles" in sys.argv:
        _trace_compiles()
    if "--log-queries" in sys.argv:
        _log_queries()
    cfg = "2" if "2" in sys.argv[1:2] else ("3" if "3" in sys.argv[1:2] else "2")
    host = "--host" in sys.argv
    prof = "--cprofile" in sys.argv
    from tests.fixture_paths import INPUTS
    from mythril_tpu.laser import lane_engine

    fixture, txs, lanes = (
        ("metacoin.sol.o", 2, 256) if cfg == "2"
        else ("overflow.sol.o", 3, 4096)
    )
    path = Path(INPUTS) / fixture
    width = lane_engine.pick_width(lanes, 1)
    for i, a in enumerate(sys.argv):
        if a == "--width":
            width = int(sys.argv[i + 1])
    lane_engine.FORCE_WIDTH = width
    try:
        if not host:
            for bucket in (16, width):
                lane_engine.warm_variant(
                    width, 1024, {}, lane_engine.DEFAULT_WINDOW, 8192,
                    seed_bucket=bucket)
        lane_engine.RUN_STATS_TOTAL = {}
        pr = cProfile.Profile()
        print(f"=== REGION START {time.strftime('%H:%M:%S')} ===",
              file=sys.stderr, flush=True)
        t0 = time.perf_counter()
        if prof:
            pr.enable()
        r = bench._analyze_fixture(path, 120, txs, 0 if host else lanes)
        if prof:
            pr.disable()
        wall = time.perf_counter() - t0
        print(f"=== REGION END {time.strftime('%H:%M:%S')} ===",
              file=sys.stderr, flush=True)
    finally:
        lane_engine.FORCE_WIDTH = None
    print(json.dumps({"mode": "host" if host else "lane", "config": cfg,
                      "wall_s": round(wall, 2), **r}), flush=True)
    print("RUN_STATS_TOTAL:", json.dumps(lane_engine.RUN_STATS_TOTAL),
          flush=True)
    if prof:
        s = io.StringIO()
        ps = pstats.Stats(pr, stream=s).sort_stats("cumulative")
        ps.print_stats(40)
        print(s.getvalue(), flush=True)


if __name__ == "__main__":
    main()
    sys.stdout.flush()
    import os
    os._exit(0)
