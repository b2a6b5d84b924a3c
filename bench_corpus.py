"""Corpus benchmark: full analysis (all 14 detectors) over the
reference's bytecode fixture corpus — the measurable stand-in for
BASELINE.md config 4 (solidity_examples sweep; solc is absent in this
image, so the reference's precompiled testdata .sol.o fixtures serve as
the corpus). Prints one JSON line per contract and an aggregate.

Usage: python bench_corpus.py [--timeout SECS]
"""

import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))
from tests.fixture_paths import INPUTS  # noqa: E402

# The corpus is mixed: these four fixtures are CREATION bytecode (the
# reference's analysis_tests run them without --bin-runtime; their
# disassembly ends in the CODECOPY/RETURN deploy prologue), everything
# else is runtime bytecode (loaded as EVMContract(code=...) by the
# reference's statespace/cmd-line tests).
CREATION_FIXTURES = {
    "flag_array.sol.o",
    "exceptions_0.8.0.sol.o",
    "symbolic_exec_bytecode.sol.o",
    "extcall.sol.o",
}


def analyze_report(path: Path, timeout: int, tpu_lanes: int = 0):
    """(report, wall seconds) of the corpus analysis of one fixture:
    every detector, two transactions, bfs."""
    from mythril_tpu.orchestration.mythril_analyzer import MythrilAnalyzer
    from mythril_tpu.orchestration.mythril_disassembler import (
        MythrilDisassembler,
    )
    from mythril_tpu.support.analysis_args import make_cmd_args

    disassembler = MythrilDisassembler(eth=None)
    code = path.read_text().strip()
    address, _ = disassembler.load_from_bytecode(
        code, bin_runtime=path.name not in CREATION_FIXTURES
    )
    cmd_args = make_cmd_args(execution_timeout=timeout,
                             tpu_lanes=tpu_lanes)
    analyzer = MythrilAnalyzer(
        disassembler=disassembler, cmd_args=cmd_args, strategy="bfs",
        address=address,
    )
    t0 = time.perf_counter()
    report = analyzer.fire_lasers(modules=None, transaction_count=2)
    return report, time.perf_counter() - t0


def analyze_one(path: Path, timeout: int, tpu_lanes: int = 0):
    report, elapsed = analyze_report(path, timeout, tpu_lanes)
    issues = report.sorted_issues()
    return {
        "contract": path.name,
        "wall_s": round(elapsed, 2),
        "issues": len(issues),
        "swc": sorted({i["swc-id"] for i in issues}),
    }


def main_daemon(cli) -> int:
    """--daemon mode: the same corpus, every fixture submitted to a
    resident daemon; rows keep the in-process schema (contract /
    wall_s / issues / swc) so reports diff directly against the
    one-shot sweep — the BENCH_r12 identity gate."""
    from mythril_tpu.daemon.client import DaemonClient, DaemonError

    client = DaemonClient(cli.daemon)
    fixtures = sorted(INPUTS.glob("*.sol.o"))
    if not fixtures:
        print(f"no *.sol.o fixtures under {INPUTS}", file=sys.stderr)
        return 1
    results = []
    t0 = time.perf_counter()
    for path in fixtures:
        try:
            row = client.analyze(
                path.read_text().strip(),
                bin_runtime=path.name not in CREATION_FIXTURES,
                name=path.name, timeout=cli.timeout,
                tpu_lanes=cli.tpu_lanes)
            r = {"contract": path.name, "wall_s": row["wall_s"],
                 "issues": row["issue_count"],
                 "swc": sorted({i["swc-id"] for i in row["issues"]})}
        except (DaemonError, OSError) as e:
            r = {"contract": path.name, "error": type(e).__name__}
        results.append(r)
        print(json.dumps(r), flush=True)
    total = time.perf_counter() - t0
    agg = {
        "corpus": len(results),
        "total_wall_s": round(total, 1),
        "total_issues": sum(r.get("issues", 0) for r in results),
        "errors": sum(1 for r in results if "error" in r),
        "daemon": cli.daemon,
    }
    try:
        agg["daemon_state"] = client.ping()
    except (DaemonError, OSError):
        pass
    print(json.dumps(agg))
    return 0


def main():
    import argparse

    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--timeout", type=int, default=60)
    parser.add_argument(
        "--tpu-lanes", type=int, default=0,
        help="lane-engine width (0 = host interpreter); corpus mode "
        "amortizes device init/trace/compile-cache over all contracts",
    )
    parser.add_argument(
        "--solver-workers", type=int, default=None,
        help="persistent solver pool width (smt/solver/pool.py; "
        "default $MTPU_SOLVER_WORKERS or min(4, cpu); 1 = serial)",
    )
    parser.add_argument(
        "--trace-out", default=None, metavar="FILE",
        help="record telemetry spans (implies MTPU_TRACE=1) and "
        "write a Chrome trace-event JSON to FILE at exit "
        "(docs/observability.md)",
    )
    parser.add_argument(
        "--warm-dir", default=None, metavar="DIR",
        help="bind the cross-run warm store to DIR/warm "
        "(support/warm_store.py; MTPU_WARM_DIR overrides) so a "
        "re-run of this corpus starts from prior proofs/static "
        "artifacts/routing history — docs/warm_store.md",
    )
    parser.add_argument(
        "--no-warm-store", action="store_true",
        help="force the cross-run warm store off (same as "
        "MTPU_WARM=0; bit-for-bit cold behavior)",
    )
    parser.add_argument(
        "--daemon", default=None, metavar="SOCK",
        help="submit every fixture to a resident `myth serve` daemon "
        "on SOCK instead of analyzing in-process (docs/daemon.md): "
        "the daemon's warm jit caches/solver sessions/warm store "
        "serve the whole corpus, and each row reports the daemon's "
        "request wall",
    )
    cli = parser.parse_args()
    if cli.daemon:
        return main_daemon(cli)
    # persistent XLA compile cache, exactly as bench.py main enables
    # it: lane-path corpus runs otherwise re-pay multi-second kernel
    # compiles per process, which swamps (and noises) every
    # cross-process wall comparison this harness exists to make
    from mythril_tpu.support.devices import enable_compile_cache

    enable_compile_cache()
    if cli.no_warm_store:
        from mythril_tpu.support.support_args import args as sargs

        sargs.no_warm_store = True
    elif cli.warm_dir:
        from mythril_tpu.support import warm_store

        warm_store.configure(cli.warm_dir)
    if cli.solver_workers is not None:
        from mythril_tpu.smt.solver.pool import configure_pool

        configure_pool(workers=cli.solver_workers)
    if cli.trace_out:
        from mythril_tpu.support import telemetry

        telemetry.configure(trace_out=cli.trace_out, enable=True)
    timeout = cli.timeout
    fixtures = sorted(INPUTS.glob("*.sol.o"))
    if not fixtures:
        print(f"no *.sol.o fixtures under {INPUTS}", file=sys.stderr)
        return 1
    results = []
    t0 = time.perf_counter()
    for path in fixtures:
        try:
            r = analyze_one(path, timeout, cli.tpu_lanes)
        except Exception as e:  # noqa: BLE001 - keep sweeping
            r = {"contract": path.name, "error": type(e).__name__}
        results.append(r)
        print(json.dumps(r), flush=True)
    total = time.perf_counter() - t0
    agg = {
        "corpus": len(results),
        "total_wall_s": round(total, 1),
        "total_issues": sum(r.get("issues", 0) for r in results),
        "errors": sum(1 for r in results if "error" in r),
    }
    try:
        # the solver-layer counter block (batched discharge, verdict
        # cache, shipped/replayed proofs) — same visibility the
        # multi-rank corpus shard reports carry
        from mythril_tpu.smt.solver.solver_statistics import (
            SolverStatistics,
        )

        agg["solver"] = SolverStatistics().batch_counters()
    except Exception:
        pass
    print(json.dumps(agg))


if __name__ == "__main__":
    sys.exit(main())
