#!/usr/bin/env python3
"""Bring-up check: run the analyzer's main paths on one TPU chip.

    python chip_smoke.py               # phases a-d on one chip
    python chip_smoke.py --four-chips  # the lane mesh on four chips

This parent process never imports JAX. Each phase runs in a child
process, one after another, so that one process at a time holds the
chip; the children share one persistent compile cache
(support/devices.enable_compile_cache). Every phase prints one JSON
line: the device as JAX reports it, wall and compile seconds, and the
counters that show the device did the work. The script exits non-zero
when a phase fails, and then prints no result line. The last line of a
passing run is {"ok": true, "device": {...}}.

Phases:
  a. cli     `myth analyze` on flag_array.sol.o, EtherThief, 64 lanes.
  b. corpus  the 18 vendored fixtures at 4096 lanes, every detector,
             -t 2, against the host interpreter on the same fixtures.
  c. state   the 2^15-path storm (bench.build_symbolic_contract(k=15))
             on a 32768-lane engine.
  d. serve   `myth serve` on the chip, three `myth analyze --daemon`
             clients that never touch JAX, then SIGTERM.
  four-chips the state storm at 4x8192 lanes on a four-chip lane mesh
             and on one device, in one process.
"""

import argparse
import hashlib
import io
import json
import shutil
import signal
import subprocess
import sys
import tempfile
import threading
import time
from contextlib import redirect_stdout
from pathlib import Path

HERE = Path(__file__).resolve().parent
INPUTS = HERE / "tests" / "fixtures" / "testdata" / "inputs"
FLAG_ARRAY = INPUTS / "flag_array.sol.o"
#: corpus fixtures the server phase re-runs against phase b's lanes
SERVE_FIXTURES = ("origin.sol.o", "suicide.sol.o")
#: per-contract execution timeout of phases b and d: generous, since
#: an analysis cut off early reports fewer issues, and the compile of
#: a new window variant lands inside the contract that first needs it;
#: identity with the host interpreter needs both sides to finish
CORPUS_TIMEOUT = 300
CORPUS_LANES = 4096
STATE_K = 15
#: device errors the analyzer recovers from on the host; any above 0
#: fails the phase (SolverStatistics, support/devices.note_device_error)
ERROR_COUNTERS = ("device_warmup_errors", "device_explore_errors",
                  "device_prefilter_errors", "device_screen_errors",
                  "device_shadow_errors")
PHASE_TIMEOUT = 900


class SmokeFailure(Exception):
    pass


def _check(cond, what):
    if not cond:
        raise SmokeFailure(what)


# -- child side: runs with the chip ------------------------------------------


def _device() -> dict:
    """The device line; fails unless JAX's default device is a TPU."""
    import jax

    devices = jax.devices()
    d = devices[0]
    if d.platform != "tpu":
        raise SmokeFailure(
            f"no TPU found: JAX's default device is {d.platform} "
            f"({d.device_kind}); chip_smoke.py runs only on a TPU")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices)}


class CompileClock:
    """Backend compile seconds (persistent-cache loads included) and
    persistent-cache hits/misses, from JAX's monitoring events."""

    def __init__(self, sink=None):
        import jax

        self.seconds = 0.0
        self.cache_hits = 0
        self.cache_misses = 0
        self._sink = sink
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, event, duration_secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration_secs
            if self._sink is not None:
                self._sink(self.as_dict())

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1

    def as_dict(self) -> dict:
        return {"compile_s": self.seconds, "cache_hits": self.cache_hits,
                "cache_misses": self.cache_misses}


def _setup():
    from mythril_tpu.support.devices import enable_compile_cache

    enable_compile_cache()


def _device_errors() -> dict:
    from mythril_tpu.smt.solver.solver_statistics import SolverStatistics

    counters = SolverStatistics().batch_counters()
    return {k: counters[k] for k in ERROR_COUNTERS}


def _windows() -> int:
    from mythril_tpu.laser import lane_engine

    return lane_engine.RUN_STATS_TOTAL.get("windows", 0)


def _canon_digest(report_json: str) -> str:
    from tests.compare_lane_host import canon

    issues = canon(json.loads(report_json))
    return hashlib.sha256(
        json.dumps(issues, sort_keys=True).encode()).hexdigest()


def _flag_array_checks(report: dict) -> None:
    issues = report.get("issues") or []
    _check(len(issues) == 1, f"flag_array: {len(issues)} issues, not 1")
    issue = issues[0]
    calldata = issue["tx_sequence"]["steps"][-1]["input"]
    _check(calldata.startswith("0xab125858") and calldata.endswith("04d2"),
           f"flag_array: exploit calldata {calldata}")
    _check(issue.get("function") == "extractMoney(uint256)",
           f"flag_array: function {issue.get('function')}")


def phase_cli() -> dict:
    """a. `myth analyze` through its own main(), in this process so the
    lane engine's counters can be read after it returns."""
    from mythril_tpu.interfaces import cli

    argv = ["myth", "analyze", "-f", str(FLAG_ARRAY), "-t", "1",
            "-m", "EtherThief", "--tpu-lanes", "64",
            "--no-onchain-data", "-o", "json"]
    out = io.StringIO()
    rc = 0
    old_argv, sys.argv = sys.argv, argv
    try:
        with redirect_stdout(out):
            cli.main()
    except SystemExit as e:
        # the CLI exits 1 when it finds issues: the report decides
        rc = e.code
    finally:
        sys.argv = old_argv
    report = json.loads(out.getvalue())
    _flag_array_checks(report)
    return {"cli_rc": rc, "issues": len(report["issues"]),
            "canon": _canon_digest(out.getvalue())}


def phase_corpus() -> dict:
    """b. The 18-fixture corpus on 4096 lanes, then host-only."""
    import bench_corpus

    fixtures = sorted(INPUTS.glob("*.sol.o"))
    _check(len(fixtures) == 18, f"{len(fixtures)} fixtures, not 18")
    rows = {}
    for lanes in (CORPUS_LANES, 0):
        w0 = _windows()
        t0 = time.perf_counter()
        for path in fixtures:
            report, wall = bench_corpus.analyze_report(
                path, CORPUS_TIMEOUT, lanes)
            row = rows.setdefault(path.name, {})
            key = "lane" if lanes else "host"
            row[key] = _canon_digest(report.as_json())
            row[key + "_issues"] = len(report.sorted_issues())
            row[key + "_wall_s"] = wall
        if lanes:
            lane_s, lane_windows = time.perf_counter() - t0, _windows() - w0
        else:
            host_s = time.perf_counter() - t0
    diff = {n: {k: v for k, v in r.items() if k not in ("lane", "host")}
            for n, r in rows.items() if r["lane"] != r["host"]}
    _check(not diff, f"lane and host issue sets differ: {diff}")
    _check(lane_windows > 0, "corpus ran no device window")
    from mythril_tpu.laser import lane_engine

    return {"lane_wall_s": lane_s, "host_wall_s": host_s,
            "lane_windows": lane_windows,
            "window_variants": len(lane_engine._WARM),
            "issues": sum(r["lane_issues"] for r in rows.values()),
            "per_fixture": {n: {"issues": r["lane_issues"],
                                "lane_s": r["lane_wall_s"],
                                "host_s": r["host_wall_s"]}
                            for n, r in rows.items()},
            "canon": {n: rows[n]["lane"] for n in SERVE_FIXTURES}}


def _storm_paths(n_lanes: int, mesh: int):
    """Explore the 2^STATE_K-path storm at n_lanes, sharded over the
    lane mesh when mesh != 0; (paths, seconds, engine, path set)."""
    import bench
    from mythril_tpu.analysis.symbolic import SymExecWrapper
    from mythril_tpu.ethereum.evmcontract import EVMContract
    from mythril_tpu.laser import lane_engine
    from mythril_tpu.orchestration.mythril_analyzer import (
        reset_analysis_state,
    )
    from mythril_tpu.support.support_args import args

    code, n_paths = bench.build_symbolic_contract(k=STATE_K)
    reset_analysis_state()
    lane_engine.PATH_HISTORY[code] = n_paths
    lane_engine.FORCE_WIDTH = n_lanes
    args.tpu_lanes, args.tpu_mesh = n_lanes, mesh
    t0 = time.perf_counter()
    try:
        sym = SymExecWrapper(
            EVMContract(code=code.hex(), name="storm"),
            address=0xDEADBEEF, strategy="bfs", max_depth=8192,
            execution_timeout=600, create_timeout=10,
            transaction_count=1, compulsory_statespace=False,
            run_analysis_modules=False)
    finally:
        lane_engine.FORCE_WIDTH = None
        args.tpu_lanes, args.tpu_mesh = 0, -1
    wall = time.perf_counter() - t0
    states = sym.laser.open_states
    # a path is the storage it wrote: level i writes slot i on one arm
    # only, so the written (slot, value) set names the path, and it is
    # free of run-specific symbol names
    paths = {tuple(sorted(
        (k.value, v.value) for k, v in
        ws.accounts[0xDEADBEEF].storage.printable_storage.items()))
        for ws in states}
    engines = list(getattr(sym.laser, "_lane_engines", {}).values())
    _check(len(states) == n_paths,
           f"storm found {len(states)} of {n_paths} paths")
    _check(len(paths) == n_paths,
           f"storm paths not distinct: {len(paths)} of {n_paths}")
    return wall, engines, paths


def _peak_bytes(device) -> int:
    stats = device.memory_stats() or {}
    return stats.get("peak_bytes_in_use", 0)


def phase_state() -> dict:
    """c. The 2^15-path storm on one 32768-lane engine."""
    import jax

    from mythril_tpu.laser import lane_engine

    n_lanes = 2 ** STATE_K
    w0 = _windows()
    wall, _engines, paths = _storm_paths(n_lanes, mesh=0)
    clamps = dict(lane_engine.CAPACITY_CLAMPS)
    return {"lanes": n_lanes, "paths": len(paths), "explore_s": wall,
            "windows": _windows() - w0,
            "peak_bytes_in_use": _peak_bytes(jax.devices()[0]),
            "capacity_clamped": bool(clamps), "capacity_clamps": clamps}


def phase_four_chips() -> dict:
    """The storm at 4x8192 lanes on the four-chip lane mesh, then on
    one device; the path sets must agree."""
    import jax

    from mythril_tpu.parallel.mesh import LANES_AXIS

    devices = jax.devices()
    _check(len(devices) == 4, f"{len(devices)} devices, not 4")
    n_lanes = 4 * 8192
    w0 = _windows()
    mesh_s, engines, mesh_paths = _storm_paths(n_lanes, mesh=4)
    mesh_windows = _windows() - w0
    sharded = [e for e in engines if e.mesh is not None]
    _check(sharded, "no engine ran on the lane mesh")
    st = sharded[0]._acquire_state()
    try:
        sh = st.pc.sharding
        spec_axes = tuple(sh.spec)
        plane_devices = sorted(d.id for d in sh.device_set)
        shard_bytes = sum(x.nbytes for x in jax.tree_util.tree_leaves(st)
                          if x.ndim and x.shape[0] == n_lanes) // 4
    finally:
        sharded[0]._release_state(st)
    _check(LANES_AXIS in spec_axes and len(plane_devices) == 4,
           f"lane planes not sharded over 4 devices: {sh}")
    # every device must hold at least its quarter of the lane planes
    mesh_peaks = [_peak_bytes(d) for d in devices]
    _check(min(mesh_peaks) >= shard_bytes,
           f"a device holds less than its lane-plane shard of "
           f"{shard_bytes} bytes: peak bytes {mesh_peaks}")
    w1 = _windows()
    one_s, _engines, one_paths = _storm_paths(n_lanes, mesh=0)
    _check(mesh_paths == one_paths,
           "mesh and single-device path sets differ")
    return {"lanes": n_lanes, "paths": len(mesh_paths),
            "mesh_explore_s": mesh_s, "one_device_explore_s": one_s,
            "mesh_windows": mesh_windows, "one_device_windows":
            _windows() - w1, "plane_spec": str(spec_axes),
            "plane_devices": plane_devices,
            "plane_bytes_per_device": shard_bytes,
            "peak_bytes_per_device": mesh_peaks}


def server_child(out_dir: str) -> int:
    """d's server: the device line on stdout, then `myth serve` in this
    process; compile seconds go to OUT/compile.json as they accrue."""
    from mythril_tpu.interfaces import cli

    print(json.dumps({"device": _device()}), flush=True)
    sink = Path(out_dir) / "compile.json"
    CompileClock(sink=lambda d: sink.write_text(json.dumps(d)))
    sys.argv = ["myth", "serve", "--out-dir", out_dir]
    cli.main()
    return 0


PHASES = {"cli": phase_cli, "corpus": phase_corpus, "state": phase_state,
          "four-chips": phase_four_chips}


def run_phase(name: str) -> int:
    """Child entry: run one phase and print its line."""
    line = {"phase": name}
    try:
        line["device"] = _device()
        _setup()
        clock = CompileClock()
        t0 = time.perf_counter()
        line.update(PHASES[name]())
        line["wall_s"] = time.perf_counter() - t0
        line.update(clock.as_dict())
        line["device_errors"] = _device_errors()
        _check(not any(line["device_errors"].values()),
               f"device errors recovered on the host: "
               f"{line['device_errors']}")
        if name in ("cli", "corpus"):
            _check(_windows() > 0, "no device window ran")
        line["ok"] = True
    except SmokeFailure as e:
        line.update(ok=False, error=str(e))
    print(json.dumps(line), flush=True)
    return 0 if line["ok"] else 1


# -- parent side: never touches JAX ------------------------------------------


def _child(args, timeout=PHASE_TIMEOUT) -> dict:
    """Run `chip_smoke.py --phase ...` and return its last line."""
    try:
        proc = subprocess.run(
            [sys.executable, str(Path(__file__).resolve()), *args],
            cwd=str(HERE), stdout=subprocess.PIPE, text=True,
            timeout=timeout)
    except subprocess.TimeoutExpired:
        return {"phase": args[-1], "ok": False,
                "error": f"no phase line within {timeout} s"}
    lines = proc.stdout.strip().splitlines()
    try:
        return json.loads(lines[-1])
    except (IndexError, json.JSONDecodeError):
        return {"phase": args[-1], "ok": False,
                "error": f"exit {proc.returncode}, no phase line"}


def _client(sock: str, fixture: str, extra) -> dict:
    """One `myth analyze --daemon` submission; its process never
    imports JAX (the server holds the chip)."""
    path = INPUTS / fixture
    argv = [sys.executable, str(HERE / "myth"), "analyze", "-f", str(path),
            "--daemon", sock, "--no-onchain-data", "-o", "json", *extra]
    if fixture not in ("flag_array.sol.o", "exceptions_0.8.0.sol.o",
                       "symbolic_exec_bytecode.sol.o", "extcall.sol.o"):
        argv.append("--bin-runtime")
    proc = subprocess.run(argv, cwd=str(HERE), capture_output=True,
                          text=True, timeout=PHASE_TIMEOUT)
    _check(proc.returncode in (0, 1),
           f"client {fixture} exit {proc.returncode}: {proc.stderr[-400:]}")
    return json.loads(proc.stdout)


def phase_serve(expect_cli: str, expect_corpus: dict) -> dict:
    """d. `myth serve` on the chip, three clients, SIGTERM."""
    line = {"phase": "serve"}
    out = Path(tempfile.mkdtemp(prefix="chip_smoke_serve_"))
    t0 = time.perf_counter()
    server = subprocess.Popen(
        [sys.executable, str(Path(__file__).resolve()), "--server",
         str(out)], cwd=str(HERE), stdout=subprocess.PIPE, text=True)
    # a server that never answers is killed, and its readline ends
    watchdog = threading.Timer(PHASE_TIMEOUT, server.kill)
    watchdog.start()
    try:
        first = server.stdout.readline()
        try:
            line["device"] = json.loads(first)["device"]
        except (json.JSONDecodeError, KeyError):
            raise SmokeFailure(f"server did not start: {first[:400]!r}")
        ready = server.stdout.readline()
        _check(ready.startswith("daemon ready on "),
               f"server not ready: {ready[:400]!r}")
        sock = ready.split("daemon ready on ", 1)[1].strip()
        # flag_array as in phase a; two corpus fixtures as in phase b
        rep = _client(sock, "flag_array.sol.o",
                      ["-t", "1", "-m", "EtherThief", "--tpu-lanes", "64"])
        _flag_array_checks(rep)
        got = {"flag_array.sol.o": _canon_of(rep)}
        _check(got["flag_array.sol.o"] == expect_cli,
               "server's flag_array issues differ from phase a")
        for fixture in SERVE_FIXTURES:
            rep = _client(sock, fixture,
                          ["-t", "2", "--tpu-lanes", str(CORPUS_LANES),
                           "--execution-timeout", str(CORPUS_TIMEOUT)])
            got[fixture] = _canon_of(rep)
            _check(got[fixture] == expect_corpus[fixture],
                   f"server's {fixture} issues differ from phase b")
        server.send_signal(signal.SIGTERM)
        rc = server.wait(timeout=120)
        line["wall_s"] = time.perf_counter() - t0
        line["server_rc"] = rc
        _check(rc in (0, -signal.SIGTERM), f"server exit {rc}")
        _check(not Path(sock).exists(), "server left its socket behind")
        queue = json.loads((out / "daemon_queue.json").read_text())
        _check(not queue["pending"] and not queue["interrupted"],
               f"server left work queued: {queue}")
        rows = [json.loads(p.read_text())
                for p in (out / "requests").glob("*.json")]
        _check(len(rows) == 3, f"{len(rows)} done rows, not 3")
        windows = sum(r["counters"].get("lane_windows", 0) for r in rows)
        errors = {k: sum(r["counters"].get(k, 0) for r in rows)
                  for k in ERROR_COUNTERS}
        line.update(requests=len(rows), lane_windows=windows,
                    device_errors=errors)
        try:
            line.update(json.loads((out / "compile.json").read_text()))
        except OSError:
            line["compile_s"] = 0.0
        _check(windows > 0, "server ran no device window")
        _check(not any(errors.values()),
               f"device errors recovered on the host: {errors}")
        line["ok"] = True
    except (SmokeFailure, subprocess.TimeoutExpired, OSError) as e:
        line.update(ok=False, error=f"{type(e).__name__}: {e}")
    finally:
        watchdog.cancel()
        if server.poll() is None:
            server.kill()
            server.wait()
        shutil.rmtree(out, ignore_errors=True)
    return line


def _canon_of(report: dict) -> str:
    from tests.compare_lane_host import canon

    return hashlib.sha256(json.dumps(
        canon(report), sort_keys=True).encode()).hexdigest()


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip lane-mesh phase")
    ap.add_argument("--phase", choices=sorted(PHASES), help=argparse.SUPPRESS)
    ap.add_argument("--server", metavar="DIR", help=argparse.SUPPRESS)
    cli = ap.parse_args()
    sys.path.insert(0, str(HERE))
    if cli.phase:
        return run_phase(cli.phase)
    if cli.server:
        return server_child(cli.server)
    if not (HERE / "mythril_tpu").is_dir():
        print(f"chip_smoke.py: no mythril_tpu package beside {__file__}; "
              "run it from a checkout of the repository", file=sys.stderr)
        return 2
    lines = []
    names = (["four-chips"] if cli.four_chips
             else ["cli", "corpus", "state", "serve"])
    for name in names:
        if name == "serve":
            line = phase_serve(lines[0]["canon"], lines[1]["canon"])
        else:
            line = _child(["--phase", name])
        if not line.get("ok"):
            # a failed phase prints no result: its line goes to stderr
            print(json.dumps(line), file=sys.stderr)
            print(f"chip_smoke.py: phase {name} failed: {line.get('error')}",
                  file=sys.stderr)
            return 1
        print(json.dumps(line), flush=True)
        lines.append(line)
    device = lines[0]["device"]
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
