"""Run-wide observability subsystem (support/telemetry/,
docs/observability.md): span ring buffer (overflow + thread safety),
Chrome trace / JSONL export schema, off-switch really off, metrics
registry (types, merge, SolverStatistics shim parity), slow-query
log, crash flight recorder (in-process dump + induced fatal and
SIGTERM in subprocesses), and the monotonic staleness clock the
migration bus dead-thief timeout now runs on."""

import gc
import json
import os
import signal
import subprocess
import sys
import threading
import time
from pathlib import Path

import pytest

from mythril_tpu.support import telemetry
from mythril_tpu.support.telemetry import (
    flightrec, metrics, render, slowlog, trace,
)

REPO = Path(__file__).resolve().parent.parent


@pytest.fixture
def traced():
    """Enabled tracing with a fresh buffer; restores prior state. A full
    collection first: tracing records each one as a gc.collect span,
    and none may land among a test's exact event counts."""
    was = trace.enabled()
    gc.collect()
    trace.clear()
    trace.set_enabled(True)
    yield trace
    trace.set_enabled(was)
    trace.clear()


# -- span ring buffer ---------------------------------------------------


def test_ring_buffer_overflow_keeps_newest(traced):
    trace.configure(capacity=32)
    try:
        for i in range(100):
            with trace.span("ring.test", i=i):
                pass
        st = trace.stats()
        assert st["buffered"] == 32
        assert st["recorded"] == 100
        assert st["dropped"] == 68
        events = trace.snapshot_events()
        # ring semantics: the NEWEST spans survive
        kept = [e[5]["i"] for e in events]
        assert kept == list(range(68, 100))
    finally:
        trace.configure(capacity=trace._DEFAULT_CAP)


def test_span_thread_safety(traced):
    trace.configure(capacity=100000)
    errors = []

    def worker(tid):
        try:
            for i in range(500):
                with trace.span("mt.span", tid=tid, i=i):
                    pass
                if i % 50 == 0:
                    trace.snapshot_events()  # concurrent reader
        except Exception as e:  # pragma: no cover - the assertion
            errors.append(e)

    threads = [threading.Thread(target=worker, args=(t,))
               for t in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    try:
        assert not errors
        st = trace.stats()
        assert st["recorded"] == 8 * 500
        assert st["buffered"] == 8 * 500
        assert st["dropped"] == 0
    finally:
        trace.configure(capacity=trace._DEFAULT_CAP)


def test_off_switch_really_off():
    was = trace.enabled()
    trace.set_enabled(False)
    trace.clear()
    try:
        before = trace.stats()["recorded"]
        # every emission API must be a no-op while off
        s1 = trace.span("off.a", x=1)
        s2 = trace.span("off.b")
        assert s1 is s2  # the shared null span: no per-call allocation
        with s1:
            s1.set(y=2)
        trace.event("off.event", z=3)
        trace.begin("off.region")
        trace.end("off.region")

        def jfn(v):
            return v + 1

        assert trace.call_jit("off.jit", jfn, 41) == 42
        assert trace.stats()["recorded"] == before
        assert trace.snapshot_events() == []
    finally:
        trace.set_enabled(was)


def test_span_records_error_attribute(traced):
    with pytest.raises(ValueError):
        with trace.span("err.span"):
            raise ValueError("boom")
    (_ph, name, _t0, _dur, _tid, attrs) = trace.snapshot_events()[-1]
    assert name == "err.span"
    assert attrs["error"] == "ValueError"


def test_call_jit_marks_compiles(traced):
    class FakeJit:
        def __init__(self):
            self.cache = 0

        def _cache_size(self):
            return self.cache

        def __call__(self, grow):
            if grow:
                self.cache += 1
            return grow

    jfn = FakeJit()
    trace.call_jit("jit.kernel", jfn, True)   # cold: compile
    trace.call_jit("jit.kernel", jfn, False)  # warm: execute
    names = [e[1] for e in trace.snapshot_events()]
    assert names == ["xla.compile", "jit.kernel"]
    compile_attrs = trace.snapshot_events()[0][5]
    assert compile_attrs == {"kernel": "jit.kernel"}


def test_query_context_nesting():
    assert trace.current_query_context() == {}
    with trace.query_context(tier="outer", tactic="a"):
        with trace.query_context(tactic="b"):
            assert trace.current_query_context() == {
                "tier": "outer", "tactic": "b"}
        assert trace.current_query_context() == {
            "tier": "outer", "tactic": "a"}
    assert trace.current_query_context() == {}


# -- spans on the profiler's clock --------------------------------------


def _profiled(tmp_path, body):
    """Run body() under jax.profiler.trace; the trace's host events as
    {name: [duration in seconds]}."""
    import jax

    with jax.profiler.trace(str(tmp_path)):
        body()
    found = sorted(tmp_path.glob("plugins/profile/*/*.xplane.pb"))
    pd = jax.profiler.ProfileData.from_file(str(found[-1]))
    out = {}
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    out.setdefault(e.name, []).append(e.duration_ns / 1e9)
    return out


def _ring_durations() -> dict:
    out, opened = {}, {}
    for phase, name, t0, dur, tid, _attrs in trace.snapshot_events():
        if phase == "X":
            out.setdefault(name, []).append(dur)
        elif phase == "B":
            opened[(tid, name)] = t0
        elif phase == "E":
            out.setdefault(name, []).append(t0 - opened.pop((tid, name)))
    return out


def test_spans_are_native_profiler_events(tmp_path, traced):
    """With tracing on, a span, a begin/end pair and a call_jit call
    are host events of the same names in the profiler's trace, each as
    long as the ring's span; instant events stay in the ring."""

    def body():
        with trace.span("prof.span", k=1):
            time.sleep(0.02)
        trace.begin("prof.region")
        with trace.span("prof.inner"):
            time.sleep(0.01)
        trace.end("prof.region")
        trace.event("prof.instant")
        trace.call_jit("prof.kernel", lambda v: v + 1, 1)

    native = _profiled(tmp_path, body)
    ring = _ring_durations()
    for name in ("prof.span", "prof.region", "prof.inner", "prof.kernel"):
        assert len(native.get(name, [])) == 1, name
        assert native[name][0] == pytest.approx(ring[name][0], abs=1e-3)
    assert native["prof.region"][0] >= native["prof.inner"][0] > 0.009
    assert "prof.instant" not in native
    assert [e[1] for e in trace.snapshot_events()].count("prof.instant") == 1


def test_begin_end_pair_per_thread(tmp_path, traced):
    """begin/end pairs nest per thread: a region another thread opens
    under the same name closes on its own thread."""

    def other():
        trace.begin("pair.region")
        time.sleep(0.03)
        trace.end("pair.region")

    def body():
        t = threading.Thread(target=other)
        trace.begin("pair.region")
        t.start()
        time.sleep(0.005)
        trace.end("pair.region")
        t.join(timeout=10)
        assert not t.is_alive()

    native = _profiled(tmp_path, body)
    assert sorted(native["pair.region"]) == pytest.approx(
        sorted(_ring_durations()["pair.region"]), abs=1e-3)
    assert max(native["pair.region"]) > 0.025 > min(native["pair.region"])


def test_spans_off_reach_neither_ring_nor_trace(tmp_path):
    was = trace.enabled()
    trace.set_enabled(False)
    trace.clear()
    try:
        def body():
            with trace.span("off.span"):
                pass
            trace.begin("off.region")
            trace.end("off.region")
            trace.call_jit("off.kernel", lambda v: v, 1)

        native = _profiled(tmp_path, body)
        assert not [n for n in native if n.startswith("off.")]
        assert trace.snapshot_events() == []
    finally:
        trace.set_enabled(was)


def test_full_collections_are_spans(traced):
    """With tracing on, a full garbage collection is a gc.collect span
    in the ring; off, the gc callback is gone."""
    assert trace._gc_span in gc.callbacks
    gc.collect()
    spans = [e for e in trace.snapshot_events() if e[1] == "gc.collect"]
    assert len(spans) == 1
    phase, _name, _t0, dur, _tid, attrs = spans[0]
    assert phase == "X" and dur >= 0 and "collected" in attrs
    gc.collect(0)  # a young collection is no span
    assert [e[1] for e in trace.snapshot_events()].count("gc.collect") == 1
    trace.set_enabled(False)
    assert trace._gc_span not in gc.callbacks
    gc.collect()
    assert [e[1] for e in trace.snapshot_events()].count("gc.collect") == 1


def test_lane_engine_has_no_phase_clocks():
    from mythril_tpu.laser import lane_engine

    assert not hasattr(lane_engine, "PROF")
    assert not hasattr(lane_engine, "PROF_ON")


# -- Chrome trace / JSONL export ----------------------------------------


def test_chrome_trace_schema_roundtrip(tmp_path, traced):
    with trace.span("rt.window", lanes=4):
        with trace.span("rt.solver"):
            pass
    trace.event("rt.mark", k=1)
    trace.begin("rt.region", r=2)
    trace.end("rt.region")
    out = tmp_path / "trace.json"
    trace.export_chrome_trace(out, rank=3)
    payload = json.loads(out.read_text())
    te = payload["traceEvents"]
    assert payload["displayTimeUnit"] == "ms"
    assert isinstance(te, list) and te
    for e in te:
        assert {"ph", "name", "pid", "tid"} <= set(e)
        assert e["pid"] == 3
        if e["ph"] != "M":
            assert isinstance(e["ts"], (int, float))
        if e["ph"] == "X":
            assert "dur" in e
    # thread lane labels ride as metadata events
    meta = [e for e in te if e["ph"] == "M"]
    assert any(e["name"] == "thread_name"
               and e["args"]["name"] for e in meta)
    by_name = {e["name"]: e for e in te if e["ph"] != "M"}
    assert by_name["rt.window"]["args"] == {"lanes": 4}
    assert by_name["rt.mark"]["ph"] == "i"
    assert {"B", "E"} <= {e["ph"] for e in te}
    # nesting: the inner complete event falls inside the outer one
    outer, inner = by_name["rt.window"], by_name["rt.solver"]
    assert outer["ts"] <= inner["ts"]
    assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1


def test_jsonl_export(tmp_path, traced):
    for i in range(5):
        trace.event("jl.mark", i=i)
    out = tmp_path / "trace.jsonl"
    trace.export_jsonl(out, rank=1)
    lines = [json.loads(line)
             for line in out.read_text().splitlines()]
    assert len(lines) == 5
    assert all(rec["name"] == "jl.mark" and rec["rank"] == 1
               and "thread" in rec for rec in lines)
    assert [rec["attrs"]["i"] for rec in lines] == list(range(5))


# -- metrics registry ---------------------------------------------------


def test_metric_types():
    reg = metrics.Registry()
    reg.counter("c").inc()
    reg.counter("c").inc(4)
    reg.gauge("g").set(7.5)
    h = reg.histogram("h", buckets=(1, 10, 100))
    for v in (0.5, 5, 50, 500):
        h.observe(v)
    state = reg.export_state()
    assert state["counters"]["c"] == 5
    assert state["gauges"]["g"] == 7.5
    hd = state["histograms"]["h"]
    assert hd["counts"] == [1, 1, 1, 1]  # one per bucket + overflow
    assert hd["count"] == 4
    assert hd["max"] == 500
    assert hd["sum"] == pytest.approx(555.5)


def test_histogram_thread_safety():
    h = metrics.Histogram("mt", buckets=(10,))
    threads = [threading.Thread(
        target=lambda: [h.observe(1) for _ in range(1000)])
        for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert h.count == 4000
    assert h.counts[0] == 4000


def test_merge_states_aggregates():
    a = {"counters": {"x": 1}, "gauges": {"w": 2},
         "histograms": {"h": {"buckets": [1, 10], "counts": [1, 2, 0],
                              "sum": 5.0, "count": 3, "max": 4.0}}}
    b = {"counters": {"x": 2, "y": 7}, "gauges": {"w": 5},
         "histograms": {"h": {"buckets": [1, 10], "counts": [0, 1, 1],
                              "sum": 30.0, "count": 2, "max": 20.0}}}
    m = metrics.merge_states([a, b, None])
    assert m["counters"] == {"x": 3, "y": 7}
    assert m["gauges"] == {"w": 5}
    assert m["histograms"]["h"]["counts"] == [1, 3, 1]
    assert m["histograms"]["h"]["count"] == 5
    assert m["histograms"]["h"]["max"] == 20.0
    assert m["histograms"]["h"]["sum"] == pytest.approx(35.0)


def test_solver_statistics_shim_parity():
    """The registry's `solver` provider IS the legacy counter block:
    every batch_counters key appears with the identical live value,
    and a bump through the old API is visible in the next snapshot."""
    from mythril_tpu.smt.solver.solver_statistics import (
        SolverStatistics,
    )

    ss = SolverStatistics()
    snap = metrics.registry().snapshot()
    assert "solver" in snap, "provider not registered"
    counters = ss.batch_counters()
    for key, val in counters.items():
        assert snap["solver"][key] == val
    # old-API write, new-API read
    ss.bump(subset_kills=3)
    snap2 = metrics.registry().snapshot()
    assert snap2["solver"]["subset_kills"] == \
        counters["subset_kills"] + 3
    assert "query_count" in snap2["solver"]
    assert "solver_time_s" in snap2["solver"]


# -- slow-query log -----------------------------------------------------


def test_slow_query_log_writes_records(tmp_path, monkeypatch):
    old = slowlog.configured_path()
    monkeypatch.setenv("MTPU_SLOW_QUERY_MS", "10")
    slowlog.configure(out_dir=tmp_path)
    try:
        slowlog.maybe_record(5.0, tids=[1], tier="t", tactic="x")
        slowlog.maybe_record(50.0, tids=[1, 2], tier="batch.serial",
                             tactic="incremental", timeout_s=2,
                             status="sat")
        path = tmp_path / slowlog.FILENAME
        lines = [json.loads(line)
                 for line in path.read_text().splitlines()]
        assert len(lines) == 1  # under-threshold record skipped
        rec = lines[0]
        assert rec["wall_ms"] == 50.0
        assert rec["tids"] == [1, 2]
        assert rec["tier"] == "batch.serial"
        assert rec["tactic"] == "incremental"
        assert rec["status"] == "sat"
    finally:
        slowlog._CFG["path"] = old


def test_slow_query_log_through_core_check(tmp_path, monkeypatch):
    """End to end: a real core.check lands in the log with tier/
    tactic attribution, the per-tactic wall histogram grows, and the
    in-flight registry is empty afterwards."""
    from mythril_tpu.smt import terms as T
    from mythril_tpu.smt.solver import core

    old = slowlog.configured_path()
    monkeypatch.setenv("MTPU_SLOW_QUERY_MS", "0")
    slowlog.configure(out_dir=tmp_path)
    try:
        h0 = metrics.registry().histogram(
            "solver_wall_ms.incremental").count
        x = T.bv_var("telemetry_slow_x", 64)
        with trace.query_context(tier="test.tier"):
            ctx = core.check([T.mk_eq(x, T.bv_const(5, 64))],
                             timeout_s=5.0)
        assert ctx.status == core.SAT
        lines = [json.loads(line) for line in
                 (tmp_path / slowlog.FILENAME).read_text()
                 .splitlines()]
        assert lines, "slow-query log empty at threshold 0"
        assert lines[-1]["tier"] == "test.tier"
        assert lines[-1]["tactic"] == "incremental"
        assert lines[-1]["status"] == "sat"
        assert lines[-1]["tids"]
        assert metrics.registry().histogram(
            "solver_wall_ms.incremental").count > h0
        assert core.inflight_queries() == []
    finally:
        slowlog._CFG["path"] = old


# -- crash flight recorder ----------------------------------------------


def test_flightrec_dump_in_process(tmp_path, traced):
    from mythril_tpu.smt.solver.solver_statistics import (
        SolverStatistics,
    )

    SolverStatistics()  # ensure the `solver` provider is registered
    with trace.span("fr.span", n=1):
        pass
    flightrec.configure(out_dir=tmp_path, rank=2)
    try:
        dest = flightrec.dump("unit_test")
        assert dest == tmp_path / flightrec.DIRNAME
        crash = json.loads((dest / "crash_rank2.json").read_text())
        assert crash["reason"] == "unit_test"
        assert crash["rank"] == 2
        m = json.loads((dest / "metrics_rank2.json").read_text())
        assert "solver" in m  # the SolverStatistics provider block
        t = json.loads((dest / "trace_rank2.json").read_text())
        assert any(e.get("name") == "fr.span"
                   for e in t["traceEvents"])
        inflight = json.loads(
            (dest / "inflight_rank2.json").read_text())
        assert inflight == {"queries": []}
        assert (dest / "events_rank2.jsonl").exists()
    finally:
        flightrec._CFG["dir"] = None
        flightrec._CFG["rank"] = 0


def test_flightrec_unconfigured_is_noop():
    old = flightrec._CFG["dir"]
    flightrec._CFG["dir"] = None
    try:
        assert flightrec.dump("nothing") is None
    finally:
        flightrec._CFG["dir"] = old


def _run_subprocess(tmp_path, tail):
    prog = (
        "import sys; sys.path.insert(0, {root!r})\n"
        "from mythril_tpu.support import telemetry\n"
        "telemetry.configure(out_dir={out!r}, enable=True)\n"
        "with telemetry.trace.span('sub.span', n=1): pass\n"
        "{tail}\n"
    ).format(root=str(REPO), out=str(tmp_path), tail=tail)
    return subprocess.run([sys.executable, "-c", prog],
                          capture_output=True, text=True, timeout=120)


def test_flightrec_fires_on_fatal_in_subprocess(tmp_path):
    proc = _run_subprocess(
        tmp_path, "raise RuntimeError('injected fatal')")
    assert proc.returncode != 0
    fr = tmp_path / flightrec.DIRNAME
    crash = json.loads((fr / "crash_rank0.json").read_text())
    assert crash["reason"] == "fatal_exception"
    assert crash["exception"]["type"] == "RuntimeError"
    assert "injected fatal" in crash["exception"]["message"]
    t = json.loads((fr / "trace_rank0.json").read_text())
    assert any(e.get("name") == "sub.span" for e in t["traceEvents"])
    assert (fr / "metrics_rank0.json").exists()
    assert (fr / "inflight_rank0.json").exists()


def test_flightrec_fires_on_sigterm_in_subprocess(tmp_path):
    tail = ("import os, signal, time\n"
            "os.kill(os.getpid(), signal.SIGTERM)\n"
            "time.sleep(30)")
    proc = _run_subprocess(tmp_path, tail)
    # default disposition re-delivered: died OF SIGTERM, after dumping
    assert proc.returncode == -signal.SIGTERM
    fr = tmp_path / flightrec.DIRNAME
    crash = json.loads((fr / "crash_rank0.json").read_text())
    assert crash["reason"] == "SIGTERM"


# -- CLI wiring ---------------------------------------------------------


def test_configure_trace_out_and_flush(tmp_path):
    was = trace.enabled()
    old_state = dict(telemetry._ATEXIT)
    trace.clear()
    try:
        out = tmp_path / "run_trace.json"
        telemetry.configure(trace_out=out, rank=1)
        assert trace.enabled()  # trace_out implies spans on
        with trace.span("cfg.span"):
            pass
        telemetry.flush_trace()
        payload = json.loads(out.read_text())
        assert any(e.get("name") == "cfg.span"
                   for e in payload["traceEvents"])
        assert all(e["pid"] == 1 for e in payload["traceEvents"])
        # the JSONL twin rides along
        assert (tmp_path / "run_trace.jsonl").exists()
        # idempotent: a second flush does not rewrite
        out.unlink()
        telemetry.flush_trace()
        assert not out.exists()
    finally:
        telemetry._ATEXIT.update(old_state)
        trace.set_enabled(was)
        trace.clear()


# -- monotonic staleness clock (migration bus) --------------------------


def test_staleness_clock_monotonic_observation(tmp_path):
    from mythril_tpu.parallel.migrate import _StalenessClock

    clock = _StalenessClock()
    path = tmp_path / "claim"
    path.touch()
    assert clock.age(path) == 0.0  # first observation = fresh
    time.sleep(0.05)
    aged = clock.age(path)
    assert 0.0 < aged < 5.0
    # an mtime CHANGE (heartbeat) resets the observed age...
    os.utime(path, (time.time() + 100, time.time() + 100))
    assert clock.age(path) == 0.0
    # ...and a missing file is infinitely stale
    assert clock.age(tmp_path / "gone") == float("inf")
    # freshest-of semantics across several paths
    other = tmp_path / "meta"
    other.touch()
    assert clock.age(path, other) == 0.0


def test_pending_requests_survive_wall_clock_steps(tmp_path):
    """The dead-thief cutoff must key on OBSERVED change, not wall
    mtime arithmetic: a request file whose mtime sits far in the past
    (exactly what an NTP step forward produces) still counts as live
    on first observation, and ages out only after CLAIMED_WAIT_S of
    observed silence."""
    from mythril_tpu.parallel import migrate

    bus = migrate.MigrationBus(str(tmp_path), rank=0, num_ranks=2)
    req = bus.dir / "request_1"
    req.touch()
    # simulate an NTP step: the file's wall mtime is an hour ago
    past = time.time() - 3600
    os.utime(req, (past, past))
    assert bus._pending_requests(max_age=0.0) == [1]