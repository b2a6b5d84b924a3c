"""The per-layer readers of the host interpreter's, the entry layer's,
the sweep's and the drain's spans and of the host steps the host loop's
spans carry, on a synthetic record and telemetry ring: what each reads,
and that each reports nothing where the program records nothing (a
program without these spans)."""

import pytest

from benchmarks import run
from benchmarks.self_time import overlap, self_time


def _read(name, record):
    return run.metric_reader(name, run.ROOT)(record)


def _record(spans, completed=4, window_s=10.0):
    return {"spans": spans, "window_s": window_s, "completed": completed,
            "counters": {"windows": 0, "solver_queries": 0}}


def test_overlap_and_self_time():
    outer = [(0.0, 4.0), (3.0, 6.0), (8.0, 9.0)]
    inner = [(1.0, 2.0), (5.0, 8.5), (10.0, 11.0)]
    # outer covers [0, 6) and [8, 9); inner covers 1 + 1 + 0.5 of it
    assert overlap(outer, inner) == pytest.approx(2.5)
    assert self_time(outer, inner) == pytest.approx(7.0 - 2.5)
    assert self_time(outer, []) == pytest.approx(7.0)


def test_host_share_is_host_exec_less_the_solver():
    spans = {"svm.host_exec": [(0.0, 4.0), (6.0, 8.0)],
             "solver.check": [(1.0, 2.0), (1.5, 2.5)],
             "solver.discharge": [(7.0, 9.0)]}
    # 6 s of host loop, of which the solver holds 1.5 + 1
    assert _read("host_share.corpus", _record(spans)) == pytest.approx(
        100.0 * 3.5 / 10.0)


def test_entry_share_is_contract_less_sym_exec():
    spans = {"analysis.contract": [(0.0, 3.0), (3.0, 5.0)],
             "svm.sym_exec": [(0.5, 2.5), (3.2, 4.0)]}
    assert _read("entry_share.corpus", _record(spans)) == pytest.approx(
        100.0 * (5.0 - 2.8) / 10.0)


def test_sweep_host_share_is_the_union_of_prep_and_retire():
    spans = {"svm.sweep_prep": [(0.0, 1.0), (4.0, 4.5)],
             "svm.sweep_retire": [(0.5, 2.0)]}
    assert _read("sweep_host_share.storm", _record(spans)) == (
        pytest.approx(100.0 * 2.5 / 10.0))


def test_drain_share_is_the_union_of_drains():
    spans = {"lane.drain": [(1.0, 2.0), (1.5, 3.0), (5.0, 5.5)]}
    assert _read("drain_share.storm", _record(spans)) == pytest.approx(
        100.0 * 2.5 / 10.0)


@pytest.fixture
def ring():
    """The program's telemetry ring, empty and on for one test."""
    from mythril_tpu.support.telemetry import trace

    was = trace.enabled()
    trace.clear()
    trace.set_enabled(True)
    yield trace
    trace.set_enabled(was)
    trace.clear()


def test_host_steps_per_analysis(ring):
    for steps in (600, 0, 400):
        ring.begin("svm.host_exec")
        ring.end("svm.host_exec", steps=steps)
    # another span's end, and a host_exec end without steps, count nothing
    ring.begin("svm.sym_exec")
    ring.end("svm.sym_exec", steps=7)
    ring.begin("svm.host_exec")
    ring.end("svm.host_exec")
    assert _read("host_steps.corpus", _record({}, completed=4)) == 250.0
    assert _read("host_steps.corpus", _record({}, completed=0)) is None


@pytest.mark.parametrize("name", [
    "host_share.corpus", "host_steps.corpus", "entry_share.corpus",
    "sweep_host_share.storm", "drain_share.storm"])
def test_nothing_recorded_reads_nothing(name, ring):
    # a program without the new spans: the ring holds only older ones
    ring.begin("svm.round")
    ring.end("svm.round")
    assert _read(name, _record({"solver.check": [(0.0, 1.0)]})) is None
