"""The garbage-collection schedule across analyses
(support/gc_schedule.py): the heap freezes only after a compile (or at
the first analysis), the generation-2 threshold scales to the frozen
heap, every full collection counts with tracing off, and a lane-on
storm finds the same paths with the schedule as without it while the
young heap stays bounded.

Each test puts the collector back as it found it: its thresholds, and
nothing frozen that the test froze."""

import gc

import pytest

import bench
from mythril_tpu.smt.solver.solver_statistics import SolverStatistics
from mythril_tpu.support import gc_schedule
from mythril_tpu.support.telemetry import trace


@pytest.fixture
def collector(monkeypatch):
    threshold = gc.get_threshold()
    frozen = gc.get_freeze_count()
    # a heap that has not grown since the last freeze, JAX heard
    gc_schedule._listen()
    monkeypatch.setattr(gc_schedule, "_grown", False)
    yield
    if gc.get_freeze_count() > frozen:
        gc.unfreeze()
    gc.set_threshold(*threshold)


def _compile_event():
    import jax

    jax.monitoring.record_event_duration_secs(gc_schedule.COMPILE_EVENT,
                                              0.01)


def _jit_compile():
    import jax
    import jax.numpy as jnp

    # a new function object: a trace and a backend compile of its own
    jax.jit(lambda v: v * 3 + 1)(jnp.arange(5))


def test_no_freeze_without_a_compile(collector):
    stats = SolverStatistics()
    freezes, frozen = stats.gc_freezes, gc.get_freeze_count()
    threshold = gc.get_threshold()
    for _ in range(3):
        assert gc_schedule.before_analysis() is False
    assert stats.gc_freezes == freezes
    assert gc.get_freeze_count() == frozen
    assert gc.get_threshold() == threshold


@pytest.mark.parametrize("compile_", [_compile_event, _jit_compile],
                         ids=["event", "jit"])
def test_one_freeze_after_a_compile(collector, compile_):
    stats = SolverStatistics()
    freezes = stats.gc_freezes
    compile_()
    assert gc_schedule.before_analysis() is True
    assert gc_schedule.before_analysis() is False
    assert stats.gc_freezes == freezes + 1
    # what froze; a frozen object that dies by refcount leaves the count
    assert stats.gc_frozen >= gc.get_freeze_count() > 0


def test_threshold2_scales_to_the_frozen_heap(collector):
    gc.set_threshold(700, 10, 10)
    gc_schedule._grown = True
    assert gc_schedule.before_analysis() is True
    frozen = gc.get_freeze_count()
    assert gc.get_threshold() == (700, 10, max(10, frozen // 7000))


@pytest.mark.parametrize("frozen,want", [
    (0, 10), (69_999, 10), (790_000, 112), (7_000_000, 1000)])
def test_scaled_threshold2(collector, frozen, want):
    gc.set_threshold(700, 10, 10)
    assert gc_schedule.scaled_threshold2(frozen) == want


def test_gc_full_counts_full_collections_with_tracing_off():
    was = trace.enabled()
    trace.set_enabled(False)
    try:
        assert trace._gc_span not in gc.callbacks
        stats = SolverStatistics()
        full = stats.gc_full
        gc.collect(0)
        gc.collect(1)
        assert stats.gc_full == full
        gc.collect()
        gc.collect()
        assert stats.gc_full == full + 2
    finally:
        trace.set_enabled(was)


def _paths(open_states) -> list:
    """Each end state's concrete storage writes."""
    out = []
    for ws in open_states:
        storage = ws.accounts[0xDEADBEEF].storage.printable_storage
        out.append(frozenset((k.value, v.value)
                             for k, v in storage.items()))
    return out


def _storm(code, lanes=64):
    """A lane-on exploration of the storm contract, no detectors."""
    from mythril_tpu.analysis.symbolic import SymExecWrapper
    from mythril_tpu.ethereum.evmcontract import EVMContract
    from mythril_tpu.orchestration.mythril_analyzer import (
        reset_analysis_state,
    )
    from mythril_tpu.support.support_args import args

    reset_analysis_state()
    args.tpu_lanes = lanes
    try:
        return SymExecWrapper(
            EVMContract(code=code.hex(), name="gc_storm"),
            address=0xDEADBEEF, strategy="bfs", max_depth=8192,
            execution_timeout=600, create_timeout=10,
            transaction_count=1, compulsory_statespace=False,
            run_analysis_modules=False)
    finally:
        args.tpu_lanes = 0


def test_storm_paths_and_young_heap(collector, monkeypatch):
    code, n_paths = bench.build_symbolic_contract(k=6)
    with monkeypatch.context() as m:
        m.setattr(gc_schedule, "before_analysis", lambda: False)
        without = sorted(map(sorted, _paths(
            _storm(code).laser.open_states)))
    assert len(without) == len({tuple(p) for p in without}) == n_paths

    stats = SolverStatistics()
    freezes = stats.gc_freezes
    gc_schedule._grown = True
    for _ in range(10):
        sym = _storm(code)
        assert sorted(map(sorted, _paths(sym.laser.open_states))) == without
        del sym
    # a freeze at the first analysis, none once nothing compiles
    assert stats.gc_freezes - freezes >= 1
    assert gc_schedule.before_analysis() is False
    frozen = gc.get_freeze_count()
    young = len(gc.get_objects())
    sym = _storm(code)
    one = len(gc.get_objects()) - young
    del sym
    assert one > 0
    assert young < frozen + one
