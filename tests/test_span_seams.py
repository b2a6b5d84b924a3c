"""The program's spans at the seams the benchmark reads
(docs/observability.md): a lane-on analysis through the analyzer
records the entry, host-interpreter, sweep and drain spans, the host
loop's spans never overlap a device explore on the same thread, and
SolverStatistics.host_steps counts exactly the instructions the host
interpreter executed, which the host loop's spans also carry."""

from types import SimpleNamespace

import pytest

from mythril_tpu.laser import svm
from mythril_tpu.orchestration.mythril_analyzer import MythrilAnalyzer
from mythril_tpu.orchestration.mythril_disassembler import (
    MythrilDisassembler,
)
from mythril_tpu.smt.solver.solver_statistics import SolverStatistics
from mythril_tpu.support.support_args import args as global_args
from mythril_tpu.support.telemetry import trace

from .fixture_paths import INPUTS

#: small runtime code whose one path the lanes run to SELFDESTRUCT
FIXTURE, MODULE = "suicide.sol.o", "AccidentallyKillable"


def _analyze(tpu_lanes: int):
    disassembler = MythrilDisassembler(eth=None)
    code = (INPUTS / FIXTURE).read_text().strip()
    address, _ = disassembler.load_from_bytecode(code, bin_runtime=True)
    cmd_args = SimpleNamespace(
        execution_timeout=300, max_depth=128, solver_timeout=60000,
        no_onchain_data=True, loop_bound=3, create_timeout=10,
        pruning_factor=None, unconstrained_storage=False,
        parallel_solving=False, call_depth_limit=3,
        disable_dependency_pruning=False, custom_modules_directory="",
        solver_log=None, transaction_sequences=None, tpu_lanes=tpu_lanes)
    analyzer = MythrilAnalyzer(disassembler=disassembler,
                               cmd_args=cmd_args, strategy="bfs",
                               address=address)
    return analyzer.fire_lasers(modules=[MODULE], transaction_count=1)


@pytest.fixture(autouse=True)
def _restore_args():
    """The analyzer mirrors its settings (tpu_lanes among them) into the
    process-wide Args; later tests in this process must not inherit
    them."""
    saved = dict(vars(global_args))
    yield
    vars(global_args).clear()
    vars(global_args).update(saved)


@pytest.fixture
def traced():
    was = trace.enabled()
    trace.clear()
    trace.set_enabled(True)
    yield
    trace.set_enabled(was)
    trace.clear()


@pytest.fixture
def counted(monkeypatch):
    """Counts LaserEVM.execute_state calls."""
    calls = [0]
    real = svm.LaserEVM.execute_state

    def execute_state(self, global_state):
        calls[0] += 1
        return real(self, global_state)

    monkeypatch.setattr(svm.LaserEVM, "execute_state", execute_state)
    return calls


def _intervals() -> dict:
    """{(thread, name): [(start, end)]} of the ring's duration spans."""
    out, opened = {}, {}
    for phase, name, t0, dur, tid, _attrs in trace.snapshot_events():
        if phase == "X":
            out.setdefault((tid, name), []).append((t0, t0 + dur))
        elif phase == "B":
            opened.setdefault((tid, name), []).append(t0)
        elif phase == "E":
            start = opened[(tid, name)].pop()
            out.setdefault((tid, name), []).append((start, t0))
    assert not any(opened.values()), "a begin without its end"
    return out


def test_lane_analysis_records_the_seam_spans(traced):
    report = _analyze(64)
    assert len(report.issues) == 1
    spans = _intervals()
    names = {name for _tid, name in spans}
    assert {"analysis.contract", "svm.sym_exec", "svm.host_exec",
            "svm.sweep_prep", "svm.sweep_retire", "lane.drain",
            "lane.explore"} <= names
    for (tid, name), host in spans.items():
        if name != "svm.host_exec":
            continue
        for s, e in host:
            for s2, e2 in spans.get((tid, "lane.explore"), []):
                assert e <= s2 or e2 <= s, (
                    "svm.host_exec overlaps lane.explore")


def test_host_steps_count_host_instructions(counted):
    stats = SolverStatistics()
    before = stats.host_steps
    host = _analyze(0)
    host_steps = stats.host_steps - before
    assert host_steps == counted[0] > 0
    counted[0] = 0
    before = stats.host_steps
    lanes = _analyze(64)
    assert stats.host_steps - before == counted[0] < host_steps
    assert len(host.issues) == len(lanes.issues) == 1


def test_host_exec_spans_carry_the_host_steps(traced, counted):
    stats = SolverStatistics()
    before = stats.host_steps
    _analyze(64)
    carried = [attrs["steps"]
               for phase, name, _t, _d, _tid, attrs in trace.snapshot_events()
               if phase == "E" and name == "svm.host_exec"]
    assert sum(carried) == stats.host_steps - before == counted[0] > 0
