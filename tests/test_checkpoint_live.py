"""Window-boundary lane-plane checkpointing (support/checkpoint.py v4,
docs/checkpoint.md): live in-flight state export/import.

Covers the tentpole's contract surface:

* checkpoint roundtrip property (randomized contracts): a mid-round
  worklist slice exported into a v4 checkpoint and resumed in a fresh
  analyzer yields, together with the interrupted run, exactly the
  uninterrupted run's issue set — and the roundtripped states are
  bit-identical at the host level (same hash-consed constraint tids,
  same stack, same pc);
* lane-path export: the engine's window-boundary export seam ships
  live device lanes through the same format with the same identity
  guarantee;
* SIGTERM mid-round in a subprocess: the flight-recorder hook dumps a
  resumable live checkpoint; the restarted run completes with the
  uninterrupted issue set;
* version-skew rejection: an old-format snapshot is skipped (fresh
  run), never crashed on; corrupt files likewise;
* MTPU_CKPT=0: the live seams stand down.
"""

import io
import json
import os
import pickle
import random
import signal
import subprocess
import sys
import tempfile
import textwrap
import time
from pathlib import Path

import pytest

from mythril_tpu.orchestration.mythril_analyzer import (
    MythrilAnalyzer,
    reset_analysis_state,
)
from mythril_tpu.orchestration.mythril_disassembler import (
    MythrilDisassembler,
)
from mythril_tpu.smt.solver.solver_statistics import SolverStatistics
from mythril_tpu.support import checkpoint as ckpt
from mythril_tpu.support.analysis_args import make_cmd_args
from mythril_tpu.support.opcodes import ADDRESS, OPCODES

OP = {name: data[ADDRESS] for name, data in OPCODES.items()}


def _push(v, n=1):
    return bytes([0x5F + n]) + v.to_bytes(n, "big")


def _fork_tree_code(k=4, rng=None):
    """k sequential symbolic branches with SSTOREs and an assert-style
    INVALID tail — forks wide, stores state, and mints a reachable
    issue (the exceptions module flags the INVALID)."""
    rng = rng or random.Random(0)
    c = bytearray(_push(0))
    for i in range(k):
        c += _push(i) + bytes([OP["CALLDATALOAD"]])
        c += _push(1) + bytes([OP["AND"], OP["ISZERO"]])
        j = len(c)
        c += _push(0, 2) + bytes([OP["JUMPI"]])
        c += _push(rng.randrange(1, 200)) + bytes([OP["ADD"],
                                                   OP["DUP1"]])
        c += _push(i) + bytes([OP["SSTORE"]])
        c[j + 1:j + 3] = len(c).to_bytes(2, "big")
        c += bytes([OP["JUMPDEST"]])
    c += bytes([OP["POP"]])
    c += _push(31) + bytes([OP["CALLDATALOAD"]])
    c += _push(0xDEADBEEF, 4) + bytes([OP["EQ"]])
    j = len(c)
    c += _push(0, 2) + bytes([OP["JUMPI"]])
    c += bytes([OP["STOP"]])
    c[j + 1:j + 3] = len(c).to_bytes(2, "big")
    c += bytes([OP["JUMPDEST"], 0xFE])
    return bytes(c)


def _issues(report):
    return sorted((i.swc_id, i.address, i.title)
                  for i in report.issues.values())


def _analyze(code_hex, tx_count=2, checkpoint=None, tpu_lanes=0,
             on_state=None, bus=None):
    """One full analysis; `on_state` monkeypatches execute_state (for
    mid-round captures)."""
    from mythril_tpu.laser import svm as svm_mod

    reset_analysis_state()
    dis = MythrilDisassembler(eth=None)
    address, _ = dis.load_from_bytecode(code_hex, bin_runtime=True)
    analyzer = MythrilAnalyzer(
        disassembler=dis,
        cmd_args=make_cmd_args(execution_timeout=300,
                               checkpoint=checkpoint,
                               tpu_lanes=tpu_lanes,
                               migration_bus=bus),
        strategy="bfs", address=address)
    orig = svm_mod.LaserEVM.execute_state
    if on_state is not None:
        count = [0]

        def patched(self, gs):
            count[0] += 1
            on_state(self, count[0])
            return orig(self, gs)

        svm_mod.LaserEVM.execute_state = patched
    try:
        report = analyzer.fire_lasers(modules=None,
                                      transaction_count=tx_count)
    finally:
        svm_mod.LaserEVM.execute_state = orig
    return report, dis.contracts[-1]


class TestFormat:
    def test_version_skew_rejected(self, tmp_path):
        """An old-format snapshot is SKIPPED (fresh run), not crashed
        on — mixed-build fleets mid-deploy stay safe."""
        path = tmp_path / "old.ckpt"
        with open(path, "wb") as f:
            pickle.dump({"version": ckpt.VERSION - 1,
                         "code_id": "c" * 64, "terms": []}, f)
            f.write(b"\x80\x04N.")  # a pickled None body
        assert ckpt.load_checkpoint(str(path), "c" * 64) is None

    def test_corrupt_rejected(self, tmp_path):
        path = tmp_path / "bad.ckpt"
        path.write_bytes(b"not a pickle at all")
        assert ckpt.load_checkpoint(str(path), "x") is None

    def test_missing_is_none(self, tmp_path):
        assert ckpt.load_checkpoint(str(tmp_path / "nope"), "x") is None

    def test_detection_module_persistent_id(self):
        """A pickled reference to a detection module resolves to the
        loading process's own singleton — never a deep copy."""
        from mythril_tpu.analysis.module.loader import ModuleLoader

        module = ModuleLoader().get_detection_modules()[0]
        buf = io.BytesIO()
        ckpt.dump_with_terms(buf, {"detector": module})
        buf.seek(0)
        back = ckpt.load_with_terms(buf)
        assert back["detector"] is module

    def test_live_enabled_gate(self, monkeypatch):
        monkeypatch.delenv("MTPU_CKPT", raising=False)
        assert ckpt.live_enabled()
        monkeypatch.setenv("MTPU_CKPT", "0")
        assert not ckpt.live_enabled()


class TestHostRoundtrip:
    def _run_split(self, code, tx_count, tmp_path, capture_at=60):
        """Baseline run; a run that exports half its mid-round
        worklist into a checkpoint; a resume run over that checkpoint.
        Returns (baseline issues, union of split-run issues)."""
        code_hex = code.hex()
        baseline, _ = _analyze(code_hex, tx_count)
        base_issues = _issues(baseline)

        path = str(tmp_path / "batch.ckpt")
        captured = {}

        def exporter(laser, n):
            if captured.get("n") or n < capture_at \
                    or len(laser.work_list) < 4:
                return
            ctx = laser._ckpt_round_ctx
            if ctx is None:
                return
            next_round, _txc, address = ctx
            half = len(laser.work_list) // 2
            chunk = laser.work_list[len(laser.work_list) - half:]
            ok = ckpt.save_checkpoint(
                path, next_round, [], address.value, captured["cid"],
                include_modules=False, inflight=chunk)
            assert ok
            del laser.work_list[len(laser.work_list) - half:]
            captured["n"] = len(chunk)

        # probe the code identity first (the exporter needs it)
        dis = MythrilDisassembler(eth=None)
        dis.load_from_bytecode(code_hex, bin_runtime=True)
        captured["cid"] = ckpt.code_identity(dis.contracts[-1])

        interrupted, _ = _analyze(code_hex, tx_count,
                                  on_state=exporter)
        assert "n" in captured, "rig never reached the capture point"
        part_a = _issues(interrupted)

        ss = SolverStatistics()
        imported0 = ss.lanes_imported
        resumed_rounds0 = ss.resume_rounds
        resumed, _ = _analyze(code_hex, tx_count, checkpoint=path)
        part_b = _issues(resumed)
        assert ss.lanes_imported - imported0 == captured["n"]
        assert ss.resume_rounds - resumed_rounds0 == 1
        return base_issues, sorted(set(part_a) | set(part_b))

    def test_inflight_split_identity(self, tmp_path):
        code = _fork_tree_code(k=4)
        base, union = self._run_split(code, 2, tmp_path)
        assert base, "rig must produce issues"
        assert union == base

    def test_inflight_split_identity_randomized(self, tmp_path):
        rng = random.Random(0xBEEF)
        for trial in range(3):
            code = _fork_tree_code(k=rng.randrange(3, 5), rng=rng)
            trial_dir = tmp_path / f"t{trial}"
            trial_dir.mkdir()
            base, union = self._run_split(
                code, 2, trial_dir,
                capture_at=rng.choice((40, 70, 100)))
            assert union == base, f"trial {trial} diverged"

    def test_roundtrip_is_bit_identical(self):
        """dump/load of a mid-path state re-interns to the SAME
        hash-consed terms (equal tids), same stack, same pc — the
        host-level 'bit-identical lane plane' guarantee."""
        code_hex = _fork_tree_code(k=3).hex()
        box = {}

        def capture(laser, n):
            if "state" not in box and n == 40 and laser.work_list:
                box["state"] = laser.work_list[-1]
                buf = io.BytesIO()
                ckpt.dump_with_terms(buf, [box["state"]])
                box["bytes"] = buf.getvalue()

        _analyze(code_hex, 2, on_state=capture)
        assert "bytes" in box
        back = ckpt.load_with_terms(io.BytesIO(box["bytes"]))[0]
        orig = box["state"]
        assert back.mstate.pc == orig.mstate.pc
        assert [c.raw.tid for c in back.world_state.constraints] == \
            [c.raw.tid for c in orig.world_state.constraints]
        assert len(back.mstate.stack) == len(orig.mstate.stack)
        for a, b in zip(back.mstate.stack, orig.mstate.stack):
            ra = getattr(a, "raw", a)
            rb = getattr(b, "raw", b)
            assert getattr(ra, "tid", ra) == getattr(rb, "tid", rb)


class TestLaneExport:
    def test_window_boundary_export_import_identity(self, tmp_path):
        """The engine's window-boundary export seam: live device lanes
        ship mid-flight as a v4 inflight batch; the interrupted run
        plus the resumed run reproduce the uninterrupted issue set."""
        pytest.importorskip("jax")
        from mythril_tpu.laser import lane_engine

        code = _fork_tree_code(k=5)
        code_hex = code.hex()
        path = str(tmp_path / "lanes.ckpt")

        lane_engine.PATH_HISTORY[code] = 64
        lane_engine.FORCE_WIDTH = 64
        old_window = lane_engine.DEFAULT_WINDOW
        lane_engine.DEFAULT_WINDOW = 32
        try:
            lane_engine.warm_variant(64, len(code), {}, 32, 8192,
                                     seed_bucket=16)
            baseline, _ = _analyze(code_hex, 1, tpu_lanes=64)
            base_issues = _issues(baseline)

            dis = MythrilDisassembler(eth=None)
            dis.load_from_bytecode(code_hex, bin_runtime=True)
            cid = ckpt.code_identity(dis.contracts[-1])

            class Client:
                def __init__(self):
                    self.shipped = 0

                def want(self, live):
                    return live // 2 if not self.shipped else 0

                def deliver(self, states):
                    ok = ckpt.save_checkpoint(
                        path, 1, [], 0, cid,
                        include_modules=False, inflight=states)
                    if ok:
                        self.shipped += len(states)
                    return ok

            client = Client()

            class Bus:
                yield_every = 1 << 30

                def lane_export_client(self):
                    return client

                def begin_round(self, *a):
                    pass

                def on_round_end(self, *a):
                    pass

                def midround_yield(self, laser):
                    pass

            interrupted, _ = _analyze(code_hex, 1, tpu_lanes=64,
                                      bus=Bus())
            assert client.shipped > 0, \
                "export seam never fired at a window boundary"
            part_a = _issues(interrupted)

            resumed, _ = _analyze(code_hex, 1, checkpoint=path)
            part_b = _issues(resumed)
        finally:
            lane_engine.FORCE_WIDTH = None
            lane_engine.DEFAULT_WINDOW = old_window

        assert base_issues, "rig must produce issues"
        assert sorted(set(part_a) | set(part_b)) == base_issues


_SIGTERM_SCRIPT = textwrap.dedent("""\
    import json, os, sys
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    sys.path.insert(0, {repo!r})
    from mythril_tpu.orchestration.mythril_analyzer import (
        MythrilAnalyzer, reset_analysis_state)
    from mythril_tpu.orchestration.mythril_disassembler import (
        MythrilDisassembler)
    from mythril_tpu.support.analysis_args import make_cmd_args
    from mythril_tpu.support import telemetry

    out_dir, code_hex = sys.argv[1], sys.argv[2]
    telemetry.configure(out_dir=out_dir, rank=0)
    reset_analysis_state()
    dis = MythrilDisassembler(eth=None)
    address, _ = dis.load_from_bytecode(code_hex, bin_runtime=True)
    analyzer = MythrilAnalyzer(
        disassembler=dis,
        cmd_args=make_cmd_args(
            execution_timeout=300,
            checkpoint=os.path.join(out_dir, "run.ckpt")),
        strategy="bfs", address=address)
    print("READY", flush=True)
    report = analyzer.fire_lasers(modules=None, transaction_count=2)
    print("ISSUES " + json.dumps(sorted(
        (i.swc_id, i.address, i.title)
        for i in report.issues.values())), flush=True)
""")


class TestSigtermResume:
    def test_sigterm_mid_round_then_resume(self, tmp_path):
        """SIGTERM mid-round: the flight-recorder hook dumps a LIVE
        checkpoint (open + in-flight states); the restarted process
        resumes from it and finishes with the uninterrupted run's
        issue set."""
        repo = str(Path(__file__).resolve().parent.parent)
        code = _fork_tree_code(k=4)
        code_hex = code.hex()
        out_dir = str(tmp_path)
        script = tmp_path / "run_under_sigterm.py"
        script.write_text(_SIGTERM_SCRIPT.format(repo=repo))

        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env["MTPU_PATH_DELAY"] = "0.25"  # ~8 s round: the kill lands
        #                                  mid-round deterministically
        proc = subprocess.Popen(
            [sys.executable, str(script), out_dir, code_hex],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
            text=True)
        assert proc.stdout.readline().strip() == "READY"
        # the first round's checkpoint marks the second round's start:
        # the kill lands inside that delayed round however slowly the
        # process started (a fixed sleep raced slow starts under load)
        ckpt = Path(out_dir) / "run.ckpt"
        deadline = time.monotonic() + 120
        while (not ckpt.exists() and proc.poll() is None
               and time.monotonic() < deadline):
            time.sleep(0.05)
        time.sleep(0.5)
        proc.send_signal(signal.SIGTERM)
        proc.communicate(timeout=120)
        assert proc.returncode != 0  # died of SIGTERM, not completion

        resume = Path(out_dir) / "flightrec" / "resume_rank0.ckpt"
        assert resume.exists(), "SIGTERM hook wrote no live checkpoint"
        # the live dump also refreshed the analysis's own checkpoint
        assert (Path(out_dir) / "run.ckpt").exists()

        env["MTPU_PATH_DELAY"] = "0"
        out, err = subprocess.Popen(
            [sys.executable, str(script), out_dir, code_hex],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
            text=True).communicate(timeout=300)
        lines = [l for l in out.splitlines() if l.startswith("ISSUES ")]
        assert lines, f"resume run produced no issues line:\n{err[-2000:]}"
        resumed_issues = json.loads(lines[-1][len("ISSUES "):])

        baseline, _ = _analyze(code_hex, 2)
        assert [list(t) for t in _issues(baseline)] == \
            sorted(resumed_issues)


class TestGateOff:
    def test_midflight_yield_stands_down(self, tmp_path, monkeypatch):
        from types import SimpleNamespace

        from mythril_tpu.parallel.migrate import MigrationBus

        monkeypatch.setenv("MTPU_CKPT", "0")
        bus = MigrationBus(str(tmp_path), 0, 2)
        bus.current_contract = "x"
        bus._round = (1, 2, 0)
        laser = SimpleNamespace(work_list=list(range(64)),
                                open_states=[])
        assert bus.midflight_yield(laser) == 0
        assert len(laser.work_list) == 64
        assert bus.lane_export_client() is None

    def test_midflight_requires_thief(self, tmp_path, monkeypatch):
        from types import SimpleNamespace

        from mythril_tpu.parallel.migrate import MigrationBus

        monkeypatch.delenv("MTPU_CKPT", raising=False)
        bus = MigrationBus(str(tmp_path), 0, 2)
        bus.current_contract = "x"
        bus._round = (1, 2, 0)
        laser = SimpleNamespace(work_list=list(range(64)),
                                open_states=[])
        # no request files on the bus dir: nothing exports
        assert bus.midflight_yield(laser) == 0
        assert len(laser.work_list) == 64


class TestResumeCli:
    def test_resume_dir_prefers_newest_flightrec_dump(self, tmp_path):
        from mythril_tpu.orchestration.mythril_analyzer import (
            _resume_checkpoint_path,
        )

        fr = tmp_path / "flightrec"
        fr.mkdir()
        older = fr / "resume_rank1.ckpt"
        newer = fr / "resume_rank0.ckpt"
        older.write_bytes(b"old")
        newer.write_bytes(b"new")
        past = time.time() - 600
        os.utime(older, (past, past))
        assert _resume_checkpoint_path(str(tmp_path)) == str(newer)

    def test_resume_dir_falls_back_to_resume_ckpt(self, tmp_path):
        from mythril_tpu.orchestration.mythril_analyzer import (
            _resume_checkpoint_path,
        )

        assert _resume_checkpoint_path(str(tmp_path)) == str(
            tmp_path / "resume.ckpt")
