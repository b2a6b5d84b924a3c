"""Lane merging / path subsumption (laser/merge.py, docs/lane_merge.md).

Covers the four properties the merge pass must preserve:

* OR-constraint SAT-equivalence per merge: the disjunction a merge
  builds is satisfiable iff some branch was (randomized over fork
  trees);
* subsumption soundness: a lane retired subsumed provably implies the
  surviving sibling (``B ∧ ¬A`` refutes), so no issue is lost;
* merged-run invariants end to end: issue set identical and final
  open-state count no higher than with ``MTPU_MERGE=0``, on both the
  host seam (svm round boundary) and, when jax is importable, the lane
  seam (window boundary) — randomized over diamond-CFG fork trees;
* witness re-concretization: a model for a merged constraint set pins
  exactly one original disjunct (support/model.witness_paths).
"""

import random

import pytest

from mythril_tpu.laser import merge
from mythril_tpu.smt import terms as T
from mythril_tpu.smt.bool import Bool
from mythril_tpu.smt.solver import core as solver_core


def _bv(v, w=64):
    return T.bv_const(v, w)


def _rand_cond(rng, syms):
    s = rng.choice(syms)
    e = (T.mk_and(s, _bv(rng.randrange(1, 1 << 10)))
         if rng.random() < 0.4 else
         T.mk_add(s, _bv(rng.randrange(1, 256))))
    k = rng.randrange(3)
    c = (T.mk_eq if k == 0 else T.mk_ult if k == 1 else T.mk_ule)(
        e, _bv(rng.randrange(0, 1 << 10)))
    if rng.random() < 0.4:
        c = T.mk_not(c)
    return Bool(c)


def _rand_fork_tree(rng, syms, depth):
    """Condition lists of every leaf of a random binary fork tree with
    a shared prefix — the shape sibling lanes carry at a rejoin."""
    prefix = [_rand_cond(rng, syms)
              for _ in range(rng.randrange(0, 3))]
    leaves = [list(prefix)]
    for _ in range(depth):
        nxt = []
        for leaf in leaves:
            if rng.random() < 0.5:
                c = _rand_cond(rng, syms)
                nxt.append(leaf + [c])
                nxt.append(leaf + [Bool(T.mk_not(c.raw))])
            else:
                nxt.append(leaf)
        leaves = nxt
    return leaves


def _sat(terms):
    ctx = solver_core.check(list(terms), timeout_s=20.0)
    assert ctx.status in (solver_core.SAT, solver_core.UNSAT)
    return ctx.status == solver_core.SAT


class TestPlanGroup:
    def test_duplicate_and_superset(self):
        c = Bool(T.bool_var("tlm_c"))
        nc = Bool(T.mk_not(c.raw))
        plan = merge.plan_group([[c], [c, c], [c, nc], [nc]])
        # [c, c] duplicates [c]; [c, nc] is a superset of [c] (implied
        # -> subsumed); [c] and [nc] OR-merge and or(c, not c) folds
        # TRUE, so the survivor carries no constraint at all
        assert plan.dropped == {1: "merged", 2: "subsumed", 3: "merged"}
        assert plan.new_conds == []

    def test_interval_subsumption_sound(self):
        x = T.bv_var("tlm_x", 256)
        tight = Bool(T.mk_ule(x, T.bv_const(50, 256)))
        loose = Bool(T.mk_ult(x, T.bv_const(101, 256)))
        plan = merge.plan_group([[loose], [tight]])
        assert plan.dropped == {1: "subsumed"}
        assert plan.new_conds is None
        # soundness witness: tight ∧ ¬loose must be UNSAT
        assert not _sat([tight.raw, T.mk_not(loose.raw)])

    def test_or_merge_sat_equivalence_randomized(self):
        """Merged-run disjunction is satisfiable iff some branch was,
        and every subsumption the planner decides is a real
        implication — over randomized fork trees."""
        rng = random.Random(0xC0FFEE)
        syms = [T.bv_var(f"tlm_r{i}", 64) for i in range(3)]
        checked_or = checked_sub = 0
        for round_i in range(40):
            leaves = _rand_fork_tree(rng, syms, rng.randrange(1, 4))
            if len(leaves) < 2:
                continue
            plan = merge.plan_group(leaves)
            if plan is None:
                continue
            for mi, reason in plan.dropped.items():
                if reason != "subsumed":
                    continue
                # the subsumed member must imply SOME surviving member
                # (region containment): B ∧ ¬(∧A) UNSAT for at least one
                survivors = [i for i in range(len(leaves))
                             if i not in plan.dropped] + [plan.keep]
                b = [c.raw for c in leaves[mi]]
                ok = False
                for si in survivors:
                    a_conj = T.mk_bool_and(
                        *[c.raw for c in leaves[si]]) \
                        if leaves[si] else T.bool_t(True)
                    if not _sat(b + [T.mk_not(a_conj)]):
                        ok = True
                        break
                assert ok, f"unsound subsumption in round {round_i}"
                checked_sub += 1
            if plan.new_conds is not None:
                merged_terms = [c.raw for c in plan.new_conds]
                branch_sat = any(
                    _sat([c.raw for c in leaves[i]] or
                         [T.bool_t(True)])
                    for i in range(len(leaves))
                    if i not in plan.dropped
                    or plan.dropped.get(i) == "merged")
                merged_sat = _sat(merged_terms or [T.bool_t(True)])
                assert merged_sat == branch_sat
                checked_or += 1
        assert checked_or > 0 and checked_sub > 0

    def test_provenance_on_or(self):
        x = T.bv_var("tlm_p", 256)
        a = Bool(T.mk_ule(x, T.bv_const(5, 256)))
        b = Bool(T.mk_ule(T.bv_const(1000, 256), x))
        plan = merge.plan_group([[a], [b]])
        assert plan.dropped == {1: "merged"}
        (orb,) = plan.new_conds
        provs = [p for p in orb.annotations
                 if isinstance(p, merge.MergeProvenance)]
        assert len(provs) == 1
        assert len(provs[0].disjuncts) == 2


class TestWitness:
    def test_witness_reconcretization(self):
        """A model for a merged constraint set pins exactly one
        original path (the disjunct whose terms all evaluate true)."""
        from mythril_tpu.laser.state.constraints import Constraints
        from mythril_tpu.support import model as support_model

        x = T.bv_var("tlm_w", 256)
        lo = Bool(T.mk_ule(x, T.bv_const(5, 256)))
        # the second disjunct is UNSAT together with the outer pin, so
        # the model MUST witness the first path
        hi = Bool(T.mk_ule(T.bv_const(1 << 200, 256), x))
        orb = merge.suffix_or([[lo], [hi]])
        pin = Bool(T.mk_ule(x, T.bv_const(100, 256)))
        support_model.get_model.cache_clear()
        m = support_model.get_model(Constraints([orb, pin]))
        wit = support_model.witness_paths([orb, pin], m)
        assert len(wit) == 1
        _c, di, terms = wit[0]
        assert di == 0 and terms == (lo.raw,)
        # and get_model attached the same selection
        assert getattr(m, "witness_disjuncts", None)


def _build_diamond(k=4, dup_levels=2, seed_ops=None):
    """Step/gas-balanced diamond-CFG fork storm with an assert-style
    INVALID tail (compact twin of bench.build_diamond_contract)."""
    from mythril_tpu.support.opcodes import ADDRESS, OPCODES

    op = {name: data[ADDRESS] for name, data in OPCODES.items()}

    def push(v, n=1):
        return bytes([0x5F + n]) + v.to_bytes(n, "big")

    c = bytearray()
    for i in range(k):
        bit = 0 if i < dup_levels else i
        c += push(bit) + bytes([op["CALLDATALOAD"]])
        c += push(1) + bytes([op["AND"]])
        j = len(c)
        c += push(0, 2) + bytes([op["JUMPI"]])
        c += bytes([op["JUMPDEST"]])
        jf = len(c)
        c += push(0, 2) + bytes([op["JUMP"]])
        t = len(c)
        c[j + 1:j + 3] = t.to_bytes(2, "big")
        c += bytes([op["JUMPDEST"]])
        jt = len(c)
        c += push(0, 2) + bytes([op["JUMP"]])
        r = len(c)
        c[jf + 1:jf + 3] = r.to_bytes(2, "big")
        c[jt + 1:jt + 3] = r.to_bytes(2, "big")
        c += bytes([op["JUMPDEST"]])
    c += push(31) + bytes([op["CALLDATALOAD"]])
    c += push(0xDEADBEEF, 4) + bytes([op["EQ"]])
    j = len(c)
    c += push(0, 2) + bytes([op["JUMPI"]])
    c += bytes([op["STOP"]])
    t = len(c)
    c[j + 1:j + 3] = t.to_bytes(2, "big")
    c += bytes([op["JUMPDEST"], 0xFE])
    return bytes(c)


def _analyze(code, merge_on, tpu_lanes, tx_count):
    from mythril_tpu.orchestration.mythril_analyzer import (
        MythrilAnalyzer, reset_analysis_state,
    )
    from mythril_tpu.orchestration.mythril_disassembler import (
        MythrilDisassembler,
    )
    from mythril_tpu.smt.solver.solver_statistics import SolverStatistics
    from mythril_tpu.support.analysis_args import make_cmd_args

    merge.FORCE = merge_on
    try:
        reset_analysis_state()
        ss = SolverStatistics()
        c0 = dict(ss.batch_counters())
        dis = MythrilDisassembler(eth=None)
        address, _ = dis.load_from_bytecode(code.hex(),
                                            bin_runtime=True)
        analyzer = MythrilAnalyzer(
            disassembler=dis,
            cmd_args=make_cmd_args(execution_timeout=120,
                                   tpu_lanes=tpu_lanes),
            strategy="bfs", address=address)
        report = analyzer.fire_lasers(modules=None,
                                      transaction_count=tx_count)
        c1 = ss.batch_counters()
        return (sorted((i.swc_id, i.address, i.title)
                       for i in report.issues.values()),
                {k: c1[k] - c0.get(k, 0)
                 for k in ("lanes_merged", "lanes_subsumed",
                           "merge_rounds", "batch_queries")})
    finally:
        merge.FORCE = None


class TestEndToEnd:
    def test_host_round_boundary_invariants(self):
        """svm round-boundary merge: issue-set identity with merge on
        vs MTPU_MERGE=0, states provably merged, and fewer open-state
        screen queries."""
        code = _build_diamond(k=3, dup_levels=1)
        issues_off, d_off = _analyze(code, False, 0, 2)
        issues_on, d_on = _analyze(code, True, 0, 2)
        assert issues_on == issues_off
        assert issues_on, "rig must produce a reachable issue"
        assert d_on["lanes_merged"] > 0
        assert d_on["batch_queries"] < d_off["batch_queries"]
        assert d_off["lanes_merged"] == 0  # off-switch really off

    def test_lane_window_boundary_invariants(self):
        """Lane window-boundary merge through the real drain: issue
        identity, merged AND subsumed lanes, collapsed path count."""
        jax = pytest.importorskip("jax")  # noqa: F841
        from mythril_tpu.laser import lane_engine

        code = _build_diamond(k=5, dup_levels=2)
        lane_engine.PATH_HISTORY[code] = 64
        lane_engine.FORCE_WIDTH = 64
        old_window = lane_engine.DEFAULT_WINDOW
        lane_engine.DEFAULT_WINDOW = 32
        try:
            lane_engine.warm_variant(64, len(code), {}, 32, 8192,
                                     seed_bucket=16)
            lane_engine.RUN_STATS_TOTAL = {}
            issues_off, _off = _analyze(code, False, 64, 1)
            parked_off = lane_engine.RUN_STATS_TOTAL.get("parked", 0)
            lane_engine.RUN_STATS_TOTAL = {}
            issues_on, d_on = _analyze(code, True, 64, 1)
            parked_on = lane_engine.RUN_STATS_TOTAL.get("parked", 0)
        finally:
            lane_engine.FORCE_WIDTH = None
            lane_engine.DEFAULT_WINDOW = old_window
        assert issues_on == issues_off
        assert d_on["lanes_merged"] > 0
        assert d_on["lanes_subsumed"] > 0
        assert parked_on < parked_off

    def test_randomized_host_fork_tree_property(self):
        """Randomized diamond shapes: merged host run reports the same
        issue set and never MORE final states than the unmerged run."""
        rng = random.Random(7)
        for _ in range(3):
            k = rng.randrange(2, 4)
            dup = rng.randrange(0, k)
            code = _build_diamond(k=k, dup_levels=dup)
            issues_off, d_off = _analyze(code, False, 0, 2)
            issues_on, d_on = _analyze(code, True, 0, 2)
            assert issues_on == issues_off
            assert d_on["batch_queries"] <= d_off["batch_queries"]


def _build_uneven_diamond(k=4, dup_levels=2, pad=3):
    """Diamond storm whose arms are STEP-balanced but GAS-unbalanced:
    both arms of level i execute pad*2^i stack-neutral filler pairs,
    but the false arm's pair is PUSH1/POP (3+2 gas) while the true
    arm's is CALLER/POP (2+2 gas) — so the arms stay in device
    lockstep (identical pc/stack/memory/storage at every rejoin) while
    every distinct branch choice lands on a UNIQUE total gas (2^i
    scaling: no equal-gas permutation twins). The shape only the
    gas-widening merge (MTPU_MERGE_GASWIDEN, docs/lane_merge.md) can
    collapse."""
    from mythril_tpu.support.opcodes import ADDRESS, OPCODES

    op = {name: data[ADDRESS] for name, data in OPCODES.items()}

    def push(v, n=1):
        return bytes([0x5F + n]) + v.to_bytes(n, "big")

    c = bytearray()
    for i in range(k):
        bit = 0 if i < dup_levels else i
        c += push(bit) + bytes([op["CALLDATALOAD"]])
        c += push(1) + bytes([op["AND"]])
        j = len(c)
        c += push(0, 2) + bytes([op["JUMPI"]])
        c += bytes([op["JUMPDEST"]])
        for _ in range(pad * (1 << i)):  # false arm: 5 gas / 2 steps
            c += push(0) + bytes([op["POP"]])
        jf = len(c)
        c += push(0, 2) + bytes([op["JUMP"]])
        t = len(c)
        c[j + 1:j + 3] = t.to_bytes(2, "big")
        c += bytes([op["JUMPDEST"]])
        for _ in range(pad * (1 << i)):  # true arm: 4 gas / 2 steps
            c += bytes([op["CALLER"], op["POP"]])
        jt = len(c)
        c += push(0, 2) + bytes([op["JUMP"]])
        r = len(c)
        c[jf + 1:jf + 3] = r.to_bytes(2, "big")
        c[jt + 1:jt + 3] = r.to_bytes(2, "big")
        c += bytes([op["JUMPDEST"]])
    c += push(31) + bytes([op["CALLDATALOAD"]])
    c += push(0xDEADBEEF, 4) + bytes([op["EQ"]])
    j = len(c)
    c += push(0, 2) + bytes([op["JUMPI"]])
    c += bytes([op["STOP"]])
    t = len(c)
    c[j + 1:j + 3] = t.to_bytes(2, "big")
    c += bytes([op["JUMPDEST"], 0xFE])
    return bytes(c)


class TestGasWidening:
    def test_uneven_gas_diamond_widens(self, monkeypatch):
        """Lane seam: an uneven-gas diamond merges ONLY under the
        gas-widening merge; issue identity holds across widening
        on/off and merge-off, and the off path stays bit-for-bit
        (zero merges — the arms' gas intervals differ)."""
        jax = pytest.importorskip("jax")  # noqa: F841
        from mythril_tpu.laser import lane_engine
        from mythril_tpu.smt.solver.solver_statistics import (
            SolverStatistics,
        )

        code = _build_uneven_diamond(k=4, dup_levels=0, pad=1)
        lane_engine.PATH_HISTORY[code] = 64
        lane_engine.FORCE_WIDTH = 64
        old_window = lane_engine.DEFAULT_WINDOW
        lane_engine.DEFAULT_WINDOW = 32
        try:
            lane_engine.warm_variant(64, len(code), {}, 32, 8192,
                                     seed_bucket=16)
            ss = SolverStatistics()
            monkeypatch.setenv("MTPU_MERGE_GASWIDEN", "0")
            issues_nowiden, d_nowiden = _analyze(code, True, 64, 1)
            w0 = ss.gas_widened_lanes
            monkeypatch.setenv("MTPU_MERGE_GASWIDEN", "1")
            issues_widen, d_widen = _analyze(code, True, 64, 1)
            widened = ss.gas_widened_lanes - w0
            issues_off, _ = _analyze(code, False, 64, 1)
        finally:
            lane_engine.FORCE_WIDTH = None
            lane_engine.DEFAULT_WINDOW = old_window
        assert issues_widen == issues_nowiden == issues_off
        assert issues_widen, "rig must produce a reachable issue"
        # the uneven arms are invisible to the gas-exact merge...
        assert d_nowiden["lanes_merged"] == 0
        # ...and collapse under widening, with the widen counter live
        assert d_widen["lanes_merged"] > 0
        assert widened > 0

    def test_balanced_diamond_unchanged_by_widening_gate(
            self, monkeypatch):
        """A gas-balanced diamond merges identically with widening on
        or off (the gate only relaxes the grouping key)."""
        jax = pytest.importorskip("jax")  # noqa: F841
        from mythril_tpu.laser import lane_engine

        code = _build_diamond(k=4, dup_levels=2)
        lane_engine.PATH_HISTORY[code] = 64
        lane_engine.FORCE_WIDTH = 64
        old_window = lane_engine.DEFAULT_WINDOW
        lane_engine.DEFAULT_WINDOW = 32
        try:
            lane_engine.warm_variant(64, len(code), {}, 32, 8192,
                                     seed_bucket=16)
            monkeypatch.setenv("MTPU_MERGE_GASWIDEN", "0")
            issues_a, d_a = _analyze(code, True, 64, 1)
            monkeypatch.setenv("MTPU_MERGE_GASWIDEN", "1")
            issues_b, d_b = _analyze(code, True, 64, 1)
        finally:
            lane_engine.FORCE_WIDTH = None
            lane_engine.DEFAULT_WINDOW = old_window
        assert issues_a == issues_b
        assert d_a["lanes_merged"] == d_b["lanes_merged"]
        assert d_a["lanes_subsumed"] == d_b["lanes_subsumed"]
