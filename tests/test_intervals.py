"""Device interval evaluator (ops/intervals) vs host domain (smt/interval).

Two obligations:
1. agreement: for random term DAGs, the device verdict must match the host
   `must_be_false` screening per assertion set;
2. soundness: whenever the device prunes a state, the host CDCL solver must
   agree the constraints are UNSAT (checked on small-width systems).
"""

import random

import pytest

from mythril_tpu.ops.intervals import prefilter_feasible
from mythril_tpu.smt import (
    And,
    LShR,
    Not,
    Or,
    Solver,
    UGE,
    UGT,
    ULE,
    ULT,
    symbol_factory,
    unsat,
)
from mythril_tpu.smt.interval import state_infeasible

random.seed(7)


def BV(v, w=256):
    return symbol_factory.BitVecVal(v, w)


def sym(name, w=256):
    return symbol_factory.BitVecSym(name, w)


def host_keep(assertion_sets):
    return [not state_infeasible(assts) for assts in assertion_sets]


def check_agreement(assertion_sets):
    """Device must never prune a state the host keeps (the host domain is
    solver-verified sound; terms wider than 256 bits are device-topped, so
    the device may legitimately keep MORE than the host)."""
    dev = list(prefilter_feasible(assertion_sets))
    host = host_keep(assertion_sets)
    for i, (d, h) in enumerate(zip(dev, host)):
        assert bool(d) or not h, (
            f"set {i}: device pruned a state the host keeps"
        )
    return dev


def test_basic_contradictions():
    x = sym("x")
    sets = [
        [UGT(x, BV(10)), ULT(x, BV(5))],           # infeasible
        [UGT(x, BV(10)), ULT(x, BV(20))],          # feasible
        [x + BV(1) == BV(5), UGT(x, BV(100))],     # x==4 vs x>100: infeasible
        [ULE(x, BV(0)), UGE(x, BV(0))],            # x == 0: feasible
        [UGT(BV(3), BV(4))],                       # constant false
        [UGT(BV(5), BV(4))],                       # constant true
    ]
    dev = check_agreement(sets)
    assert [bool(d) for d in dev] == [False, True, False, True, False, True]


def test_arith_propagation():
    x, y = sym("x2"), sym("y2")
    sets = [
        # x < 16, y < 16 => x*y < 256; assert x*y > 300 must die
        [ULT(x, BV(16)), ULT(y, BV(16)), UGT(x * y, BV(300))],
        # same but assert x*y > 100: may be true
        [ULT(x, BV(16)), ULT(y, BV(16)), UGT(x * y, BV(100))],
        # x & 0xff <= 255, assert > 255 dies
        [UGT(x & BV(0xFF), BV(255))],
        # x | 1 >= 1, assert == 0 dies
        [(x | BV(1)) == BV(0)],
        # LShR(x, 250) <= 63, assert > 63 dies (note: BitVec >> is the
        # arithmetic shift, which the interval domain tops)
        [UGT(LShR(x, BV(250)), BV(63))],
    ]
    dev = check_agreement(sets)
    assert [bool(d) for d in dev] == [False, True, False, False, False]

    # note: interval domain cannot refine multiplication when operand
    # ranges are full-width; those go to the solver, not the pruner


def test_bool_structure():
    x = sym("x3")
    t = UGT(x, BV(10))
    f = ULT(x, BV(5))
    sets = [
        [And(t, f)],               # conjunction of disjoint ranges: dead
        [Or(t, f)],                # disjunction: alive
        [Not(Or(t, f))],           # negation of satisfiable-or: may hold
        [And(t, Not(t))],          # x>10 and not(x>10): dead
    ]
    dev = check_agreement(sets)
    assert [bool(d) for d in dev] == [False, True, True, False]


def test_ite_and_extract():
    x = sym("x4")
    cond = UGT(x, BV(100))
    ite_v = symbol_factory.BitVecVal(0, 256)
    from mythril_tpu.smt import If, Extract, Concat

    v = If(cond, BV(1), BV(2))
    lowbyte = Extract(7, 0, x)
    sets = [
        [UGT(v, BV(5))],                       # v in {1,2}: dead
        [ULT(v, BV(5))],                       # alive
        # byte <= 255 < 300, but the concat is 264 bits wide: host prunes,
        # device soundly tops wide terms and keeps it
        [UGT(Concat(BV(0, 8), lowbyte), BV(300, 264))],
        # same fact inside 256 bits: both must prune
        [UGT(Concat(BV(0, 248), lowbyte), BV(300))],
    ]
    dev = check_agreement(sets)
    assert [bool(d) for d in dev] == [False, True, True, False]
    assert host_keep(sets) == [False, True, False, False]


def test_device_prune_soundness_vs_solver():
    """Every device-pruned system must actually be UNSAT (32-bit widths so
    the CDCL core answers quickly)."""
    w = 32
    names = iter(range(1000))
    rand_const = lambda: BV(random.getrandbits(w) >> random.choice([0, 8, 16, 24]), w)

    def rand_expr(depth, syms):
        if depth == 0 or random.random() < 0.3:
            return random.choice(syms) if random.random() < 0.6 else rand_const()
        a = rand_expr(depth - 1, syms)
        b = rand_expr(depth - 1, syms)
        op = random.choice(["add", "sub", "and", "or", "shr", "not"])
        if op == "add":
            return a + b
        if op == "sub":
            return a - b
        if op == "and":
            return a & b
        if op == "or":
            return a | b
        if op == "shr":
            return a >> BV(random.choice([1, 4, 8, 16]), w)
        return ~a

    sets = []
    for i in range(40):
        xs = [sym(f"r{i}_{j}", w) for j in range(2)]
        assts = []
        for _ in range(random.randint(1, 4)):
            a, b = rand_expr(2, xs), rand_expr(2, xs)
            assts.append(random.choice([ULT, UGT, lambda p, q: p == q])(a, b))
        sets.append(assts)

    keep = prefilter_feasible(sets)
    pruned = [i for i, k in enumerate(keep) if not k]
    checked = 0
    for i in pruned:
        s = Solver()
        s.set_timeout(5000)
        for a in sets[i]:
            s.add(a)
        assert s.check() == unsat, f"device pruned a satisfiable system {i}"
        checked += 1
    # device never prunes what the host keeps
    host = host_keep(sets)
    for i, (d, h) in enumerate(zip(keep, host)):
        assert bool(d) or not h, i


def test_pruner_entry_point():
    """models/pruner device path drops exactly the infeasible states."""
    from mythril_tpu.models.pruner import _prefilter_device

    class FakeWS:
        def __init__(self, constraints):
            self.constraints = constraints

    x = sym("x5")
    good = FakeWS([UGT(x, BV(10))])
    bad = FakeWS([UGT(x, BV(10)), ULT(x, BV(3))])
    states = [good, bad] * 5
    kept = _prefilter_device(states)
    assert len(kept) == 5
    assert all(k is good for k in kept)


def test_wide_constants_are_topped_not_truncated():
    """A >256-bit constant whose low bits are zero must not produce a
    false-tight interval (regression: truncation made ULT(concat(0,x),
    2**260) look must-false and pruned a satisfiable state)."""
    from mythril_tpu.smt import Concat

    x = sym("xw")
    wide = Concat(BV(0, 8), x)  # 264-bit
    sets = [
        [ULT(wide, BV(1 << 260, 264))],   # trivially sat
        [UGT(wide, BV(1 << 260, 264))],   # unsat, but device must KEEP
                                          # (wide terms are topped)
    ]
    dev = list(prefilter_feasible(sets))
    assert bool(dev[0]) and bool(dev[1])


def test_device_failure_counted_and_retried(monkeypatch):
    """A device failure is counted (SolverStatistics.device_screen_
    errors), the wave is screened on the host, and the next call tries
    the device again: one hiccup never latches screening off."""
    from mythril_tpu.models import pruner
    from mythril_tpu.smt.solver.solver_statistics import SolverStatistics
    from mythril_tpu.support.support_args import args

    class FakeWS:
        def __init__(self, constraints):
            self.constraints = constraints

    x = sym("x_backoff")
    states = [FakeWS([UGT(x, BV(10))]) for _ in range(16)]

    calls = {"n": 0, "fail": True}

    def fake_device(open_states):
        calls["n"] += 1
        if calls["fail"]:
            raise RuntimeError("transient device hiccup")
        return list(open_states)

    monkeypatch.setattr(pruner, "_prefilter_device", fake_device)
    monkeypatch.setattr(args, "tpu_lanes", 64)
    ss = SolverStatistics()
    n0 = ss.device_screen_errors
    out = pruner.prefilter_world_states(states)
    assert len(out) == len(states)  # host fallback kept everything
    assert calls["n"] == 1
    assert ss.device_screen_errors == n0 + 1
    calls["fail"] = False
    pruner.prefilter_world_states(states)
    assert calls["n"] == 2
    assert ss.device_screen_errors == n0 + 1


def test_prune_feasible_states_batched(monkeypatch):
    """prune_feasible_states: interval screen (device when batched)
    drops provably-unsat forks; survivors keep is_possible semantics."""
    from mythril_tpu.models import pruner
    from mythril_tpu.support.support_args import args

    class FakeConstraints(list):
        def is_possible(self):
            return True

    class FakeWS:
        def __init__(self, constraints):
            self.constraints = FakeConstraints(constraints)

    class FakeGS:
        def __init__(self, constraints):
            self.world_state = FakeWS(constraints)

    x = sym("x_forks")
    good = FakeGS([UGT(x, BV(10))])
    bad = FakeGS([UGT(x, BV(10)), ULT(x, BV(3))])

    # host path (small batch)
    monkeypatch.setattr(args, "tpu_lanes", 0)
    out = pruner.prune_feasible_states([good, bad])
    assert out == [good]

    # device path (batched)
    monkeypatch.setattr(args, "tpu_lanes", 64)
    try:
        states = [good, bad] * 5
        out = pruner.prune_feasible_states(states)
        assert len(out) == 5 and all(s is good for s in out)
    finally:
        args.tpu_lanes = 0
