"""Bidirectional fixpoint propagation (ops/propagate.py): product
domain (intervals x known-bits) kills the interval-only screen cannot
make, SAT preservation over a randomized tree corpus (the soundness
property), hinted-solve verdict parity, fact harvest into the verdict
cache, seed-table bucketing, and the pruner's fatal-exception
classification. See docs/propagation.md."""

import random

import numpy as np
import pytest

from mythril_tpu.ops import intervals, propagate
from mythril_tpu.smt import terms as T
from mythril_tpu.smt.solver import core as solver_core
from mythril_tpu.smt.solver import verdicts
from mythril_tpu.smt.solver.core import reset_session
from mythril_tpu.smt.solver.solver_statistics import SolverStatistics

_N = [0]


def _fresh(name, w=256):
    """Per-test-unique symbols (terms intern process-wide)."""
    _N[0] += 1
    return T.bv_var(f"prop_{name}_{_N[0]}", w)


def _bv(v, w=256):
    return T.bv_const(v, w)


@pytest.fixture(autouse=True)
def _fresh_state():
    verdicts.reset_cache()
    old_force = propagate.FORCE
    yield
    propagate.FORCE = old_force
    verdicts.reset_cache()


def test_bit_conflict_killed_only_by_propagation():
    """The motivating shape: `x & 0xff == 0x42  /\\  x & 0xff == 0x43`.
    Forward intervals keep both equalities may-true (the masked node's
    range [0, 0xff] contains both constants); backward EQ-pinning
    forces the SHARED masked node's known bits both ways — a
    `k0 & k1` contradiction. The solver confirms the kill."""
    x = _fresh("bc")
    s = [T.mk_eq(T.mk_and(x, _bv(0xFF)), _bv(0x42)),
         T.mk_eq(T.mk_and(x, _bv(0xFF)), _bv(0x43))]
    assert list(intervals.prefilter_feasible([s])) == [True]
    ss = SolverStatistics()
    kills0 = ss.propagate_kills
    assert list(propagate.prefilter_feasible([s])) == [False]
    assert ss.propagate_kills > kills0
    assert ss.propagate_sweeps > 0
    assert solver_core.check(s, timeout_s=10.0).status == solver_core.UNSAT


def test_unit_propagation_chain():
    """`not(a or b) /\\ a` dies by unit propagation (backward NOT/OR
    forces `a` false against its pinned-true root); the consistent
    variant survives."""
    a, b = T.bool_var("prop_ua_%d" % _N[0]), T.bool_var(
        "prop_ub_%d" % _N[0])
    _N[0] += 1
    dead = [T.mk_not(T.mk_bool_or(a, b)), a]
    alive = [T.mk_not(T.mk_bool_or(a, b)), T.mk_not(a)]
    assert list(intervals.prefilter_feasible([dead])) == [True]
    got = list(propagate.prefilter_feasible([dead, alive]))
    assert got == [False, True]


def test_backward_arithmetic_and_shift_inversion():
    """Inverse ADD pins `x` from `x + 5 == 7`; inverse SHL recovers
    x's low byte from `(x << 8) == 0x4200` and conflicts it with a
    second mask equality. Consistent variants survive."""
    x = _fresh("ar")
    add_dead = [T.mk_eq(T.mk_add(x, _bv(5)), _bv(7)),
                T.mk_ule(_bv(10), x)]
    shl_dead = [T.mk_eq(T.mk_shl(x, _bv(8)), _bv(0x4200)),
                T.mk_eq(T.mk_and(x, _bv(0xFF)), _bv(0x43))]
    shl_ok = [T.mk_eq(T.mk_shl(x, _bv(8)), _bv(0x4200)),
              T.mk_eq(T.mk_and(x, _bv(0xFF)), _bv(0x42))]
    got = list(propagate.prefilter_feasible([add_dead, shl_dead, shl_ok]))
    assert got == [False, False, True]
    for s in (add_dead, shl_dead):
        assert solver_core.check(
            list(s), timeout_s=10.0).status == solver_core.UNSAT


def _random_tree_sets(rng, n_sets, pinned):
    """Random constraint trees over masked/shifted/added subterms —
    the shapes the product domain reasons about. `pinned` sets include
    an exact variable pin, so backward rules start from a point."""
    W = 64
    syms = [_fresh(f"rt{i}", W) for i in range(3)]

    def b64(v):
        return T.bv_const(v, W)

    def rand_e():
        s = rng.choice(syms)
        k = rng.random()
        if k < 0.3:
            return T.mk_and(s, b64(rng.randrange(1, 1 << 10)))
        if k < 0.5:
            return T.mk_add(s, b64(rng.randrange(1, 256)))
        if k < 0.6:
            return T.mk_shl(s, b64(rng.randrange(0, 6)))
        return s

    sets = []
    for _ in range(n_sets):
        terms = []
        if pinned:
            terms.append(T.mk_eq(rng.choice(syms),
                                 b64(rng.randrange(0, 1 << 10))))
        for _ in range(rng.randrange(2, 5)):
            e = rand_e()
            k = rng.randrange(3)
            mk = (T.mk_eq if k == 0
                  else T.mk_ult if k == 1 else T.mk_ule)
            c = mk(e, b64(rng.randrange(0, 1 << 10)))
            if rng.random() < 0.2:
                c = T.mk_not(c)
            terms.append(c)
        sets.append(terms)
    return sets


def test_sat_preservation_randomized():
    """THE soundness property: across 200 random trees (100 pinned +
    100 unpinned) the screen never kills a set the solver proves SAT —
    every kill re-derives as a core UNSAT."""
    rng = random.Random(0xA11CE)
    sets = (_random_tree_sets(rng, 100, pinned=False)
            + _random_tree_sets(rng, 100, pinned=True))
    keep = propagate.prefilter_feasible(sets)
    assert len(keep) == len(sets)
    killed = [s for s, k in zip(sets, keep) if not k]
    assert killed, "the corpus should produce some kills"
    for s in killed:
        got = solver_core.check(list(s), timeout_s=10.0).status
        assert got == solver_core.UNSAT, (
            "propagation killed a non-UNSAT set: %r" % ([repr(t) for t in s],))


def test_hinted_solves_verdict_parity():
    """Hinted solves (harvested facts asserted ahead of the real
    constraints) must return verdicts identical to unhinted solves
    through the real check_batch seam."""
    from mythril_tpu.laser.state.constraints import Constraints
    from mythril_tpu.models import pruner
    from mythril_tpu.smt.bool import Bool
    from mythril_tpu.support import model as support_model
    from mythril_tpu.support.model import check_batch
    from mythril_tpu.support.support_args import args

    rng = random.Random(0xFACE)
    raw_sets = (_random_tree_sets(rng, 12, pinned=False)
                + _random_tree_sets(rng, 12, pinned=True))
    sets = [Constraints([Bool(t) for t in s]) for s in raw_sets]

    old_lanes = args.tpu_lanes
    args.tpu_lanes = 8
    ss = SolverStatistics()
    kills0, hints0 = ss.propagate_kills, ss.hinted_solves
    try:
        propagate.FORCE = True
        verdicts.reset_cache()
        reset_session()
        support_model.get_model.cache_clear()
        hinted = check_batch(sets)
        assert ss.propagate_kills > kills0
        assert ss.hinted_solves > hints0

        propagate.FORCE = False
        verdicts.reset_cache()
        reset_session()
        support_model.get_model.cache_clear()
        plain = check_batch(sets)
    finally:
        args.tpu_lanes = old_lanes
        support_model.get_model.cache_clear()
        reset_session()
    assert hinted == plain


def test_facts_harvested_into_verdict_cache():
    """Surviving lanes bank pinned constants / tightened bounds /
    known-bit masks in the run-wide cache; absorb_bounds feeds tier-3
    inheritance."""
    x = _fresh("fh")
    s = [T.mk_eq(T.mk_and(x, _bv(0xFF)), _bv(0x42)),
         T.mk_ule(x, _bv(1 << 16))]
    ss = SolverStatistics()
    facts0 = ss.facts_harvested
    assert list(propagate.prefilter_feasible([s])) == [True]
    assert ss.facts_harvested > facts0
    vc = verdicts.cache()
    facts = vc.facts_for(tuple(t.tid for t in s))
    assert facts, "the masked equality should harvest facts"
    # every harvested fact is IMPLIED by the set: set /\ not(fact)
    # must be UNSAT
    for f in facts:
        got = solver_core.check(list(s) + [T.mk_not(f)],
                                timeout_s=10.0).status
        assert got == solver_core.UNSAT
    # the propagated bounds seeded the entry for tier-3 inheritance
    e = vc._entries.get(vc.key(tuple(t.tid for t in s)))
    assert e is not None and e.bounds


def test_propagate_off_restores_interval_screen():
    """MTPU_PROPAGATE=0 (FORCE=False) routes the pruner's device
    screen through the plain forward interval pass — the rigged bit
    conflict survives again, bit-for-bit the pre-propagation verdict."""
    from mythril_tpu.models.pruner import _device_prefilter

    x = _fresh("off")
    s = [T.mk_eq(T.mk_and(x, _bv(0xFF)), _bv(0x42)),
         T.mk_eq(T.mk_and(x, _bv(0xFF)), _bv(0x43))]
    propagate.FORCE = False
    off = list(_device_prefilter([s]))
    propagate.FORCE = True
    on = list(_device_prefilter([s]))
    assert off == [True]  # interval-only cannot kill it
    assert on == [False]


def test_seed_tables_bucket_to_pow2():
    """Satellite: linearize pads the state axis AND the per-state
    seed/assert slot axes to powers of two under CANONICAL_KEYS, pad
    lanes report dead-on-arrival, and verdicts slice back to n_real."""
    if not intervals.CANONICAL_KEYS:
        pytest.skip("canonical keys disabled")
    xs = [_fresh(f"bk{i}") for i in range(3)]
    sets = []
    for i in range(5):  # 5 states -> 8 rows
        s = [T.mk_ule(_bv(1), xs[i % 3])]
        if i % 2:
            s.append(T.mk_ule(xs[(i + 1) % 3], _bv(1 << 20)))
            s.append(T.mk_ule(_bv(2), xs[(i + 2) % 3]))  # 3 asserts
        sets.append(s)
    enc = intervals.linearize(sets)
    S, V = enc.seed_idx.shape
    A = enc.assert_idx.shape[1]
    assert enc.n_real == 5
    assert S == 8 and S == enc.assert_idx.shape[0]
    assert V & (V - 1) == 0 and A & (A - 1) == 0  # pow2
    assert bool(np.all(enc.dead[5:]))  # pad lanes dead-on-arrival
    keep = intervals.eval_feasible(enc)
    assert len(keep) == 5 and all(keep)


def test_device_failed_fatal_classification():
    """MemoryError/KeyboardInterrupt are FATAL — they re-raise instead
    of being counted as a device error the screen recovers from;
    ordinary exceptions are counted."""
    from mythril_tpu.models import pruner

    ss = SolverStatistics()
    n0 = ss.device_screen_errors
    with pytest.raises(MemoryError):
        pruner._device_failed(MemoryError("oom"))
    with pytest.raises(KeyboardInterrupt):
        pruner._device_failed(KeyboardInterrupt())
    assert ss.device_screen_errors == n0
    pruner._device_failed(RuntimeError("transient"))
    assert ss.device_screen_errors == n0 + 1


def test_prescreen_respects_gates():
    """The discharge-seam prescreen honors the MTPU_PROPAGATE gate and
    the device lane gate (no device config -> no kills, no crash)."""
    from mythril_tpu.support.support_args import args

    x = _fresh("pg")
    dead = [T.mk_eq(T.mk_and(x, _bv(0xFF)), _bv(0x42)),
            T.mk_eq(T.mk_and(x, _bv(0xFF)), _bv(0x43))]
    sets = [dead] * 10
    old_lanes = args.tpu_lanes
    try:
        args.tpu_lanes = 0
        propagate.FORCE = True
        assert propagate.prescreen(sets, range(len(sets))) == {}
        args.tpu_lanes = 8
        propagate.FORCE = False
        assert propagate.prescreen(sets, range(len(sets))) == {}
        propagate.FORCE = True
        from mythril_tpu.models import pruner

        kills = propagate.prescreen(sets, range(len(sets)))
        assert set(kills) == set(range(10))
    finally:
        args.tpu_lanes = old_lanes
