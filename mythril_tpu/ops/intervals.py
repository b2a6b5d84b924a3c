"""Batched interval constraint evaluation on device.

This is the TPU half of the `Constraints.is_possible` replacement promised
in SURVEY.md §2.1/§2.10 (solver-level row): the reference discharges every
reachability check to Z3 (mythril/laser/ethereum/svm.py:244-252 open-state
pruning; state/constraints.py:27 `is_possible`). Here, the union term DAG
of many states' constraint systems is linearized host-side into
level-synchronous tensors and abstractly evaluated on device with the same
unsigned-interval transfer functions as the host prototype
(mythril_tpu/smt/interval.py).

The batching axis is the *state*: each state's syntactic variable bounds
(smt.interval.extract_bounds — the cross-assertion seeding that catches
contradictory branch conditions like x>10 ∧ x<5) seed that state's own
copy of the interval table, so one device dispatch evaluates the shared
DAG under S different variable environments at once: tables are
(S, T, 2, 8) and every transfer function is vectorized over both the
state axis and the level's node axis. A state is pruned when any of its
assertions' may-be-true bits comes back 0 — sound by construction (the
abstraction only ever over-approximates feasibility).

Encoding details:
- interval endpoints are 256-bit words in the bv256 8xuint32 limb format;
  terms wider than 256 bits (post-SHA3 concats) are soundly topped;
- a Bool abstraction (may_false, may_true) rides in limb 0 of the lo/hi
  endpoint slots;
- per-node static data is baked host-side: device opcode, three arg
  indices (EXTRACT reuses two as bit-position immediates), a width mask
  (2^w - 1), and an aux word (SEXT sign threshold, EXTRACT field mask,
  CONCAT low-part width);
- evaluation loops over topological levels; within a level every transfer
  function runs vectorized and a per-node select keys on the opcode —
  the same masked-family pattern as the lane stepper. MUL's 512-bit
  product and UDIV's shift-subtract loops are lax.cond-gated per level.
"""

import logging
import os
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..smt import terms as T
from ..smt.interval import extract_bounds
from . import bv256

log = logging.getLogger(__name__)

# device opcodes (NOP = leaf/unsupported: table keeps its host-seeded value)
(
    NOP, ADD, SUB, MUL, UDIV, UREM, BAND, BOR, BXOR, BNOT, NEG, SHL, LSHR,
    COPY, SEXT, EXTRACT, CONCAT2, ITE, EQ, ULT, ULE, BAND2, BOR2, BNOT1,
    BXOR2, BITE,
) = range(26)

_BINOP_MAP = {
    T.ADD: ADD,
    T.SUB: SUB,
    T.MUL: MUL,
    T.UDIV: UDIV,
    T.UREM: UREM,
    T.BAND: BAND,
    T.BOR: BOR,
    T.BXOR: BXOR,
    T.SHL: SHL,
    T.LSHR: LSHR,
}

# ---------------------------------------------------------------------------
# compile-key canonicalization
# ---------------------------------------------------------------------------
#
# The level kernel jit-specializes per (ops_present, shapes). Raw keys
# made every structurally-new DAG a cold compile: level widths repeat
# (pow2-padded) but the node-table row count and the exact opcode subset
# of each level varied per contract, so a corpus sweep re-specialized
# near-identical kernels dozens of times. Two canonicalizations collapse
# the key space:
#
# 1. the node table pads to a power of two, so table shapes bucket the
#    same way level widths and the state axis already do;
# 2. a level's ops_present widens to the CHEAP cover (every transfer
#    function except the 512-bit MUL product and the UDIV/UREM
#    shift-subtract loops) plus exactly the expensive ops it uses.
#    Absent ops are masked off by the per-node opcode select, so the
#    result is bit-identical; the cheap extras cost a few masked
#    elementwise bv256 ops at runtime while structurally-repeated DAGs
#    across contracts hit the jit cache instead of recompiling.
#
# MYTHRIL_TPU_INTERVAL_CANONICAL=0 restores exact keys (A/B debugging).

CANONICAL_KEYS = os.environ.get(
    "MYTHRIL_TPU_INTERVAL_CANONICAL", "1") != "0"

_EXPENSIVE_OPS = frozenset({MUL, UDIV, UREM})
_CHEAP_COVER = frozenset(range(1, 26)) - _EXPENSIVE_OPS


def _canonical_ops(ops: set) -> tuple:
    """Static compile key for a level's opcode set (see above)."""
    if not CANONICAL_KEYS:
        return tuple(sorted(ops))
    return tuple(sorted(_CHEAP_COVER | (ops & _EXPENSIVE_OPS)))


class EncodedDAG:
    """Host-side linearization of a term-DAG union into level tensors."""

    def __init__(self, n_nodes, levels, init_lo, init_hi, seed_idx, seed_lo,
                 seed_hi, dead, assert_idx, assert_mask, n_real=None,
                 host=None):
        self.n_nodes = n_nodes
        self.levels = levels  # list of dicts of per-level arrays
        self.init_lo = init_lo  # (T, 8) uint32 shared defaults
        self.init_hi = init_hi
        self.seed_idx = seed_idx  # (S, V) int32 node index (T = unused slot)
        self.seed_lo = seed_lo  # (S, V, 8)
        self.seed_hi = seed_hi
        self.dead = dead  # (S,) bool — contradictory bounds, pre-pruned
        self.assert_idx = assert_idx  # (S, A) int32 node index per assertion
        self.assert_mask = assert_mask  # (S, A) bool
        # logical lane count: the state axis buckets to a power of two
        # under CANONICAL_KEYS (pad lanes seeded TOP, no live
        # assertions, marked dead-on-arrival), so sibling-wave sizes
        # stop forking fresh XLA variants of the level kernels
        self.n_real = seed_idx.shape[0] if n_real is None else n_real
        # host-side node tables (numpy; kept for the propagation kernel
        # — ops/propagate.py builds its backward/product-domain plan
        # from these instead of re-walking the term DAG)
        self.host = host or {}


def _word(v: int) -> np.ndarray:
    return bv256.int_to_limbs(v)


def _next_pow2(x: int) -> int:
    return 1 << max(x - 1, 0).bit_length()


def linearize(assertion_sets: Sequence[Sequence["T.Term"]],
              pin_bv: Optional[Dict[str, int]] = None,
              pin_bools: Optional[Dict[str, bool]] = None) -> EncodedDAG:
    """Topo-sort the union DAG, bake static node tensors, and extract the
    per-state variable-bound seeds.

    ``pin_bv``/``pin_bools`` pin named variables to point intervals —
    the model-shadow evaluation mode (smt/solver/verdicts.py): every
    state shares one assignment, so the pins bake into the shared init
    tables, the per-state bound seeds are skipped, and a must-true
    assertion under the pins is exact (sound for proving SAT)."""
    assertion_sets = [
        [getattr(t, "raw", t) for t in s] for s in assertion_sets
    ]
    pinned = pin_bv is not None or pin_bools is not None
    pin_bv = pin_bv or {}
    pin_bools = pin_bools or {}
    # collect nodes iteratively (deep chains exceed recursion limits)
    depth: Dict[int, int] = {}
    nodes: Dict[int, "T.Term"] = {}
    stack: List["T.Term"] = [t for s in assertion_sets for t in s]
    while stack:
        cur = stack[-1]
        if cur.tid in depth:
            stack.pop()
            continue
        pending = [a for a in cur.args if a.tid not in depth]
        if pending:
            stack.extend(pending)
            continue
        stack.pop()
        d = 1 + max((depth[a.tid] for a in cur.args), default=0)
        depth[cur.tid] = d
        nodes[cur.tid] = cur

    order = sorted(nodes.values(), key=lambda t: (depth[t.tid], t.tid))
    index = {t.tid: i for i, t in enumerate(order)}
    n = len(order)

    # table rows bucket to a power of two (the pad slot at index n and
    # above is never an argument of a real node, so writes landing
    # there are inert) — repeated DAG sizes across contracts then share
    # the level kernels' (S, T, 8) table shapes instead of
    # re-specializing per exact node count
    n_slots = _next_pow2(n + 1) if CANONICAL_KEYS else n

    init_lo = np.zeros((n_slots, bv256.NLIMBS), dtype=np.uint32)
    init_hi = np.zeros((n_slots, bv256.NLIMBS), dtype=np.uint32)
    dev_op = np.zeros(n, dtype=np.int32)
    args = np.zeros((n, 3), dtype=np.int32)
    mask_w = np.zeros((n, bv256.NLIMBS), dtype=np.uint32)
    aux = np.zeros((n, bv256.NLIMBS), dtype=np.uint32)

    for i, t in enumerate(order):
        op = t.op
        w = t.width if isinstance(t.width, int) else 0
        wide = w > 256
        if w and not wide:
            mask_w[i] = _word((1 << w) - 1)
        # default/seed abstraction
        if op == T.BV_CONST:
            if wide:
                # a >256-bit constant must be topped, not truncated:
                # truncation would manufacture a false tight interval and
                # let comparisons prune satisfiable states. All wide
                # nodes keep lo=0, so capped his can never mis-fire the
                # disjointness/ordering tests.
                init_hi[i] = _word((1 << 256) - 1)
            else:
                init_lo[i] = init_hi[i] = _word(t.val)
        elif op == T.TRUE:
            init_hi[i] = _word(1)  # (may_false=0, may_true=1)
        elif op == T.FALSE:
            init_lo[i] = _word(1)
        elif op == T.BOOL_VAR and t.name in pin_bools:
            # pinned definite bool: (may_false, may_true) = (!v, v)
            val = bool(pin_bools[t.name])
            init_lo[i] = _word(0 if val else 1)
            init_hi[i] = _word(1 if val else 0)
        elif t.is_bool:
            init_lo[i] = _word(1)
            init_hi[i] = _word(1)
        elif op == T.BV_VAR and not wide and w and t.name in pin_bv:
            # pinned point interval from the shadow model
            val = int(pin_bv[t.name]) & ((1 << w) - 1)
            init_lo[i] = init_hi[i] = _word(val)
        elif w:
            init_hi[i] = _word((1 << min(w, 256)) - 1)

        for k, a in enumerate(t.args[:3]):
            args[i, k] = index[a.tid]

        if wide:
            continue  # NOP: stays at top

        if op in _BINOP_MAP:
            dev_op[i] = _BINOP_MAP[op]
        elif op == T.BNOT:
            dev_op[i] = BNOT
        elif op == T.NEG:
            dev_op[i] = NEG
        elif op == T.ZEXT:
            dev_op[i] = COPY
        elif op == T.SEXT:
            iw = t.args[0].width
            if isinstance(iw, int) and iw <= 256:
                dev_op[i] = SEXT
                aux[i] = _word(1 << (iw - 1))
        elif op == T.EXTRACT:
            hi_b, lo_b = t.params
            dev_op[i] = EXTRACT
            aux[i] = _word((1 << (hi_b - lo_b + 1)) - 1)
            args[i, 1] = lo_b  # immediate, not a node index
            args[i, 2] = hi_b
        elif op == T.CONCAT:
            # 2-ary concat only; n-ary stays at top (sound)
            if len(t.args) == 2 and all(
                isinstance(a.width, int) and a.width <= 256 for a in t.args
            ):
                dev_op[i] = CONCAT2
                aux[i] = _word(t.args[1].width)
        elif op == T.ITE:
            dev_op[i] = ITE
        elif op == T.EQ:
            a, b = t.args
            if not (a.is_array or b.is_array or a.is_bool or b.is_bool):
                dev_op[i] = EQ
        elif op == T.ULT:
            dev_op[i] = ULT
        elif op == T.ULE:
            dev_op[i] = ULE
        elif op == T.AND:
            if len(t.args) == 2:
                dev_op[i] = BAND2
        elif op == T.OR:
            if len(t.args) == 2:
                dev_op[i] = BOR2
        elif op == T.NOT:
            dev_op[i] = BNOT1
        elif op == T.XOR:
            dev_op[i] = BXOR2
        elif op == T.BOOL_ITE:
            dev_op[i] = BITE
        # everything else (vars, SELECT/APPLY, SDIV/SREM, SLT/SLE) stays
        # NOP at its seeded default

    # build level tensors (skip levels that are all NOP — usually leaves).
    # Width is padded to a power of two and each level records a
    # CANONICALIZED opcode set (_canonical_ops): the level kernel is
    # jit-specialized per (ops_present, shapes), the cheap-cover key
    # keeps expensive ops (512-bit MUL, divmod shift-subtract) gated on
    # actual occurrence, and structurally-repeated DAGs across contracts
    # hit the jit cache instead of paying a per-shape cold compile.
    levels = []
    start = 0
    while start < n:
        d = depth[order[start].tid]
        end = start
        while end < n and depth[order[end].tid] == d:
            end += 1
        idx = np.arange(start, end, dtype=np.int32)
        if np.any(dev_op[idx] != NOP):
            w = _next_pow2(len(idx))
            pad = w - len(idx)
            # pad rows: node index n scatters with mode="drop"; op NOP
            node_p = np.concatenate(
                [idx, np.full(pad, n, dtype=np.int32)])
            op_p = np.concatenate(
                [dev_op[idx], np.zeros(pad, dtype=np.int32)])
            args_p = np.concatenate(
                [args[idx], np.zeros((pad, 3), dtype=np.int32)])
            mask_p = np.concatenate(
                [mask_w[idx],
                 np.zeros((pad, bv256.NLIMBS), dtype=np.uint32)])
            aux_p = np.concatenate(
                [aux[idx],
                 np.zeros((pad, bv256.NLIMBS), dtype=np.uint32)])
            levels.append(
                dict(
                    node=jnp.asarray(node_p),
                    op=jnp.asarray(op_p),
                    args=jnp.asarray(args_p),
                    mask=jnp.asarray(mask_p),
                    aux=jnp.asarray(aux_p),
                    ops_present=_canonical_ops(
                        set(dev_op[idx].tolist()) - {NOP}),
                )
            )
        start = end

    # per-state variable-bound seeds + assertion pointers (pinned mode
    # bakes the one shared assignment into the init tables above; the
    # syntactic bound seeds add nothing to point intervals and their
    # empty-range dead marking would conflate "model rejected" with
    # "infeasible", so they are skipped)
    n_states = len(assertion_sets)
    all_bounds = ([{} for _ in assertion_sets] if pinned
                  else [extract_bounds(s) for s in assertion_sets])
    max_v = max((len(b) for b in all_bounds), default=1) or 1
    max_a = max((len(s) for s in assertion_sets), default=1) or 1
    # the seed/assert tables bucket BOTH free axes the way the node
    # tables already bucket: the per-state slot counts (V, A) and the
    # lane count S pad to powers of two, so a wave of 9 siblings with
    # 3 seeded vars reuses the level kernels compiled for the
    # (16, 4)-shaped wave instead of forking a fresh XLA variant. Pad
    # lanes carry no seeds and no live assertions and are marked
    # dead-on-arrival (callers slice verdicts back to n_real).
    s_rows = _next_pow2(n_states) if CANONICAL_KEYS else n_states
    if CANONICAL_KEYS:
        max_v = _next_pow2(max_v)
        max_a = _next_pow2(max_a)
    seed_idx = np.full((s_rows, max_v), n, dtype=np.int32)
    seed_lo = np.zeros((s_rows, max_v, bv256.NLIMBS), dtype=np.uint32)
    seed_hi = np.zeros((s_rows, max_v, bv256.NLIMBS), dtype=np.uint32)
    dead = np.zeros(s_rows, dtype=bool)
    dead[n_states:] = True
    for s, bounds in enumerate(all_bounds):
        j = 0
        for var, lo, hi in bounds.values():
            if lo > hi:
                dead[s] = True
                break
            if var.tid in index:
                seed_idx[s, j] = index[var.tid]
                seed_lo[s, j] = _word(lo)
                seed_hi[s, j] = _word(hi)
                j += 1

    assert_idx = np.zeros((s_rows, max_a), dtype=np.int32)
    assert_mask = np.zeros((s_rows, max_a), dtype=bool)
    for s, assts in enumerate(assertion_sets):
        for j, t in enumerate(assts):
            assert_idx[s, j] = index[t.tid]
            assert_mask[s, j] = True

    return EncodedDAG(
        n, levels, jnp.asarray(init_lo), jnp.asarray(init_hi),
        jnp.asarray(seed_idx), jnp.asarray(seed_lo), jnp.asarray(seed_hi),
        dead, jnp.asarray(assert_idx), jnp.asarray(assert_mask),
        n_real=n_states,
        host=dict(terms=order, index=index, depth=depth, op=dev_op,
                  args=args, mask=mask_w, aux=aux, n_slots=n_slots),
    )


# ---------------------------------------------------------------------------
# device evaluation
# ---------------------------------------------------------------------------


def _smear(x):
    """All bits at/below the most significant set bit."""
    for s in (1, 2, 4, 8, 16, 32, 64, 128):
        x = x | bv256.shr(
            x, bv256.from_u32(jnp.full(x.shape[:-1], s, jnp.uint32))
        )
    return x


def _transfer_level(level, lo_tab, hi_tab, ops_present):
    """Interval transfer for one level's nodes, vectorized over
    (state, node): returns the level's (out_lo, out_hi) WITHOUT
    scattering (NOP/pad rows carry their current table value through).
    Shared by the plain forward evaluation below and the bidirectional
    product-domain kernel (ops/propagate.py), which meets these
    outputs against its refined tables instead of overwriting.

    `ops_present` is static: only the transfer functions for opcodes that
    actually occur in the level are traced, so small DAGs never pay the
    compile cost of the 512-bit product or the divmod shift-subtract
    loops."""
    op = level["op"]  # (W,)
    node = level["node"]
    argi = level["args"]
    mask = level["mask"]  # (W, 8) — broadcasts against (S, W, 8)
    aux = level["aux"]
    present = set(ops_present)

    def g(k):
        return lo_tab[:, argi[:, k]], hi_tab[:, argi[:, k]]  # (S, W, 8)

    alo, ahi = g(0)
    blo, bhi = g(1)
    batch = alo.shape[:-1]  # (S, W)

    top_lo = jnp.zeros_like(alo)
    top_hi = jnp.broadcast_to(mask, alo.shape)

    def iv(cond, lo, hi):
        """Select refined (lo, hi) where cond, else top."""
        c = cond[..., None]
        return jnp.where(c, lo, top_lo), jnp.where(c, hi, top_hi)

    def mk_bool(mf, mt):
        z = jnp.zeros(mf.shape + (bv256.NLIMBS,), jnp.uint32)
        return (
            z.at[..., 0].set(mf.astype(jnp.uint32)),
            z.at[..., 0].set(mt.astype(jnp.uint32)),
        )

    results = {}  # code -> (lo, hi)

    if ADD in present:
        s_lo, s_hi = bv256.add(alo, blo), bv256.add(ahi, bhi)
        add_ovf = bv256.ult(s_hi, ahi) | bv256.ugt(s_hi, top_hi)
        results[ADD] = iv(~add_ovf, s_lo, s_hi)
    if SUB in present:
        can_sub = ~bv256.ult(alo, bhi)  # alo >= bhi
        results[SUB] = iv(
            can_sub, bv256.sub(alo, bhi), bv256.sub(ahi, blo))
    if MUL in present:
        plo, phi = bv256.mul_full(ahi, bhi)
        ok = bv256.is_zero(phi) & ~bv256.ugt(plo, top_hi)
        results[MUL] = iv(ok, bv256.mul(alo, blo), plo)
    if UDIV in present:
        q1, _ = bv256.divmod_u(alo, bhi)
        q2, _ = bv256.divmod_u(ahi, blo)
        results[UDIV] = iv(~bv256.is_zero(blo), q1, q2)
    if UREM in present:
        # divisor may be 0 -> x % 0 = x (pass dividend interval)
        one = bv256.from_u32(jnp.ones(batch, jnp.uint32))
        bhi_m1 = bv256.sub(bhi, one)
        div_zero = bv256.is_zero(bhi)[..., None]
        urem_lo = jnp.where(div_zero, alo, top_lo)
        urem_hi = jnp.where(
            div_zero, ahi,
            jnp.where(~bv256.is_zero(blo)[..., None], bhi_m1, top_hi),
        )
        results[UREM] = (urem_lo, urem_hi)
    if BAND in present:
        results[BAND] = (
            top_lo, jnp.where(bv256.ult(ahi, bhi)[..., None], ahi, bhi))
    if BOR in present or BXOR in present:
        or_smear = _smear(ahi) | _smear(bhi)
        bor_hi = jnp.where(
            bv256.ult(or_smear, top_hi)[..., None], or_smear, top_hi
        )
        if BOR in present:
            results[BOR] = (
                jnp.where(bv256.ult(alo, blo)[..., None], blo, alo),
                bor_hi,
            )
        if BXOR in present:
            results[BXOR] = (top_lo, bor_hi)
    if BNOT in present:
        results[BNOT] = (bv256.sub(top_hi, ahi), bv256.sub(top_hi, alo))
    if NEG in present:
        # (-x) mod 2^w — (2^256 - x) & mask == (2^w - x) for 0 < x <= 2^w
        zero = jnp.zeros_like(alo)
        neg_exact = bv256.sub(zero, alo) & top_hi
        neg_lo_c = bv256.sub(zero, ahi) & top_hi
        neg_hi_c = bv256.sub(zero, alo) & top_hi
        a_const = bv256.eq(alo, ahi)
        a_pos = ~bv256.is_zero(alo)
        results[NEG] = (
            jnp.where(a_const[..., None], neg_exact,
                      jnp.where(a_pos[..., None], neg_lo_c, top_lo)),
            jnp.where(a_const[..., None], neg_exact,
                      jnp.where(a_pos[..., None], neg_hi_c, top_hi)),
        )
    if SHL in present:
        # constant in-range shift without overflow
        b_const = bv256.eq(blo, bhi)
        shl_hi_t = bv256.shl(ahi, bhi)
        shl_ok = (
            b_const
            & bv256.eq(bv256.shr(shl_hi_t, bhi), ahi)
            & ~bv256.ugt(shl_hi_t, top_hi)
        )
        results[SHL] = iv(shl_ok, bv256.shl(alo, blo), shl_hi_t)
    if LSHR in present:
        results[LSHR] = (bv256.shr(alo, bhi), bv256.shr(ahi, blo))
    if COPY in present:
        results[COPY] = (alo, ahi)
    if SEXT in present:
        # provably non-negative input passes through
        sext_ok = bv256.ult(ahi, jnp.broadcast_to(aux, alo.shape))
        results[SEXT] = iv(sext_ok, alo, ahi)
    if EXTRACT in present:
        # args[:,1]=lo_b, args[:,2]=hi_b immediates, aux = field mask
        ext_mask = jnp.broadcast_to(aux, alo.shape)
        lo_b = jnp.broadcast_to(
            bv256.from_u32(argi[:, 1].astype(jnp.uint32)), alo.shape
        )
        hi_b1 = jnp.broadcast_to(
            bv256.from_u32((argi[:, 2] + 1).astype(jnp.uint32)), alo.shape
        )
        same_high = bv256.eq(
            bv256.shr(alo, hi_b1), bv256.shr(ahi, hi_b1))
        slo_f = bv256.shr(alo, lo_b)
        shi_f = bv256.shr(ahi, lo_b)
        diff_ok = ~bv256.ugt(bv256.sub(shi_f, slo_f), ext_mask)
        slo_m = slo_f & ext_mask
        shi_m = shi_f & ext_mask
        ext_ok = same_high & diff_ok & ~bv256.ugt(slo_m, shi_m)
        # node width == field width, so top for EXTRACT is ext_mask == mask
        results[EXTRACT] = iv(ext_ok, slo_m, shi_m)
    if CONCAT2 in present:
        # (a << low_width) | b, bit-disjoint
        bw = jnp.broadcast_to(bv256.from_u32(aux[:, 0]), alo.shape)
        results[CONCAT2] = (
            bv256.shl(alo, bw) | blo, bv256.shl(ahi, bw) | bhi)
    if ITE in present:
        # ITE(cond, a, b): cond bool abs rides in limb 0 of arg0
        clo, chi = g(2)
        c_mf = (alo[..., 0] != 0)[..., None]
        c_mt = (ahi[..., 0] != 0)[..., None]
        results[ITE] = (
            jnp.where(
                ~c_mf, blo,
                jnp.where(~c_mt, clo,
                          jnp.where(bv256.ult(blo, clo)[..., None],
                                    blo, clo)),
            ),
            jnp.where(
                ~c_mf, bhi,
                jnp.where(~c_mt, chi,
                          jnp.where(bv256.ugt(bhi, chi)[..., None],
                                    bhi, chi)),
            ),
        )

    # comparisons -> bool abs
    if EQ in present:
        disjoint = bv256.ult(ahi, blo) | bv256.ult(bhi, alo)
        all_const = (
            bv256.eq(alo, ahi) & bv256.eq(blo, bhi) & bv256.eq(alo, blo))
        results[EQ] = mk_bool(~all_const, ~disjoint)
    if ULT in present:
        lt_must = bv256.ult(ahi, blo)
        lt_never = ~bv256.ult(alo, bhi)  # alo >= bhi
        results[ULT] = mk_bool(~lt_must, ~lt_never)
    if ULE in present:
        le_must = ~bv256.ugt(ahi, blo)  # ahi <= blo
        le_never = bv256.ugt(alo, bhi)
        results[ULE] = mk_bool(~le_must, ~le_never)
    # bool connectives (abs in limb 0)
    if present & {BAND2, BOR2, BNOT1, BXOR2, BITE}:
        amf, amt = alo[..., 0] != 0, ahi[..., 0] != 0
        bmf, bmt = blo[..., 0] != 0, bhi[..., 0] != 0
        if BAND2 in present:
            results[BAND2] = mk_bool(amf | bmf, amt & bmt)
        if BOR2 in present:
            results[BOR2] = mk_bool(amf & bmf, amt | bmt)
        if BNOT1 in present:
            results[BNOT1] = mk_bool(amt, amf)
        if BXOR2 in present:
            results[BXOR2] = mk_bool(
                (amt & bmt) | (amf & bmf), (amt & bmf) | (amf & bmt))
        if BITE in present:
            clo, chi = g(2)
            cmf, cmt = clo[..., 0] != 0, chi[..., 0] != 0
            results[BITE] = mk_bool(
                (amt & bmf) | (amf & cmf), (amt & bmt) | (amf & cmt))

    # select by opcode (pad/NOP rows keep their current value; the final
    # scatter drops pad rows via their out-of-range node index)
    cur_lo = lo_tab[:, jnp.minimum(node, lo_tab.shape[1] - 1)]
    cur_hi = hi_tab[:, jnp.minimum(node, hi_tab.shape[1] - 1)]
    out_lo, out_hi = cur_lo, cur_hi
    for code, (rlo, rhi) in results.items():
        m = (op == code)[None, :, None]
        out_lo = jnp.where(m, rlo, out_lo)
        out_hi = jnp.where(m, rhi, out_hi)
    return out_lo, out_hi


def _eval_level(level, lo_tab, hi_tab, ops_present):
    """One forward level: transfer + scatter-overwrite into the tables."""
    out_lo, out_hi = _transfer_level(level, lo_tab, hi_tab, ops_present)
    node = level["node"]
    lo_tab = lo_tab.at[:, node].set(out_lo, mode="drop")
    hi_tab = hi_tab.at[:, node].set(out_hi, mode="drop")
    return lo_tab, hi_tab


_eval_level_jit = jax.jit(_eval_level, static_argnames=("ops_present",))


def _run_tables(enc: EncodedDAG):
    """Seed the per-state interval tables, sweep every level, and
    return (lo_tab, hi_tab, rows, assert_idx, assert_mask, n_states) —
    the shared core of the feasibility and shadow evaluations."""
    n_states = enc.assert_idx.shape[0]
    n = enc.n_nodes
    # pad the state axis to a power of two so repeated batch sizes reuse
    # compiled level kernels (pad rows: no seeds, no live assertions)
    s_pad = _next_pow2(n_states)
    seed_idx = np.asarray(enc.seed_idx)
    seed_lo, seed_hi = np.asarray(enc.seed_lo), np.asarray(enc.seed_hi)
    assert_idx = np.asarray(enc.assert_idx)
    assert_mask = np.asarray(enc.assert_mask)
    if s_pad != n_states:
        extra = s_pad - n_states
        seed_idx = np.concatenate(
            [seed_idx,
             np.full((extra, seed_idx.shape[1]), n, dtype=np.int32)])
        seed_lo = np.concatenate(
            [seed_lo, np.zeros((extra,) + seed_lo.shape[1:], np.uint32)])
        seed_hi = np.concatenate(
            [seed_hi, np.zeros((extra,) + seed_hi.shape[1:], np.uint32)])
        assert_idx = np.concatenate(
            [assert_idx,
             np.zeros((extra, assert_idx.shape[1]), np.int32)])
        assert_mask = np.concatenate(
            [assert_mask,
             np.zeros((extra, assert_mask.shape[1]), bool)])

    shape = (s_pad,) + enc.init_lo.shape
    lo_tab = jnp.broadcast_to(enc.init_lo, shape)
    hi_tab = jnp.broadcast_to(enc.init_hi, shape)
    # scatter the per-state variable-bound seeds (index n == padded slot,
    # dropped by scatter mode)
    rows = jnp.arange(s_pad)[:, None]
    lo_tab = lo_tab.at[rows, seed_idx].set(seed_lo, mode="drop")
    hi_tab = hi_tab.at[rows, seed_idx].set(seed_hi, mode="drop")
    from ..support.telemetry import trace

    with trace.span("intervals.eval", states=n_states,
                    levels=len(enc.levels)):
        for level in enc.levels:
            arrays = {k: v
                      for k, v in level.items() if k != "ops_present"}
            lo_tab, hi_tab = trace.call_jit(
                "intervals.eval_level", _eval_level_jit,
                arrays, lo_tab, hi_tab,
                ops_present=level["ops_present"])
    return lo_tab, hi_tab, rows, assert_idx, assert_mask, n_states


def eval_feasible(enc: EncodedDAG) -> np.ndarray:
    """Returns (n_states,) bool: True = state may be feasible (keep)."""
    lo_tab, hi_tab, rows, assert_idx, assert_mask, n_states = (
        _run_tables(enc))
    may_true = hi_tab[rows, jnp.asarray(assert_idx)][..., 0] != 0  # (S, A)
    ok = np.asarray(jnp.all(may_true | ~jnp.asarray(assert_mask), axis=1))
    return (ok[:n_states] & ~enc.dead)[:enc.n_real]


def eval_shadow(enc: EncodedDAG):
    """(proved, rejected) bool arrays for a model-pinned encoding.

    proved: every live assertion is MUST-true (may_false bit 0) — with
    the shadow model pinned as point intervals, every completion of the
    pinned assignment satisfies the set, so the parent model extends to
    a witness (sound SAT proof). rejected: some live assertion is
    MUST-false — every completion falsifies it, so the shadow model
    cannot survive (says nothing about satisfiability by other models).
    Neither flag set = the abstraction lost precision; the caller
    decides by exact host term-eval."""
    lo_tab, hi_tab, rows, assert_idx, assert_mask, n_states = (
        _run_tables(enc))
    aidx = jnp.asarray(assert_idx)
    amask = jnp.asarray(assert_mask)
    may_false = lo_tab[rows, aidx][..., 0] != 0  # (S, A)
    may_true = hi_tab[rows, aidx][..., 0] != 0
    proved = np.asarray(jnp.all(~may_false | ~amask, axis=1))
    rejected = np.asarray(jnp.any(~may_true & amask, axis=1))
    return proved[:enc.n_real], rejected[:enc.n_real]


def prefilter_feasible(assertion_sets) -> np.ndarray:
    """Host entry: linearize + evaluate. Soundness: only provably-unsat
    states report False."""
    enc = linearize(assertion_sets)
    return eval_feasible(enc)


def shadow_prefilter(delta_sets, bv_values: Dict[str, int],
                     bool_values: Dict[str, bool]):
    """Device-batched model shadowing (tier 2 of the run-wide verdict
    cache, smt/solver/verdicts.py): evaluate each delta constraint set
    under one parent model pinned as point intervals. Returns
    (proved, rejected) per set — see eval_shadow for the semantics."""
    enc = linearize(delta_sets, pin_bv=bv_values, pin_bools=bool_values)
    return eval_shadow(enc)
