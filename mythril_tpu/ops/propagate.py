"""Device-resident bidirectional fixpoint propagation over EncodedDAG.

ops/intervals.py is half of the paper's "TPU-side interval/unit-
propagation pass": a single FORWARD sweep over one abstract domain
(256-bit unsigned intervals). This module is the other half — a
fixpoint kernel over a PRODUCT domain (intervals x known-bits) with
BACKWARD refinement seeded by pinning every asserted root TRUE, the
word-level combination PolySAT runs inside Z3 (interval/"tbv" domains
with mutual refinement; PAPERS.md) — here data-parallel across every
lane of a screening wave in one device dispatch.

Domains, per (state, node):
- interval [lo, hi] in the bv256 8xuint32 limb format (bool nodes keep
  the (may_false, may_true) abstraction in limb 0, exactly as the
  forward evaluator);
- known bits as (k0, k1): k0 bits MUST be 0, k1 bits MUST be 1.
  `k0 & k1 != 0` is a per-node contradiction. Bits above a node's
  width start in k0, so forcing an out-of-width bit refutes the lane.

One sweep = forward transfer (the interval functions from
ops/intervals._transfer_level plus known-bits transfer, MET against
the current tables — refinement is monotone, so contradictions never
erase), a table-wide interval<->known-bits exchange (shared leading
bits of [lo,hi] become known; k1 raises lo, ~k0 lowers hi), then the
backward pass: levels in reverse, applying inverse transfer functions
gated per-state on each parent's current abstraction — unit
propagation (`AND(a,b)=TRUE` forces both, `NOT`, `OR=FALSE`),
`EQ(x,c)=TRUE` pins x to c's full abstraction, ULT/ULE interval
tightening both ways, ADD/SUB interval inversion under no-wrap gates,
and known-bits inversion for AND/OR/XOR/NOT/SHL/LSHR/ZEXT/EXTRACT/
CONCAT. Sweeps iterate to a fixpoint (no table changed) or the
MTPU_PROPAGATE_SWEEPS cap.

Two sweep drivers share the level/round kernels:
- default: HOST-sequenced sweeps over per-level jit kernels with one
  device-reduced changed-flag readback per sweep — the level kernels
  bucket and reuse compilations exactly like the forward interval
  screen's (pow2 widths, canonical op keys), so a corpus of
  structurally-repeating DAGs pays seconds of compile total;
- MTPU_PROPAGATE_FUSE=1: the whole fixpoint as ONE kernel iterating
  under ``lax.while_loop``. Fewer dispatches per wave, but the fused program re-specializes per DAG structure — measured
  60-120 s XLA CPU compiles for even 4-level DAGs vs seconds for the
  per-level path, hence not the default.

Backward scatters write through per-level rounds with HOST-UNIQUE
targets (duplicate refiners of one node split across rounds, capped),
because combining two sound multi-limb interval candidates elementwise
is not sound; a dropped round beyond the cap only loses precision.

Soundness: every refinement is an implied consequence of the state's
asserted roots, so (a) a lane whose table holds an empty interval, a
`k0 & k1` conflict, or a (may_false=0, may_true=0) bool is UNSAT —
`propagate_kills`; (b) per-variable facts read back for SURVIVING
lanes (pinned constants, tightened bounds, forced bit masks) are
implied by the constraint set and may be asserted ahead of the real
constraints in a Z3 query without changing its verdict or model set —
`harvest()` records them in the run-wide verdict cache
(smt/solver/verdicts.py note_facts/absorb_bounds) where
batch.discharge / support/model.get_model assert them as hints
(`hinted_solves`) and tier-3 interval screens inherit the propagated
bounds. Gated by MTPU_PROPAGATE (default on; =0 restores the
interval-only screen bit-for-bit). See docs/propagation.md.
"""

import logging
import os
from collections import OrderedDict
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..smt import terms as T
from . import bv256
from .intervals import (
    ADD, BAND, BAND2, BNOT, BNOT1, BOR, BOR2, BXOR, CONCAT2, COPY, EQ,
    EXTRACT, ITE, LSHR, NOP, SHL, SUB, ULE, ULT,
    CANONICAL_KEYS, EncodedDAG, _next_pow2, _smear, _transfer_level,
    linearize,
)

log = logging.getLogger(__name__)

#: tri-state override for tests/bench (None = read MTPU_PROPAGATE)
FORCE: Optional[bool] = None


def enabled() -> bool:
    """The MTPU_PROPAGATE gate (default on). With the screen off every
    caller falls back to the forward interval-only path bit-for-bit."""
    if FORCE is not None:
        return bool(FORCE)
    return os.environ.get("MTPU_PROPAGATE", "1") != "0"


#: fixpoint sweep cap (each sweep = forward + exchange + backward;
#: both drivers exit early when no table changes)
SWEEP_CAP = int(os.environ.get("MTPU_PROPAGATE_SWEEPS", "6"))
#: level-count ceiling: beyond it the screen falls back to the
#: forward interval-only pass (a sweep costs levels x rounds
#: dispatches; very deep DAGs are rare and interval-screen well)
MAX_LEVELS = int(os.environ.get("MTPU_PROPAGATE_MAX_LEVELS", "96"))
#: opt-in fused lax.while_loop kernel (see module docstring)
FUSE = os.environ.get("MTPU_PROPAGATE_FUSE", "0") == "1"
#: duplicate-target backward rounds kept per level (further refiners of
#: an already-refined node are dropped — precision only, never
#: soundness)
MAX_BACK_ROUNDS = 4
#: harvested facts kept per surviving lane
FACT_CAP = 16

#: parent ops with inverse transfer functions, and which arg slots
#: each refines
_BACK_ROLES = {
    EQ: (0, 1), ULT: (0, 1), ULE: (0, 1),
    ADD: (0, 1), SUB: (0, 1),
    BAND: (0, 1), BOR: (0, 1), BXOR: (0, 1), BNOT: (0,),
    SHL: (0,), LSHR: (0,), COPY: (0,),
    EXTRACT: (0,), CONCAT2: (0, 1), ITE: (1, 2),
    BAND2: (0, 1), BOR2: (0, 1), BNOT1: (0,),
}
_BACK_COVER = tuple(sorted(_BACK_ROLES))


def _canonical_back_ops(ops: set) -> tuple:
    """Static compile key for a backward round's opcode set. EXACT,
    not a cover: tracing all 18 inverse rules per round multiplies the
    per-round program ~10x for rounds that typically carry 1-3 ops,
    and round op-sets repeat heavily across structurally-similar DAGs
    anyway (the EQ/ULT/BAND handful)."""
    return tuple(sorted(ops))


# ---------------------------------------------------------------------------
# host-side plan build
# ---------------------------------------------------------------------------


class Plan:
    """Device arrays + static compile keys for one encoded wave."""

    def __init__(self, arrays, statics):
        self.arrays = arrays
        self.statics = statics


def build_plan(enc: EncodedDAG) -> Optional[Plan]:
    """Backward tables + product-domain statics from the host arrays
    linearize() left on the EncodedDAG. None when the DAG is too deep
    for the whole-fixpoint kernel (caller falls back to the forward
    interval screen)."""
    host = enc.host
    if not host or not enc.levels or len(enc.levels) > MAX_LEVELS:
        return None
    order = host["terms"]
    dev_op = host["op"]
    args = host["args"]
    mask_w = host["mask"]
    aux = host["aux"]
    n = enc.n_nodes
    n_slots = host["n_slots"]

    isbool = np.zeros(n_slots, dtype=bool)
    numeric = np.zeros(n_slots, dtype=bool)
    wide = np.zeros(n_slots, dtype=bool)
    node_mask = np.zeros((n_slots, bv256.NLIMBS), dtype=np.uint32)
    for i, t in enumerate(order):
        if t.is_bool:
            isbool[i] = True
        elif not t.is_array and isinstance(t.width, int) and t.width >= 1:
            numeric[i] = True
            if t.width > 256:
                # topped cap: the table value is NOT the node's value,
                # so wide nodes keep full-range masks and are excluded
                # as backward targets (refining the cap is unsound)
                wide[i] = True
                node_mask[i] = 0xFFFFFFFF
            else:
                node_mask[i] = mask_w[i] if np.any(mask_w[i]) else \
                    bv256.int_to_limbs((1 << t.width) - 1)

    # initial known bits: out-of-width bits are known 0; point inits
    # (constants / pinned vars) are fully known
    init_lo = np.asarray(enc.init_lo)
    init_hi = np.asarray(enc.init_hi)
    init_k0 = np.zeros_like(init_lo)
    init_k1 = np.zeros_like(init_lo)
    num_nw = numeric & ~wide
    init_k0[num_nw] = ~node_mask[num_nw]
    point = num_nw & np.all(init_lo == init_hi, axis=-1)
    init_k1[point] = init_lo[point]
    init_k0[point] = ~init_lo[point]

    # per-level row flags for the forward meet
    levels_extra = []
    for level in enc.levels:
        node = np.asarray(level["node"])
        in_range = node < n_slots
        safe = np.where(in_range, node, 0)
        levels_extra.append(dict(
            lvl_bool=jnp.asarray(np.where(in_range, isbool[safe], False)),
            lvl_num=jnp.asarray(np.where(in_range, numeric[safe], False)),
        ))

    # backward rounds: entries (parent, role) grouped so each round's
    # targets are unique within its level
    back: List[list] = []
    back_ops: List[tuple] = []
    for level in enc.levels:
        node = np.asarray(level["node"])
        entries = []  # (parent, role, target, op)
        seen: Dict[int, int] = {}
        for i in node.tolist():
            if i >= n:
                continue
            op = int(dev_op[i])
            roles = _BACK_ROLES.get(op)
            if roles is None:
                continue
            for role in roles:
                tgt = int(args[i, role])
                if tgt >= n or wide[tgt]:
                    continue
                if not (numeric[tgt] or isbool[tgt]):
                    continue
                rnd = seen.get(tgt, 0)
                seen[tgt] = rnd + 1
                if rnd >= MAX_BACK_ROUNDS:
                    continue
                entries.append((rnd, i, role, tgt, op))
        rounds: List[dict] = []
        r_ops: List[tuple] = []
        n_rounds = max((e[0] for e in entries), default=-1) + 1
        for r in range(n_rounds):
            es = [e for e in entries if e[0] == r]
            w = _next_pow2(len(es)) if CANONICAL_KEYS else len(es)
            ops_set = set()
            parent = np.zeros(w, dtype=np.int32)
            role = np.zeros(w, dtype=np.int32)
            tgt = np.full(w, n_slots, dtype=np.int32)  # pad: dropped
            e_op = np.zeros(w, dtype=np.int32)  # pad: NOP
            for j, (_r, p, ro, tg, op) in enumerate(es):
                parent[j], role[j], tgt[j], e_op[j] = p, ro, tg, op
                ops_set.add(op)
            a_idx = args[np.minimum(parent, n - 1), 0].astype(np.int32)
            b_idx = args[np.minimum(parent, n - 1), 1].astype(np.int32)
            # EXTRACT stores its lo-bit immediate in args[:, 1]
            is_ext = e_op == EXTRACT
            lob = np.where(is_ext, b_idx, 0).astype(np.uint32)
            b_idx = np.where(is_ext, 0, b_idx).astype(np.int32)
            # ITE refines its arg-1/2 branches; the gate reads arg 0
            # (the condition), gathered through a_idx as usual
            c_idx = args[np.minimum(parent, n - 1), 2].astype(np.int32)
            rounds.append(dict(
                parent=jnp.asarray(np.minimum(parent, n_slots - 1)),
                a=jnp.asarray(np.minimum(a_idx, n_slots - 1)),
                b=jnp.asarray(np.minimum(b_idx, n_slots - 1)),
                c=jnp.asarray(np.minimum(c_idx, n_slots - 1)),
                tgt=jnp.asarray(tgt),
                tgt_c=jnp.asarray(np.minimum(tgt, n_slots - 1)),
                role=jnp.asarray(role),
                op=jnp.asarray(e_op),
                pmask=jnp.asarray(node_mask[np.minimum(parent, n_slots - 1)]),
                paux=jnp.asarray(aux[np.minimum(parent, n - 1)]),
                lob=jnp.asarray(lob),
                tnum=jnp.asarray(numeric[np.minimum(tgt, n_slots - 1)]
                                 & (tgt < n_slots)),
                tbool=jnp.asarray(isbool[np.minimum(tgt, n_slots - 1)]
                                  & (tgt < n_slots)),
            ))
            r_ops.append(_canonical_back_ops(ops_set))
        back.append(rounds)
        back_ops.append(tuple(r_ops))

    arrays = dict(
        init_lo=enc.init_lo, init_hi=enc.init_hi,
        init_k0=jnp.asarray(init_k0), init_k1=jnp.asarray(init_k1),
        numeric=jnp.asarray(numeric), isbool=jnp.asarray(isbool),
        seed_idx=enc.seed_idx, seed_lo=enc.seed_lo, seed_hi=enc.seed_hi,
        assert_idx=enc.assert_idx, assert_mask=enc.assert_mask,
        levels=tuple(
            dict({k: v for k, v in lvl.items() if k != "ops_present"},
                 **extra)
            for lvl, extra in zip(enc.levels, levels_extra)),
        back=tuple(tuple(rnds) for rnds in back),
    )
    statics = (
        SWEEP_CAP,
        tuple(lvl["ops_present"] for lvl in enc.levels),
        tuple(back_ops),
    )
    return Plan(arrays, statics)


# ---------------------------------------------------------------------------
# device kernel
# ---------------------------------------------------------------------------


def _max_n(a, b):
    return jnp.where(bv256.ult(a, b)[..., None], b, a)


def _min_n(a, b):
    return jnp.where(bv256.ult(b, a)[..., None], b, a)


def _meet(cur, new, isbool, isnum):
    """Meet a candidate (lo, hi, k0, k1) against the current value:
    bools intersect their (mf, mt) bits, numerics take max-lo / min-hi
    and union the known-bit masks. Non-numeric non-bool rows (arrays,
    pads) pass the current value through."""
    clo, chi, ck0, ck1 = cur
    nlo, nhi, nk0, nk1 = new
    b = isbool[..., None]
    m = isnum[..., None]
    lo = jnp.where(b, clo & nlo, jnp.where(m, _max_n(clo, nlo), clo))
    hi = jnp.where(b, chi & nhi, jnp.where(m, _min_n(chi, nhi), chi))
    k0 = jnp.where(m, ck0 | nk0, ck0)
    k1 = jnp.where(m, ck1 | nk1, ck1)
    return lo, hi, k0, k1


def _exchange_all(lo, hi, k0, k1, numeric):
    """Table-wide interval <-> known-bits refinement (numeric rows):
    shared leading bits of [lo, hi] become known; k1 is a sound lower
    bound and ~k0 a sound upper bound."""
    m = numeric[None, :, None]
    known = ~_smear(lo ^ hi)
    k1n = jnp.where(m, k1 | (lo & known), k1)
    k0n = jnp.where(m, k0 | (~lo & known), k0)
    lon = jnp.where(m, _max_n(lo, k1n), lo)
    hin = jnp.where(m, _min_n(hi, ~k0n), hi)
    return lon, hin, k0n, k1n


def _fwd_level(level, lo_tab, hi_tab, k0_tab, k1_tab, ops_present):
    """Forward product-domain transfer for one level, MET against the
    current tables (ops/intervals._transfer_level supplies the interval
    half; known-bits transfer below)."""
    out_lo, out_hi = _transfer_level(level, lo_tab, hi_tab, ops_present)
    op = level["op"]
    node = level["node"]
    argi = level["args"]
    mask = level["mask"]
    aux = level["aux"]
    present = set(ops_present)
    tmax = lo_tab.shape[1] - 1
    node_c = jnp.minimum(node, tmax)

    def g(tab, k):
        return tab[:, argi[:, k]]

    ak0, ak1 = g(k0_tab, 0), g(k1_tab, 0)
    bk0, bk1 = g(k0_tab, 1), g(k1_tab, 1)
    alo, ahi = g(lo_tab, 0), g(hi_tab, 0)
    blo, bhi = g(lo_tab, 1), g(hi_tab, 1)
    full_mask = jnp.broadcast_to(mask, ak0.shape)
    not_w = ~full_mask  # out-of-width bits (known 0 for w<=256 nodes)

    zero = jnp.zeros_like(ak0)
    results = {}  # code -> (k0, k1)

    if BAND in present:
        results[BAND] = ((ak0 | bk0) | not_w, ak1 & bk1 & full_mask)
    if BOR in present:
        results[BOR] = ((ak0 & bk0) | not_w, (ak1 | bk1) & full_mask)
    if BXOR in present:
        results[BXOR] = (
            (((ak0 & bk0) | (ak1 & bk1)) & full_mask) | not_w,
            ((ak0 & bk1) | (ak1 & bk0)) & full_mask,
        )
    if BNOT in present:
        results[BNOT] = ((ak1 & full_mask) | not_w, ak0 & full_mask)
    if COPY in present:
        results[COPY] = (ak0 | not_w, ak1 & full_mask)
    if SHL in present:
        b_const = bv256.eq(blo, bhi)[..., None]
        sk1 = bv256.shl(ak1, blo) & full_mask
        sk0 = (bv256.shl(ak0, blo) | ~bv256.shl(full_mask, blo)) \
            & full_mask
        results[SHL] = (
            jnp.where(b_const, sk0 | not_w, not_w),
            jnp.where(b_const, sk1, zero),
        )
    if LSHR in present:
        b_const = bv256.eq(blo, bhi)[..., None]
        surviving = bv256.shr(full_mask, blo)
        results[LSHR] = (
            jnp.where(b_const,
                      (bv256.shr(ak0, blo) & surviving) | ~surviving,
                      not_w),
            jnp.where(b_const, bv256.shr(ak1, blo) & surviving, zero),
        )
    if EXTRACT in present:
        field = jnp.broadcast_to(aux, ak0.shape)
        lo_b = jnp.broadcast_to(
            bv256.from_u32(argi[:, 1].astype(jnp.uint32)), ak0.shape)
        results[EXTRACT] = (
            (bv256.shr(ak0, lo_b) & field) | ~field,
            bv256.shr(ak1, lo_b) & field,
        )
    if CONCAT2 in present:
        bw = jnp.broadcast_to(bv256.from_u32(aux[:, 0]), ak0.shape)
        low = ~bv256.shl(bv256.ones_mask(bw.shape[:-1]), bw)
        results[CONCAT2] = (
            ((bv256.shl(ak0, bw) | (bk0 & low)) & full_mask) | not_w,
            (bv256.shl(ak1, bw) | (bk1 & low)) & full_mask,
        )
    if ADD in present or SUB in present:
        a_full = bv256.is_zero(~(ak0 | ak1))[..., None]
        b_full = bv256.is_zero(~(bk0 | bk1))[..., None]
        both = a_full & b_full
        if ADD in present:
            s = bv256.add(ak1, bk1) & full_mask
            results[ADD] = (jnp.where(both, ~s, zero),
                            jnp.where(both, s, zero))
        if SUB in present:
            d = bv256.sub(ak1, bk1) & full_mask
            results[SUB] = (jnp.where(both, ~d, zero),
                            jnp.where(both, d, zero))
    if ITE in present:
        c_mf = (alo[..., 0] != 0)[..., None]
        c_mt = (ahi[..., 0] != 0)[..., None]
        ck0, ck1 = g(k0_tab, 2), g(k1_tab, 2)
        results[ITE] = (
            jnp.where(~c_mf, bk0, jnp.where(~c_mt, ck0, bk0 & ck0)),
            jnp.where(~c_mf, bk1, jnp.where(~c_mt, ck1, bk1 & ck1)),
        )

    nk0, nk1 = zero, zero
    for code, (rk0, rk1) in results.items():
        m = (op == code)[None, :, None]
        nk0 = jnp.where(m, rk0, nk0)
        nk1 = jnp.where(m, rk1, nk1)

    # known-bits refutation of EQ: a bit one side must set and the
    # other must clear makes the equality MUST-false (the rigged
    # `x & 0xff == 0x42  /\  x & 0xff == 0x43` shape dies here after
    # the backward pass pins the shared masked subterm both ways)
    if EQ in present:
        conflict = ~bv256.is_zero((ak1 & bk0) | (ak0 & bk1))
        m = (op == EQ)[None, :] & conflict
        out_hi = out_hi.at[..., 0].set(
            jnp.where(m, 0, out_hi[..., 0]))

    cur = (lo_tab[:, node_c], hi_tab[:, node_c],
           k0_tab[:, node_c], k1_tab[:, node_c])
    lvl_bool = level["lvl_bool"][None, :]
    lvl_num = level["lvl_num"][None, :]
    flo, fhi, fk0, fk1 = _meet(cur, (out_lo, out_hi, nk0, nk1),
                               lvl_bool, lvl_num)
    lo_tab = lo_tab.at[:, node].set(flo, mode="drop")
    hi_tab = hi_tab.at[:, node].set(fhi, mode="drop")
    k0_tab = k0_tab.at[:, node].set(fk0, mode="drop")
    k1_tab = k1_tab.at[:, node].set(fk1, mode="drop")
    return lo_tab, hi_tab, k0_tab, k1_tab


def _back_round(rnd, lo_tab, hi_tab, k0_tab, k1_tab, ops_present):
    """One backward scatter round: inverse transfer functions keyed on
    the parent opcode, gated per state on the parent's current
    abstraction, MET into targets (host-unique within the round)."""
    present = set(ops_present)
    op = rnd["op"]
    role = rnd["role"]
    S = lo_tab.shape[0]
    rows = jnp.arange(S)[:, None]

    def g(tab, idx):
        return tab[:, idx]

    p, ai, bi, ci = rnd["parent"], rnd["a"], rnd["b"], rnd["c"]
    rlo, rhi = g(lo_tab, p), g(hi_tab, p)
    rk0, rk1 = g(k0_tab, p), g(k1_tab, p)
    alo, ahi = g(lo_tab, ai), g(hi_tab, ai)
    ak0, ak1 = g(k0_tab, ai), g(k1_tab, ai)
    blo, bhi = g(lo_tab, bi), g(hi_tab, bi)
    bk0, bk1 = g(k0_tab, bi), g(k1_tab, bi)
    cur = (g(lo_tab, rnd["tgt_c"]), g(hi_tab, rnd["tgt_c"]),
           g(k0_tab, rnd["tgt_c"]), g(k1_tab, rnd["tgt_c"]))
    cur_lo, cur_hi, cur_k0, cur_k1 = cur

    pmask = jnp.broadcast_to(rnd["pmask"], rlo.shape)
    r0 = (role == 0)[None, :]
    r1 = (role == 1)[None, :]
    r2 = (role == 2)[None, :]
    # sibling of the refined arg (binary numeric rules)
    slo = jnp.where(r0[..., None], blo, alo)
    shi = jnp.where(r0[..., None], bhi, ahi)
    sk0 = jnp.where(r0[..., None], bk0, ak0)
    sk1 = jnp.where(r0[..., None], bk1, ak1)

    mtrue = (rlo[..., 0] == 0)   # parent bool cannot be false
    mfalse = (rhi[..., 0] == 0)  # parent bool cannot be true
    one = bv256.from_u32(jnp.ones(rlo.shape[:-1], jnp.uint32))
    zero = jnp.zeros_like(rlo)
    empty_lo, empty_hi = one, zero  # meet target -> empty interval

    results = {}  # code -> (lo, hi, k0, k1) candidate (vs cur default)

    def sel(c, x, y):
        return jnp.where(c[..., None] if c.ndim < x.ndim else c, x, y)

    if EQ in present:
        gate = mtrue
        results[EQ] = (
            sel(gate, slo, cur_lo), sel(gate, shi, cur_hi),
            sel(gate, sk0, cur_k0), sel(gate, sk1, cur_k1),
        )
    if ULT in present or ULE in present:
        for code in (ULT, ULE):
            if code not in present:
                continue
            strict = code == ULT
            n_lo, n_hi = cur_lo, cur_hi
            if strict:
                # a < b: a <= b.hi-1, b >= a.lo+1; !(a < b): a >= b.lo,
                # b <= a.hi
                bhi_m1 = bv256.sub(bhi, one)
                alo_p1 = bv256.add(alo, one)
                t0 = mtrue & ~bv256.is_zero(bhi)
                t1 = mtrue & ~bv256.is_zero(alo_p1)
                n_hi = sel(t0 & r0, bhi_m1, n_hi)
                n_lo = sel(mfalse & r0, blo, n_lo)
                n_lo = sel(t1 & r1, alo_p1, n_lo)
                n_hi = sel(mfalse & r1, ahi, n_hi)
            else:
                # a <= b: a <= b.hi, b >= a.lo; !(a <= b): a >= b.lo+1,
                # b <= a.hi-1
                blo_p1 = bv256.add(blo, one)
                ahi_m1 = bv256.sub(ahi, one)
                n_hi = sel(mtrue & r0, bhi, n_hi)
                n_lo = sel((mfalse & ~bv256.is_zero(blo_p1)) & r0,
                           blo_p1, n_lo)
                n_lo = sel(mtrue & r1, alo, n_lo)
                n_hi = sel((mfalse & ~bv256.is_zero(ahi)) & r1,
                           ahi_m1, n_hi)
            results[code] = (n_lo, n_hi, cur_k0, cur_k1)
    if ADD in present:
        s_hi = bv256.add(ahi, bhi)
        no_ovf = ~(bv256.ult(s_hi, ahi) | bv256.ugt(s_hi, pmask))
        ok_lo = ~bv256.ult(rlo, shi)
        ok_hi = ~bv256.ult(rhi, slo)
        c_lo = jnp.where(ok_lo[..., None], bv256.sub(rlo, shi), zero)
        c_hi = bv256.sub(rhi, slo)
        n_lo = sel(no_ovf, jnp.where(ok_hi[..., None], c_lo, empty_lo),
                   cur_lo)
        n_hi = sel(no_ovf, jnp.where(ok_hi[..., None], c_hi, empty_hi),
                   cur_hi)
        results[ADD] = (n_lo, n_hi, cur_k0, cur_k1)
    if SUB in present:
        # forward-exact gate: a >= b guaranteed (alo >= bhi)
        gate = ~bv256.ult(alo, bhi)
        # role 0 (a = r + b) under add no-wrap; role 1 (b = a - r)
        s2 = bv256.add(rhi, bhi)
        no_ovf = ~(bv256.ult(s2, rhi) | bv256.ugt(s2, pmask))
        a_lo, a_hi = bv256.add(rlo, blo), s2
        ok_lo = ~bv256.ult(alo, rhi)
        ok_hi = ~bv256.ult(ahi, rlo)
        b_lo = jnp.where(ok_lo[..., None], bv256.sub(alo, rhi), zero)
        b_hi = bv256.sub(ahi, rlo)
        b_lo = jnp.where(ok_hi[..., None], b_lo, empty_lo)
        b_hi = jnp.where(ok_hi[..., None], b_hi, empty_hi)
        n_lo = sel(gate & no_ovf & r0, a_lo,
                   sel(gate & r1, b_lo, cur_lo))
        n_hi = sel(gate & no_ovf & r0, a_hi,
                   sel(gate & r1, b_hi, cur_hi))
        results[SUB] = (n_lo, n_hi, cur_k0, cur_k1)
    if BAND in present:
        results[BAND] = (cur_lo, cur_hi,
                         cur_k0 | (rk0 & sk1),
                         cur_k1 | (rk1 & pmask))
    if BOR in present:
        results[BOR] = (cur_lo, cur_hi,
                        cur_k0 | (rk0 & pmask),
                        cur_k1 | (rk1 & sk0))
    if BXOR in present:
        results[BXOR] = (
            cur_lo, cur_hi,
            cur_k0 | (((rk0 & sk0) | (rk1 & sk1)) & pmask),
            cur_k1 | (((rk1 & sk0) | (rk0 & sk1)) & pmask),
        )
    if BNOT in present:
        results[BNOT] = (cur_lo, cur_hi,
                         cur_k0 | (rk1 & pmask),
                         cur_k1 | (rk0 & pmask))
    if SHL in present:
        b_const = bv256.eq(blo, bhi)[..., None]
        surviving = bv256.shr(pmask, blo)
        results[SHL] = (
            cur_lo, cur_hi,
            jnp.where(b_const,
                      cur_k0 | (bv256.shr(rk0, blo) & surviving),
                      cur_k0),
            jnp.where(b_const,
                      cur_k1 | (bv256.shr(rk1, blo) & surviving),
                      cur_k1),
        )
    if LSHR in present:
        b_const = bv256.eq(blo, bhi)[..., None]
        results[LSHR] = (
            cur_lo, cur_hi,
            jnp.where(b_const,
                      cur_k0 | (bv256.shl(rk0, blo) & pmask), cur_k0),
            jnp.where(b_const,
                      cur_k1 | (bv256.shl(rk1, blo) & pmask), cur_k1),
        )
    if COPY in present:
        results[COPY] = (_max_n(cur_lo, rlo), _min_n(cur_hi, rhi),
                         cur_k0 | rk0, cur_k1 | rk1)
    if EXTRACT in present:
        field = jnp.broadcast_to(rnd["paux"], rlo.shape)
        lo_b = jnp.broadcast_to(bv256.from_u32(rnd["lob"]), rlo.shape)
        results[EXTRACT] = (
            cur_lo, cur_hi,
            cur_k0 | bv256.shl(rk0 & field, lo_b),
            cur_k1 | bv256.shl(rk1 & field, lo_b),
        )
    if CONCAT2 in present:
        bw = jnp.broadcast_to(bv256.from_u32(rnd["paux"][:, 0]),
                              rlo.shape)
        hi_surv = bv256.shr(pmask, bw)
        low = ~bv256.shl(bv256.ones_mask(bw.shape[:-1]), bw)
        results[CONCAT2] = (
            cur_lo, cur_hi,
            cur_k0 | jnp.where(r0[..., None],
                               bv256.shr(rk0, bw) & hi_surv,
                               rk0 & low),
            cur_k1 | jnp.where(r0[..., None],
                               bv256.shr(rk1, bw) & hi_surv,
                               rk1 & low),
        )
    if ITE in present:
        # args = (cond, then, else): cond's bool abs gathered via a;
        # a known branch equals the parent
        c_t = (alo[..., 0] == 0)  # cond must-true
        c_f = (ahi[..., 0] == 0)  # cond must-false
        gate = (c_t & r1) | (c_f & r2)
        results[ITE] = (
            sel(gate, rlo, cur_lo), sel(gate, rhi, cur_hi),
            sel(gate, rk0, cur_k0), sel(gate, rk1, cur_k1),
        )
    # bool unit propagation: the sibling's abs gathered like the
    # numeric rules (limb 0 carries (mf, mt))
    s_mt = (slo[..., 0] == 0)  # sibling must-true
    s_mf = (shi[..., 0] == 0)  # sibling must-false
    if BAND2 in present:
        f_true = mtrue                  # AND true -> target true
        f_false = mfalse & s_mt         # AND false, sibling true
        results[BAND2] = (
            cur_lo.at[..., 0].set(
                jnp.where(f_true, 0, cur_lo[..., 0])),
            cur_hi.at[..., 0].set(
                jnp.where(f_false, 0, cur_hi[..., 0])),
            cur_k0, cur_k1,
        )
    if BOR2 in present:
        f_false = mfalse                # OR false -> target false
        f_true = mtrue & s_mf           # OR true, sibling false
        results[BOR2] = (
            cur_lo.at[..., 0].set(
                jnp.where(f_true, 0, cur_lo[..., 0])),
            cur_hi.at[..., 0].set(
                jnp.where(f_false, 0, cur_hi[..., 0])),
            cur_k0, cur_k1,
        )
    if BNOT1 in present:
        results[BNOT1] = (
            cur_lo.at[..., 0].set(
                jnp.where(mfalse, 0, cur_lo[..., 0])),
            cur_hi.at[..., 0].set(
                jnp.where(mtrue, 0, cur_hi[..., 0])),
            cur_k0, cur_k1,
        )

    n_lo, n_hi, n_k0, n_k1 = cur
    for code, (xlo, xhi, xk0, xk1) in results.items():
        m = (op == code)[None, :, None]
        n_lo = jnp.where(m, xlo, n_lo)
        n_hi = jnp.where(m, xhi, n_hi)
        n_k0 = jnp.where(m, xk0, n_k0)
        n_k1 = jnp.where(m, xk1, n_k1)

    f_lo, f_hi, f_k0, f_k1 = _meet(
        cur, (n_lo, n_hi, n_k0, n_k1),
        rnd["tbool"][None, :], rnd["tnum"][None, :])
    tgt = rnd["tgt"]
    lo_tab = lo_tab.at[rows, tgt].set(f_lo, mode="drop")
    hi_tab = hi_tab.at[rows, tgt].set(f_hi, mode="drop")
    k0_tab = k0_tab.at[rows, tgt].set(f_k0, mode="drop")
    k1_tab = k1_tab.at[rows, tgt].set(f_k1, mode="drop")
    return lo_tab, hi_tab, k0_tab, k1_tab


def _init_tables(arrays):
    """Seed the per-state product tables and pin every asserted root
    TRUE (may_false := 0 — the unit-propagation seed; pad assertion
    slots scatter out of range and drop)."""
    init_lo = arrays["init_lo"]
    seed_idx = arrays["seed_idx"]
    S = seed_idx.shape[0]
    Tn = init_lo.shape[0]
    rows = jnp.arange(S)[:, None]
    shape = (S,) + init_lo.shape
    lo = jnp.broadcast_to(init_lo, shape)
    hi = jnp.broadcast_to(arrays["init_hi"], shape)
    k0 = jnp.broadcast_to(arrays["init_k0"], shape)
    k1 = jnp.broadcast_to(arrays["init_k1"], shape)
    lo = lo.at[rows, seed_idx].set(arrays["seed_lo"], mode="drop")
    hi = hi.at[rows, seed_idx].set(arrays["seed_hi"], mode="drop")
    aidx = jnp.where(arrays["assert_mask"], arrays["assert_idx"], Tn)
    lo = lo.at[rows, aidx, 0].set(0, mode="drop")
    return lo, hi, k0, k1


def _verdicts(arrays, lo, hi, k0, k1):
    """(ok, contra): a lane dies on a bit forced both ways, an empty
    numeric interval, a bool pinned neither-true-nor-false, or a
    must-false assertion."""
    numeric, isbool = arrays["numeric"], arrays["isbool"]
    S = lo.shape[0]
    rows = jnp.arange(S)[:, None]
    bitconf = ~bv256.is_zero(k0 & k1)
    emptyiv = bv256.ult(hi, lo)
    boolempty = (lo[..., 0] == 0) & (hi[..., 0] == 0)
    conf = (numeric[None, :] & (bitconf | emptyiv)) \
        | (isbool[None, :] & boolempty)
    contra = jnp.any(conf, axis=1)
    amask = arrays["assert_mask"]
    may_true = hi[rows, arrays["assert_idx"]][..., 0] != 0
    ok = jnp.all(may_true | ~amask, axis=1) & ~contra
    return ok, contra


_init_tables_jit = jax.jit(_init_tables)
_verdicts_jit = jax.jit(_verdicts)
_fwd_level_jit = jax.jit(_fwd_level, static_argnames=("ops_present",))
_back_round_jit = jax.jit(_back_round, static_argnames=("ops_present",))
_exchange_all_jit = jax.jit(_exchange_all)


def _changed(a, b):
    got = False
    for x, y in zip(a, b):
        got = got | jnp.any(x != y)
    return got


_changed_jit = jax.jit(_changed)


def _run_host(arrays, statics):
    """Default driver: host-sequenced sweeps over the per-level jit
    kernels (compilations bucket and reuse across DAGs exactly like
    the forward interval screen's), one changed-flag readback per
    sweep for the fixpoint early exit. Level-kernel calls route
    through trace.call_jit so a cold XLA compile shows up as a
    distinct `xla.compile` span, not an anonymously slow sweep (the
    BENCH_r06 artifact class — docs/observability.md); with tracing
    off call_jit is a direct call."""
    from ..support.telemetry import trace

    cap, level_ops, back_ops = statics
    levels, back = arrays["levels"], arrays["back"]
    numeric = arrays["numeric"]
    tabs = _init_tables_jit(
        {k: v for k, v in arrays.items() if k not in ("levels", "back")})
    sweeps = 0
    for _ in range(cap):
        prev = tabs
        lo, hi, k0, k1 = tabs
        for li, level in enumerate(levels):
            lo, hi, k0, k1 = trace.call_jit(
                "propagate.fwd_level", _fwd_level_jit,
                level, lo, hi, k0, k1, ops_present=level_ops[li])
        lo, hi, k0, k1 = _exchange_all_jit(lo, hi, k0, k1, numeric)
        for li in range(len(levels) - 1, -1, -1):
            for ri, rnd in enumerate(back[li]):
                lo, hi, k0, k1 = trace.call_jit(
                    "propagate.back_round", _back_round_jit,
                    rnd, lo, hi, k0, k1,
                    ops_present=back_ops[li][ri])
        tabs = _exchange_all_jit(lo, hi, k0, k1, numeric)
        sweeps += 1
        if not bool(_changed_jit(prev, tabs)):
            break
    lo, hi, k0, k1 = tabs
    core = {k: v for k, v in arrays.items()
            if k not in ("levels", "back")}
    ok, contra = _verdicts_jit(core, lo, hi, k0, k1)
    return lo, hi, k0, k1, ok, contra, sweeps


def _fixpoint(arrays, statics):
    """Fused driver (MTPU_PROPAGATE_FUSE=1): the whole fixpoint as one
    kernel iterating under lax.while_loop — one dispatch per wave, at
    the price of per-DAG-structure specialization (see module
    docstring for the measured compile cost tradeoff)."""
    cap, level_ops, back_ops = statics
    levels, back = arrays["levels"], arrays["back"]
    numeric = arrays["numeric"]
    core = {k: v for k, v in arrays.items()
            if k not in ("levels", "back")}
    tabs = _init_tables(core)

    def sweep(tabs):
        lo, hi, k0, k1 = tabs
        for li, level in enumerate(levels):
            lo, hi, k0, k1 = _fwd_level(level, lo, hi, k0, k1,
                                        level_ops[li])
        lo, hi, k0, k1 = _exchange_all(lo, hi, k0, k1, numeric)
        for li in range(len(levels) - 1, -1, -1):
            for ri, rnd in enumerate(back[li]):
                lo, hi, k0, k1 = _back_round(rnd, lo, hi, k0, k1,
                                             back_ops[li][ri])
        return _exchange_all(lo, hi, k0, k1, numeric)

    def cond(carry):
        _lo, _hi, _k0, _k1, i, changed = carry
        return changed & (i < cap)

    def body(carry):
        lo, hi, k0, k1, i, _ = carry
        nlo, nhi, nk0, nk1 = sweep((lo, hi, k0, k1))
        return (nlo, nhi, nk0, nk1, i + 1,
                _changed((lo, hi, k0, k1), (nlo, nhi, nk0, nk1)))

    lo, hi, k0, k1, sweeps, _ = jax.lax.while_loop(
        cond, body, tabs + (jnp.int32(0), jnp.array(True)))
    ok, contra = _verdicts(core, lo, hi, k0, k1)
    return lo, hi, k0, k1, ok, contra, sweeps


_fixpoint_jit = jax.jit(_fixpoint, static_argnames=("statics",))


# ---------------------------------------------------------------------------
# harvest: learned facts for surviving lanes
# ---------------------------------------------------------------------------

#: free BV variables per constraint term, memoized process-wide by tid
#: (terms are interned, so the support set is immutable)
_SUPPORT_CACHE: Dict[int, frozenset] = {}


def _free_bv_vars(t: "T.Term") -> frozenset:
    got = _SUPPORT_CACHE.get(t.tid)
    if got is None:
        out, seen, stack = set(), set(), [t]
        while stack:
            cur = stack.pop()
            if cur.tid in seen:
                continue
            seen.add(cur.tid)
            if cur.op == T.BV_VAR:
                out.add(cur.tid)
            stack.extend(cur.args)
        if len(_SUPPORT_CACHE) > 1 << 20:
            _SUPPORT_CACHE.clear()
        got = _SUPPORT_CACHE[t.tid] = frozenset(out)
    return got


def _limbs_to_ints(arr: np.ndarray) -> np.ndarray:
    """(..., 8) uint32 -> object-dtype python ints, vectorized."""
    out = arr[..., 0].astype(object)
    for i in range(1, bv256.NLIMBS):
        out = out | (arr[..., i].astype(object) << (32 * i))
    return out


def harvest(enc: EncodedDAG, lo, hi, k0, k1, keep: np.ndarray):
    """Per-state learned facts for surviving lanes, as
    ``{state index: (fact terms, {var_tid: (var, lo, hi)})}``.

    A fact is an implied consequence of the state's asserted set:
    a variable pinned to a constant (``v == c``), a bound strictly
    tighter than the syntactic seed (``c <= v`` / ``v <= c``), or a
    forced bit mask beyond what the interval already implies
    (``v & known == ones``). Sound to assert ahead of the real
    constraints in any query over the same set."""
    order = enc.host["terms"]
    var_rows = [i for i, t in enumerate(order)
                if t.op == T.BV_VAR and isinstance(t.width, int)
                and 1 <= t.width <= 256]
    if not var_rows:
        return {}
    vi = jnp.asarray(np.asarray(var_rows, dtype=np.int32))
    vlo = _limbs_to_ints(np.asarray(lo[:, vi]))
    vhi = _limbs_to_ints(np.asarray(hi[:, vi]))
    vk0 = _limbs_to_ints(np.asarray(k0[:, vi]))
    vk1 = _limbs_to_ints(np.asarray(k1[:, vi]))

    # the syntactic seed bounds, to emit only STRICTLY tighter facts
    seed_idx = np.asarray(enc.seed_idx)
    seed_lo = _limbs_to_ints(np.asarray(enc.seed_lo))
    seed_hi = _limbs_to_ints(np.asarray(enc.seed_hi))
    row_of = {r: j for j, r in enumerate(var_rows)}

    out = {}
    for s in range(enc.n_real):
        if not keep[s]:
            continue
        support = set()
        for t in _state_terms(enc, s):
            support |= _free_bv_vars(t)
        if not support:
            continue
        seeds = {}
        for v in range(seed_idx.shape[1]):
            j = row_of.get(int(seed_idx[s, v]))
            if j is not None:
                seeds[j] = (int(seed_lo[s, v]), int(seed_hi[s, v]))
        facts: List["T.Term"] = []
        bounds: Dict[int, tuple] = {}
        for j, r in enumerate(var_rows):
            t = order[r]
            if t.tid not in support:
                continue
            w = t.width
            m = (1 << w) - 1
            lo_i, hi_i = int(vlo[s, j]), int(vhi[s, j])
            k0_i, k1_i = int(vk0[s, j]), int(vk1[s, j])
            if lo_i > hi_i or (k0_i & k1_i):
                continue  # contradictory lane rows never become facts
            slo, shi = seeds.get(j, (0, m))
            if lo_i > slo or hi_i < shi:
                bounds[t.tid] = (t, lo_i, hi_i)
            if len(facts) >= FACT_CAP:
                continue
            if lo_i == hi_i:
                facts.append(T.mk_eq(t, T.bv_const(lo_i & m, w)))
                continue
            if lo_i > slo:
                facts.append(T.mk_ule(T.bv_const(lo_i & m, w), t))
            if hi_i < shi and len(facts) < FACT_CAP:
                facts.append(T.mk_ule(t, T.bv_const(hi_i & m, w)))
            known = (k0_i | k1_i) & m
            # skip bit masks the interval already implies (the shared
            # leading bits of [lo, hi])
            span = lo_i ^ hi_i
            lead = ~((1 << span.bit_length()) - 1) & m
            if known & ~lead and len(facts) < FACT_CAP:
                facts.append(T.mk_eq(
                    T.mk_and(t, T.bv_const(known, w)),
                    T.bv_const(k1_i & m & known, w)))
        if facts or bounds:
            out[s] = (facts, bounds)
    return out


def _state_terms(enc: EncodedDAG, s: int):
    """The raw assertion terms of state s (host assert table rows)."""
    idx = np.asarray(enc.assert_idx)[s]
    mask = np.asarray(enc.assert_mask)[s]
    order = enc.host["terms"]
    return [order[int(i)] for i, live in zip(idx, mask) if live]


# ---------------------------------------------------------------------------
# host entry points
# ---------------------------------------------------------------------------


def _inject_static_seeds(enc: EncodedDAG) -> None:
    """Meet the static storage-ITE candidate hulls
    (analysis/static_pass/deps.static_seed_rows) into the encoding's
    shared init tables BEFORE the fixpoint/interval screen runs: the
    hull is implied by the term structure (an ITE's value is always
    one of its leaves), so the tighter seed removes only states the
    term provably cannot reach — same soundness contract as the
    syntactic bound seeds. No-shape change, so jit variants are
    untouched. Counted as ``static_facts_seeded``."""
    try:
        from ..analysis.static_pass import deps as static_deps

        rows = static_deps.static_seed_rows(enc)
    except Exception:
        return
    if not rows:
        return
    try:
        from .intervals import _word

        init_lo = np.asarray(enc.init_lo).copy()
        init_hi = np.asarray(enc.init_hi).copy()
        for i, (lo, hi) in rows.items():
            if i >= init_lo.shape[0]:
                continue
            init_lo[i] = _word(lo)
            init_hi[i] = _word(hi)
        enc.init_lo = jnp.asarray(init_lo)
        enc.init_hi = jnp.asarray(init_hi)
        from ..smt.solver.solver_statistics import SolverStatistics

        SolverStatistics().bump(static_facts_seeded=len(rows))
    except Exception:  # a seed, never an error path
        log.debug("static seed injection failed", exc_info=True)


def run(enc: EncodedDAG):
    """(keep, tables) for an encoded wave, or None when the plan falls
    outside the whole-kernel envelope (caller uses the forward interval
    screen on the SAME encoding)."""
    plan = build_plan(enc)
    if plan is None:
        return None
    from ..support.telemetry import trace

    driver = _fixpoint_jit if FUSE else _run_host
    with trace.span("propagate.fixpoint", states=enc.n_real,
                    fused=FUSE) as sp:
        lo, hi, k0, k1, ok, _contra, sweeps = driver(
            plan.arrays, plan.statics)
        sp.set(sweeps=int(sweeps))
    keep = np.asarray(ok)[:enc.n_real] & ~np.asarray(
        enc.dead[:enc.n_real])
    return keep, (lo, hi, k0, k1), int(sweeps)


def prefilter_feasible(assertion_sets: Sequence[Sequence]) -> np.ndarray:
    """Drop-in for ops/intervals.prefilter_feasible with the product
    domain, bidirectional sweeps, UNSAT recording and fact harvest.
    Sound: only provably-unsat states report False."""
    from ..smt.solver.solver_statistics import SolverStatistics

    sets = [[getattr(t, "raw", t) for t in s] for s in assertion_sets]
    enc = linearize(sets)
    _inject_static_seeds(enc)
    got = run(enc)
    if got is None:
        from .intervals import eval_feasible

        return eval_feasible(enc)
    keep, (lo, hi, k0, k1), sweeps = got
    ss = SolverStatistics()
    kills = int(len(keep) - int(keep.sum()))
    ss.bump(propagate_kills=kills, propagate_sweeps=sweeps)

    # close the loop: killed sets are sound run-wide UNSAT proofs;
    # surviving sets bank their learned facts as solver hints and
    # propagated bounds for tier-3 inheritance
    try:
        from ..smt.solver import verdicts as verdict_mod

        vc = verdict_mod.cache()
    except Exception:
        vc = None
    if vc is not None:
        try:
            n_facts = 0
            for s, ok_s in enumerate(keep):
                tids = tuple(t.tid for t in sets[s])
                if not tids:
                    continue
                if not ok_s:
                    vc.record(tids, verdict_mod.UNSAT)
            for s, (facts, bounds) in harvest(
                    enc, lo, hi, k0, k1, keep).items():
                tids = tuple(t.tid for t in sets[s])
                if not tids:
                    continue
                if facts:
                    vc.note_facts(tids, facts)
                    n_facts += len(facts)
                if bounds:
                    vc.absorb_bounds(tids, bounds)
            if n_facts:
                ss.bump(facts_harvested=n_facts)
        except Exception:  # a screen, never an error path
            log.debug("propagation harvest failed", exc_info=True)
    return keep


def abstraction_sets(assertion_sets: Sequence[Sequence]
                     ) -> Optional[List[Optional[Dict[int, tuple]]]]:
    """Per-set variable abstractions from the product-domain fixpoint:
    ``{var_tid: (lo, hi, k0, k1)}`` for every free BV variable of each
    assertion set, with the interval<->known-bits exchange already
    applied. A set the fixpoint refutes maps to ``None`` (bottom).
    Returns ``None`` when the plan falls outside the kernel envelope —
    callers fall back to host bounds (the lane-merge subsumption tier,
    laser/merge.py, falls back to the verdict cache's tier-3 bounds,
    which absorb these same tables when the fork screen ran)."""
    sets = [[getattr(t, "raw", t) for t in s] for s in assertion_sets]
    enc = linearize(sets)
    got = run(enc)
    if got is None:
        return None
    keep, (lo, hi, k0, k1), _sweeps = got
    order = enc.host["terms"]
    var_rows = [i for i, t in enumerate(order)
                if t.op == T.BV_VAR and isinstance(t.width, int)
                and 1 <= t.width <= 256]
    if not var_rows:
        return [None if not keep[s] else {}
                for s in range(enc.n_real)]
    vi = jnp.asarray(np.asarray(var_rows, dtype=np.int32))
    vlo = _limbs_to_ints(np.asarray(lo[:, vi]))
    vhi = _limbs_to_ints(np.asarray(hi[:, vi]))
    vk0 = _limbs_to_ints(np.asarray(k0[:, vi]))
    vk1 = _limbs_to_ints(np.asarray(k1[:, vi]))
    out: List[Optional[Dict[int, tuple]]] = []
    for s in range(enc.n_real):
        if not keep[s]:
            out.append(None)
            continue
        support = set()
        for t in _state_terms(enc, s):
            support |= _free_bv_vars(t)
        d: Dict[int, tuple] = {}
        for j, r in enumerate(var_rows):
            t = order[r]
            if t.tid not in support:
                continue
            lo_i, hi_i = int(vlo[s, j]), int(vhi[s, j])
            k0_i, k1_i = int(vk0[s, j]), int(vk1[s, j])
            if lo_i > hi_i or (k0_i & k1_i):
                d = None  # contradictory row missed by the verdict
                break
            d[t.tid] = (lo_i, hi_i, k0_i, k1_i)
        out.append(d)
    return out


def prescreen(term_sets: Sequence[Sequence], undecided: Sequence[int]
              ) -> Dict[int, bool]:
    """{query index: False} kills for a discharge/check_batch wave,
    under the device-screen gates (MTPU_PROPAGATE, lane config, batch
    threshold); a device failure is counted and kills nothing. Fact harvest for the surviving sets
    rides along in the verdict cache. Fatal exceptions
    (KeyboardInterrupt/MemoryError) propagate."""
    out: Dict[int, bool] = {}
    if not enabled():
        return out
    try:
        from ..models import pruner
        from ..support.devices import effective_tpu_lanes
    except Exception:
        return out
    todo = [i for i in undecided if term_sets[i]]
    if (not todo or len(todo) < pruner.DEVICE_BATCH_THRESHOLD
            or not effective_tpu_lanes()):
        return out
    try:
        keep = prefilter_feasible([term_sets[i] for i in todo])
    except (KeyboardInterrupt, MemoryError):
        raise
    except Exception as e:
        pruner._device_failed(e)
        return out
    for i, k in zip(todo, keep):
        if not k:
            out[i] = False
    return out
