"""Lane engine bridge: host side of the symbolic lane stepper.

Seeds device lanes from host `GlobalState`s at transaction entry, runs
sync windows of `ops/symstep.sym_run`, drains the device's deferred-op /
path-condition / fork logs back into facade terms, and materializes parked
lanes as host `GlobalState`s positioned at the instruction the device
could not execute. The host engine (svm.py) remains the semantic
authority: CALL/CREATE/SHA3/terminal opcodes and every detector hook run
host-side on the materialized states.

Parity contract (why this cannot diverge from the interpreter):
- deferred ALU records resolve through mythril_tpu/laser/alu.py — the
  same functions the instruction handlers call;
- CALLDATALOAD resolves through the transaction's own calldata object
  (state/calldata.py get_word_at), SLOAD through the same select+simplify
  the Storage class performs (state/account.py:37-67);
- JUMPI conditions build exactly the condi/negated pair of the jumpi_
  handler (instructions.py), including trivial-falsity pruning;
- materialized memory reproduces the byte-granular int/Extract layout of
  state/memory.py write_word_at;
- gas is the device's [min,max] interval added onto the seed state's
  counters, matching StateTransition accumulation.

The object table maps device sids (>0) to facade BitVec/Bool wrappers.
Provisional (negative) sids minted on device encode (lane, record-slot)
and are rewritten to table ids at each drain.
"""

import functools
import logging
import os
import threading
import time
from collections import deque
from copy import copy, deepcopy
from typing import Dict, List, Optional, Tuple

import numpy as np

from ..ops import bv256, symstep
from ..ops.stepper import Status, compile_code
from ..ops.symstep import DEAD, SymLaneState
from ..smt import (
    BitVec, Bool, Extract, If, Not, simplify, symbol_factory,
)
from ..smt import terms as T
from . import alu
from .state.global_state import GlobalState
from .state.calldata import ConcreteCalldata
from ..support.telemetry import trace

log = logging.getLogger(__name__)

_OPN = {}  # opcode byte -> name, filled below
from ..support.opcodes import ADDRESS, OPCODES  # noqa: E402

for _name, _data in OPCODES.items():
    _OPN[_data[ADDRESS]] = _name
_OPB = {v: k for k, v in _OPN.items()}


class ObjectTable:
    """sid (>0) -> facade object (BitVec or Bool)."""

    def __init__(self):
        self._objs: List = [None]

    def add(self, obj) -> int:
        self._objs.append(obj)
        return len(self._objs) - 1

    def __getitem__(self, sid: int):
        if sid <= 0:
            # a negative sid here means a provisional id leaked through
            # a drain unresolved (e.g. a plane slot remapped to -1) —
            # fail loudly instead of returning an unrelated object
            raise IndexError(f"unresolved/invalid sid {sid}")
        return self._objs[sid]

    def __len__(self):
        return len(self._objs)


class LaneCtx:
    """Host context of one device lane: the pristine entry state it was
    seeded from, the (step-stamped) path conditions accumulated through
    drains, and per-adapter sink-taint promotions."""

    __slots__ = ("template", "conds", "addr2idx", "storage_seed_raw",
                 "calldata", "gas0_min", "gas0_max", "promos",
                 "swrites", "owner", "code_base", "func_names")

    def __init__(self, template, addr2idx, storage_seed_raw, calldata,
                 gas0_min, gas0_max, owner=None, code_base=0,
                 func_names=None):
        self.template = template
        #: cross-tenant wave packing (docs/daemon.md §wave packing):
        #: the owning request's tag (None outside packed explores —
        #: READ only through retire_ring.owner_of, lint rule 10), the
        #: member segment's arena base offset, and the member's own
        #: function-name map (None = use the engine's per-explore map)
        self.owner = owner
        self.code_base = code_base
        self.func_names = func_names
        # [(global step, Bool)] — the step stamp lets drain-time sites
        # reconstruct the constraint prefix at any earlier record
        self.conds: List[tuple] = []
        self.addr2idx = addr2idx
        self.storage_seed_raw = storage_seed_raw
        self.calldata = calldata
        self.gas0_min = gas0_min
        self.gas0_max = gas0_max
        # adapter-id -> [(step, annotation)] (lane_adapters promotions)
        self.promos: Dict[int, List[tuple]] = {}
        # per-path storage-write mirror [(key BitVec, value BitVec)] in
        # program order, built from the lane's SSTORE records —
        # REC_SLOAD_RW resolution folds it over the seed storage
        self.swrites: List[tuple] = []

    def clone(self) -> "LaneCtx":
        c = LaneCtx(self.template, self.addr2idx, self.storage_seed_raw,
                    self.calldata, self.gas0_min, self.gas0_max,
                    owner=self.owner, code_base=self.code_base,
                    func_names=self.func_names)
        c.conds = list(self.conds)
        c.promos = {k: list(v) for k, v in self.promos.items()}
        c.swrites = list(self.swrites)
        return c


class _DrainSite:
    """A reconstructed pre-hook site: enough of the GlobalState at a
    device-executed instruction (pc, constraint prefix, gas interval,
    active function, relevant stack tail) for an unmodified detection
    module to run against. Built lazily — most sites are never
    materialized."""

    __slots__ = ("engine", "ctx", "step", "byte_pc", "fentry", "gmin",
                 "gmax", "stack_tail", "_prefix")

    def __init__(self, engine, ctx, step, byte_pc, fentry, gmin=None,
                 gmax=None, stack_tail=(), prefix=None):
        self.engine = engine
        self.ctx = ctx
        self.step = step
        self.byte_pc = byte_pc
        self.fentry = fentry
        self.gmin = gmin
        self.gmax = gmax
        self.stack_tail = stack_tail
        self._prefix = prefix  # explicit snapshot, or None -> by step

    def _conds(self):
        if self._prefix is not None:
            return self._prefix
        return [c for (s, c) in self.ctx.conds if s < self.step]

    def build_state(self) -> GlobalState:
        # copy(), not deepcopy(): the same sharing level the
        # interpreter's own per-instruction StateTransition copy uses —
        # accounts/storage fork independently, terms/code are shared
        gs = copy(self.ctx.template)
        for c in self._conds():
            gs.world_state.constraints.append(c)
        ms = gs.mstate
        a2i = self.ctx.addr2idx
        # device pcs are arena coordinates under a packed wave; the
        # ctx carries its member segment's base (0 unpacked)
        byte_pc = self.byte_pc - self.ctx.code_base
        ms.pc = int(a2i[min(max(byte_pc, 0), a2i.shape[0] - 1)])
        if self.gmin is not None:
            ms.min_gas_used = self.ctx.gas0_min + int(self.gmin)
            ms.max_gas_used = self.ctx.gas0_max + int(self.gmax)
        fentry = self.fentry
        fnames = self.ctx.func_names if self.ctx.func_names \
            is not None else self.engine._func_names
        if fentry >= 0 and fentry in fnames:
            gs.environment.active_function_name = fnames[fentry]
        for v in self.stack_tail:
            ms.stack.append(v)
        return gs

    def lazy_ostate(self):
        return _LazyOState(self)

    def fire_module_pre_hook(self, module):
        """Run the module's hook entry point against this site — the
        function name makes module_helpers.is_prehook() report True,
        exactly as under svm._execute_pre_hook."""
        module.execute(self.build_state())


class _LazyOState:
    """Materialize-on-first-touch proxy for annotation-captured states
    (the integer module stores one per arithmetic op; almost none are
    ever promoted to a sink, so the deepcopy is deferred)."""

    __slots__ = ("_site", "_gs")

    def __init__(self, site):
        self._site = site
        self._gs = None

    def __getattr__(self, name):
        if self._gs is None:
            self._gs = self._site.build_state()
        return getattr(self._gs, name)


#: stats of the most recent completed explore() in this process — lets
#: callers/tests assert the device path genuinely ran (a fallback to the
#: host interpreter would make lane-vs-host comparisons vacuous).
#: RUN_STATS_TOTAL accumulates across engines (spill/refill re-sweeps
#: create several per analysis).
LAST_RUN_STATS: Optional[dict] = None
RUN_STATS_TOTAL: Dict[str, int] = {}


@functools.lru_cache(maxsize=65536)
def _bv_raw(v: int):
    return symbol_factory.BitVecVal(v, 256).raw


@functools.lru_cache(maxsize=256)
def _bv8_raw(v: int):
    return symbol_factory.BitVecVal(v, 8).raw


def _bv_val(v: int) -> BitVec:
    """256-bit constant facade over a memoized term: materialization
    interns the same slot keys / small constants tens of times per
    path across a terminal storm, and the intern round trip dominated
    the stack/storage rebuild. The facade itself stays per-call —
    Expression.annotate mutates in place, so instances must not be
    shared across paths."""
    return BitVec(_bv_raw(v))


def _geo_bucket(k: int, cap: int, floor: int) -> int:
    """Power-of-two bucket {floor, 2*floor, ..., cap} for the
    escalation-retire dims: that gather is a SMALL graph (seconds to
    compile, vs ~25 s for the fused window), and two-point bucketing
    made a 12-slot batch pull 64-slot rows, whose padding bytes cost
    more transfer than a rare extra compile."""
    b = min(cap, floor)
    while b < min(k, cap):
        b *= 2
    return min(b, cap)


# ---------------------------------------------------------------------------
# streaming retire/materialize pipeline gates (docs/drain_pipeline.md,
# "streaming retire"). MTPU_STREAM is the master gate (default on;
# =0 restores the monolithic-retire behavior bit-for-bit);
# MTPU_RETIRE_CHUNK bounds rows per retire gather (pow2-rounded so
# compile keys repeat; 0 disables chunking specifically);
# MTPU_MAT_WORKERS sizes the materialization ring's worker pool (K=1
# stays the default — single-CPU container constraint, ROADMAP).
# ---------------------------------------------------------------------------

#: tri-state test/bench overrides (None = read the env)
FORCE_STREAM: Optional[bool] = None
FORCE_RETIRE_CHUNK: Optional[int] = None

#: default rows-per-gather bound: at full plane caps a retire row is
#: ~7 KB, so 1024 bounds any single gather's device output buffer to a
#: few MB regardless of live width — live width stops being a
#: single-allocation limit
DEFAULT_RETIRE_CHUNK = 1024


def stream_enabled() -> bool:
    """The MTPU_STREAM master gate (default on). Off: monolithic
    retire gathers, no spill merge, K=1 inline materialization —
    today's behavior bit-for-bit."""
    if FORCE_STREAM is not None:
        return bool(FORCE_STREAM)
    return os.environ.get("MTPU_STREAM", "1") != "0"


def retire_chunk() -> int:
    """Rows-per-gather bound for the chunked retire path (pow2-rounded
    down, min 16 so the floors bucketing stays sane); 0 = monolithic
    (MTPU_RETIRE_CHUNK=0, or the master gate off)."""
    if not stream_enabled():
        return 0
    if FORCE_RETIRE_CHUNK is not None:
        ch = int(FORCE_RETIRE_CHUNK)
    else:
        try:
            ch = int(os.environ.get("MTPU_RETIRE_CHUNK",
                                    str(DEFAULT_RETIRE_CHUNK)))
        except ValueError:
            ch = DEFAULT_RETIRE_CHUNK
    if ch <= 0:
        return 0
    ch = max(ch, 4)  # tiny chunks exist for tests/smoke rigs only
    return 1 << (ch.bit_length() - 1)  # pow2 floor: compile keys repeat


def mat_workers() -> int:
    """Materialization ring worker count (MTPU_MAT_WORKERS, default 1
    — the single-CPU pool default; the ring structure is what scales)."""
    if not stream_enabled():
        return 1
    try:
        return max(1, int(os.environ.get("MTPU_MAT_WORKERS", "1")))
    except ValueError:
        return 1


# ---------------------------------------------------------------------------
# capacity autoprobe (docs/drain_pipeline.md): on the first kernel-fault
# fallback the engine binary-searches the max stable live width ONCE and
# clamps pick_width (persisted into stats.json via parallel/cost_model
# so subsequent runs — and the future daemon — never re-fault).
# ---------------------------------------------------------------------------

#: in-process clamps discovered by the autoprobe, keyed by the pow2
#: shape of the faulted request ({} = no fault seen): a 256k fault's
#: clamp binds 256k requests only — the 32k path that never faulted
#: keeps its full width (PR 17; persisted per-shape via cost_model)
CAPACITY_CLAMPS: Dict[int, int] = {}
_FAULT_PROBED_SHAPES: set = set()
_CLAMP_WARNED = False


def capacity_clamp(width: Optional[int] = None) -> Optional[int]:
    """The live-width clamp binding a request of `width`: this
    process's probe result for that pow2 shape, else the one a prior
    run persisted into stats.json (cost_model, per-shape map — a
    legacy scalar loads as the shape-blind entry and binds every
    width). ``width=None`` returns the tightest clamp known from any
    shape (admission-control callers without a concrete request)."""
    try:
        from ..parallel import cost_model

        if width is None:
            cands = list(CAPACITY_CLAMPS.values()) \
                + list(cost_model.WIDTH_CLAMPS.values())
            return min(cands) if cands else None
        shape = cost_model.clamp_shape(width)
        persisted = cost_model.width_clamp_for(width)
        local = CAPACITY_CLAMPS.get(shape)
        cands = [c for c in (local, persisted) if c is not None]
        return min(cands) if cands else None
    except Exception:  # pragma: no cover - cost model optional
        if width is None:
            return min(CAPACITY_CLAMPS.values()) \
                if CAPACITY_CLAMPS else None
        return CAPACITY_CLAMPS.get(
            1 << (max(int(width), 1) - 1).bit_length())


def _probe_width(width: int, lane_kwargs: Optional[dict] = None) -> bool:
    """One capacity probe: allocate the lane planes at `width` and run
    the full-cap escalation retire gather — the exact allocation shape
    that kernel-faults an over-capacity worker (BENCH_r08: init and
    all-dead windows at 64k ran clean; the LIVE window's gather did
    not). True = stable."""
    lk = dict(lane_kwargs or {})
    try:
        st = symstep.init_sym_lanes(width, **lk)
        ridx = jnp.full(_geo_bucket(1, width, min(64, width)), width,
                        jnp.int32)
        st, rows = _retire_rows(
            st, ridx,
            lk.get("stack_depth", 64), lk.get("memory_bytes", 4096),
            lk.get("mem_records", 64), lk.get("storage_slots", 64))
        jax.block_until_ready(rows)
        del st, rows
        return True
    except Exception as e:
        log.info("capacity probe at width %d failed: %s", width, e)
        return False


def note_kernel_fault(width: int,
                      lane_kwargs: Optional[dict] = None,
                      probe=None) -> Optional[int]:
    """First kernel-fault fallback at `width`: re-probe that width in
    isolation (a transient failure that probes clean must NOT clamp),
    then bisect the pow2 widths below it for the largest stable one.
    The clamp lands in CAPACITY_CLAMPS + cost_model (stats.json),
    keyed by the faulted request's pow2 shape — it binds THAT shape
    only, so a 256k probe can't clamp the 32k path — and is logged at
    WARNING once. Runs at most once per shape per process; returns
    the clamp for this shape (None = no clamp)."""
    from ..parallel import cost_model as _cm

    shape = _cm.clamp_shape(width)
    if shape in _FAULT_PROBED_SHAPES or width < 128:
        return CAPACITY_CLAMPS.get(shape)
    _FAULT_PROBED_SHAPES.add(shape)
    probe = probe or _probe_width
    try:
        if probe(width, lane_kwargs):
            log.info("width %d probes clean after engine failure — "
                     "not a capacity fault, no clamp", width)
            return None
        # pow2 bisection over exponents in [64, width/2]
        lo, hi = 64, width // 2
        best = None
        while lo <= hi:
            mid = 1 << ((lo.bit_length() + hi.bit_length()) // 2 - 1)
            mid = max(lo, min(mid, hi))
            if probe(mid, lane_kwargs):
                best = mid
                if mid >= hi:
                    break
                lo = mid * 2
            else:
                if mid <= lo:
                    break
                hi = mid // 2
        if best is None:
            return None
        CAPACITY_CLAMPS[shape] = best
        try:
            _cm.record_width_clamp(best, shape=shape)
        except Exception:  # pragma: no cover - cost model optional
            pass
        log.warning(
            "lane capacity autoprobe: %d-wide live windows fault this "
            "worker; clamping pick_width to %d for the %d-lane shape "
            "(persisted per-shape to stats.json — subsequent runs at "
            "this shape clamp instead of re-faulting; other shapes "
            "are unaffected)",
            width, best, shape)
        trace.event("lane.capacity_clamp", faulted=width, clamp=best,
                    shape=shape)
        return best
    except Exception as e:  # pragma: no cover - probe best-effort
        log.debug("capacity autoprobe failed: %s", e)
        return None


# ---- fused per-window device calls (one dispatch each; every extra
# dispatch is a host-device round trip) --------------------------------------

import jax  # noqa: E402  (this module is only imported on the lane path)
import jax.numpy as jnp  # noqa: E402




def _prologue_core(st: SymLaneState, idx, i32p, u32p, u8p, stack_v,
                   stack_s, mem_v, mem_k, fs, fcount) -> SymLaneState:
    """Per-window device prologue: reset + seed the rows in idx (padded
    entries hold n -> dropped) from packed host arrays, and refresh the
    free-slot stack. Mid-path states (host spill/refill, ROADMAP
    mid-state re-seeding) arrive with nonzero pc/sp/stack/memory
    columns. The stack/memory/calldata arrays are SEED_*-narrow: the
    row is zeroed, then the narrow prefix written (states deeper than
    the seed caps never reach the device — lane_seedable)."""
    k = idx.shape[0]
    n_env = st.env.shape[1]
    sd = stack_s.shape[1]
    mc = mem_v.shape[1]
    ccw = u8p.shape[1]

    def zero(plane):
        return plane.at[idx].set(0, mode="drop")

    # i32 pack: [sbase, cd_size, cd_sym, cd_size_sid, pc, sp, msize,
    #            group, env_sid…]
    sbase, cd_size, cd_sym, cd_size_sid = (
        i32p[:, 0], i32p[:, 1], i32p[:, 2], i32p[:, 3])
    pc, sp, msize, group = (i32p[:, 4], i32p[:, 5], i32p[:, 6],
                            i32p[:, 7])
    env_sid = i32p[:, 8:8 + n_env]
    # u32 pack: [gas_limit, env limbs…]
    gas_limit = u32p[:, 0]
    env = u32p[:, 1:].reshape(k, n_env, bv256.NLIMBS)

    return st._replace(
        pc=st.pc.at[idx].set(pc, mode="drop"),
        sp=st.sp.at[idx].set(sp, mode="drop"),
        depth=zero(st.depth),
        group=st.group.at[idx].set(group, mode="drop"),
        ssid=st.ssid.at[idx].set(0, mode="drop")
        .at[idx, :sd].set(stack_s, mode="drop"),
        stack=st.stack.at[idx].set(0, mode="drop")
        .at[idx, :sd].set(
            stack_v.reshape(k, sd, bv256.NLIMBS), mode="drop"),
        memory=st.memory.at[idx].set(0, mode="drop")
        .at[idx, :mc].set(mem_v, mode="drop"),
        mkind=st.mkind.at[idx].set(0, mode="drop")
        .at[idx, :mc].set(mem_k, mode="drop"),
        msize=st.msize.at[idx].set(msize, mode="drop"),
        mlog_count=zero(st.mlog_count),
        sval_sid=zero(st.sval_sid),
        s_written=zero(st.s_written),
        s_read=zero(st.s_read),
        skey_sid=zero(st.skey_sid),
        s_wstep=zero(st.s_wstep),
        s_mode=zero(st.s_mode),
        scount=zero(st.scount),
        skeys=zero(st.skeys),
        svals=zero(st.svals),
        min_gas=zero(st.min_gas),
        max_gas=zero(st.max_gas),
        steps=zero(st.steps),
        dlog_count=zero(st.dlog_count),
        fentry=st.fentry.at[idx].set(-1, mode="drop"),
        last_jump=st.last_jump.at[idx].set(-1, mode="drop"),
        status=st.status.at[idx].set(Status.RUNNING, mode="drop"),
        sbase=st.sbase.at[idx].set(sbase, mode="drop"),
        calldata=st.calldata.at[idx].set(0, mode="drop")
        .at[idx, :ccw].set(u8p, mode="drop"),
        cd_size=st.cd_size.at[idx].set(cd_size, mode="drop"),
        cd_sym=st.cd_sym.at[idx].set(cd_sym, mode="drop"),
        cd_size_sid=st.cd_size_sid.at[idx].set(cd_size_sid,
                                               mode="drop"),
        env=st.env.at[idx].set(env, mode="drop"),
        env_sid=st.env_sid.at[idx].set(env_sid, mode="drop"),
        gas_limit=st.gas_limit.at[idx].set(gas_limit, mode="drop"),
        free_slots=fs,
        free_count=fcount,
    )


def _retire_gather_core(st: SymLaneState, rc, k: int, dstack: int,
                        dmem: int, dmlog: int, dslot: int):
    """Pack k retired lanes' rows into 3 arrays, column-clipped (planes
    are mostly padding)."""

    def flat(x):
        return x.reshape(k, -1)

    i32 = jnp.concatenate([
        st.pc[rc, None], st.sp[rc, None], st.depth[rc, None],
        st.fentry[rc, None], st.last_jump[rc, None],
        st.msize[rc, None], st.mlog_count[rc, None],
        st.scount[rc, None],
        st.min_gas[rc, None].astype(jnp.int32),  # < 2^31: exact
        st.max_gas[rc, None].astype(jnp.int32),
        st.mlog_off[rc, :dmlog], st.mlog_len[rc, :dmlog],
        st.mlog_sid[rc, :dmlog],
        st.ssid[rc, :dstack],
        st.sval_sid[rc, :dslot], st.s_written[rc, :dslot],
        st.s_read[rc, :dslot],
        st.skey_sid[rc, :dslot], st.s_wstep[rc, :dslot],
    ], axis=1)
    u32 = jnp.concatenate([
        flat(st.stack[rc, :dstack]),
        flat(st.skeys[rc, :dslot]), flat(st.svals[rc, :dslot]),
    ], axis=1)
    u8 = jnp.concatenate(
        [st.memory[rc, :dmem], st.mkind[rc, :dmem]], axis=1)
    return i32, u32, u8


@functools.partial(jax.jit, donate_argnums=(0,),
                   static_argnums=(2, 3, 4, 5))
def _retire_rows(st: SymLaneState, ridx, dstack: int, dmem: int,
                 dmlog: int, dslot: int):
    """Escalation retire: gather the given lanes' rows AND mark them
    free, one dispatch — for retired lanes the fused window dispatch
    could not cover (over its row budget or over a column floor).
    Padded ridx entries hold n: the status write drops them and the
    gather clamps (host ignores those rows)."""
    rc = jnp.clip(ridx, 0, st.pc.shape[0] - 1)
    rows = _retire_gather_core(st, rc, ridx.shape[0], dstack, dmem,
                               dmlog, dslot)
    st = st._replace(status=st.status.at[ridx].set(DEAD, mode="drop"))
    return st, rows


def _resume_gather_core(st: SymLaneState, rc):
    """Slim rows for in-place-resume candidates: top-2 stack entries,
    gas counters, the RESUME_MEM memory prefix, and the overlay
    records — everything the host needs to replay a pop-k/push-term
    instruction's semantics, a fraction of a full retire row. Rides
    the fused window dispatch (no separate round trip); declined
    lanes keep their planes and retire through escalation."""
    top = jnp.clip(st.sp[rc] - 1, 0, st.stack.shape[1] - 1)
    sub = jnp.clip(st.sp[rc] - 2, 0, st.stack.shape[1] - 1)
    i32 = jnp.concatenate([
        st.msize[rc, None],
        st.min_gas[rc, None].astype(jnp.int32),
        st.max_gas[rc, None].astype(jnp.int32),
        st.gas_limit[rc, None].astype(jnp.int32),
        st.mlog_count[rc, None],
        st.ssid[rc, top][:, None], st.ssid[rc, sub][:, None],
        st.mlog_off[rc, :RESUME_MLOG], st.mlog_len[rc, :RESUME_MLOG],
        st.mlog_sid[rc, :RESUME_MLOG],
    ], axis=1)
    u32 = jnp.concatenate(
        [st.stack[rc, top], st.stack[rc, sub]], axis=1)
    u8 = jnp.concatenate([
        st.memory[rc, :RESUME_MEM], st.mkind[rc, :RESUME_MEM],
    ], axis=1)
    return i32, u32, u8


def _unpack_resume(packed) -> dict:
    """Host-side inverse of _resume_gather_core's packing."""
    i32, u32, u8 = [np.asarray(x) for x in packed]
    out = {}
    for col, name in enumerate(("msize", "min_gas", "max_gas",
                                "gas_limit", "mlog_count",
                                "sid_top", "sid_sub")):
        out[name] = i32[:, col]
    off = 7
    for name in ("mlog_off", "mlog_len", "mlog_sid"):
        out[name] = i32[:, off:off + RESUME_MLOG]
        off += RESUME_MLOG
    out["top"] = u32[:, :bv256.NLIMBS]
    out["sub"] = u32[:, bv256.NLIMBS:]
    out["memory"] = u8[:, :RESUME_MEM]
    out["mkind"] = u8[:, RESUME_MEM:]
    return out


def _unpack_rows(packed, dstack, dmem, dmlog, dslot) -> dict:
    """Host-side inverse of _retire_rows' packing."""
    i32, u32, u8 = [np.asarray(x) for x in packed]
    k = i32.shape[0]
    out = {}
    off = 0
    for name in ("pc", "sp", "depth", "fentry", "last_jump", "msize",
                 "mlog_count", "scount", "min_gas", "max_gas"):
        out[name] = i32[:, off]
        off += 1
    for name, w in (("mlog_off", dmlog), ("mlog_len", dmlog),
                    ("mlog_sid", dmlog), ("ssid", dstack),
                    ("sval_sid", dslot), ("s_written", dslot),
                    ("s_read", dslot), ("skey_sid", dslot),
                    ("s_wstep", dslot)):
        out[name] = i32[:, off:off + w]
        off += w
    off = 0
    for name, w, shp in (
        ("stack", dstack * bv256.NLIMBS, (dstack, bv256.NLIMBS)),
        ("skeys", dslot * bv256.NLIMBS, (dslot, bv256.NLIMBS)),
        ("svals", dslot * bv256.NLIMBS, (dslot, bv256.NLIMBS)),
    ):
        out[name] = u32[:, off:off + w].reshape((k,) + shp)
        off += w
    out["memory"] = u8[:, :dmem]
    out["mkind"] = u8[:, dmem:]
    return out


def _counts_core(st: SymLaneState):
    """Per-lane counters + scalars (pc rides along so the host can
    classify parked lanes for in-place resume without a row pull)."""
    misc = jnp.stack(
        [st.dlog_count, st.status, st.steps,
         st.sp, st.scount, st.mlog_count, st.msize, st.pc], axis=1)
    scal = jnp.stack([st.flog_count, st.free_count])
    return misc, scal


#: unique-record / fork-row budgets of the fused window pull (escalate
#: to a full gather in the rare window that exceeds them)
URB = 512
FB = 512
_DEDUP_H = 4096  # dedup hash-table cells

_SSTORE_BYTE = _OPB["SSTORE"]


def _dedup_canon(st: SymLaneState, d_recs: int):
    """Canonicalize this window's deferred records ON DEVICE: lockstep
    sibling lanes recompute identical records (same seed cohort, op,
    pc, step, operands), and draining one instance per distinct term —
    instead of one per lane — is what makes the drain cost scale with
    the tree's distinct work rather than the lane count (the round-2
    symbolic bench spent 112 s of 177 s re-walking duplicate records).

    Processed in GLOBAL STEP order (one record per lane per step) so an
    argument referencing an ancestor lane's earlier record is already
    canonical when its referrer is hashed — content-equal records then
    compare equal on their canonical argument sids. Hash collisions
    fall back to self (less dedup, never wrong); SSTORE taint-sink
    records keep per-lane identity by construction. Returns the
    arg-remapped dlog_sid plane and the (N, R) canonical-pid plane."""
    from jax import lax

    n = st.pc.shape[0]
    lanes = jnp.arange(n)
    intmax = jnp.iinfo(jnp.int32).max
    live_all = jnp.arange(d_recs)[None, :] < st.dlog_count[:, None]
    any_rec = jnp.any(live_all)
    lo = jnp.min(jnp.where(live_all, st.dlog_step, intmax))
    hi = jnp.max(jnp.where(live_all, st.dlog_step, -1))

    def round_s(s, carry):
        dlog_sid, canon_pid = carry
        match = live_all & (st.dlog_step == s)
        has = jnp.any(match, axis=1)
        slot = jnp.argmax(match, axis=1)

        def take(plane):
            return plane[lanes, slot]

        sids = dlog_sid[lanes, slot]
        negm = sids < 0
        idx = jnp.where(negm, -sids - 1, 0)
        mapped = canon_pid[idx // d_recs, idx % d_recs]
        sids = jnp.where(negm, mapped, sids)
        dlog_sid = dlog_sid.at[lanes, slot].set(
            jnp.where(has[:, None], sids, dlog_sid[lanes, slot]))
        op = take(st.dlog_op)
        pc = take(st.dlog_pc)
        fen = take(st.dlog_fentry)
        grp = st.group
        vals = st.dlog_val[lanes, slot].reshape(n, -1)
        h = jnp.zeros(n, jnp.uint32)
        for f in (grp, op, pc, fen, sids[:, 0], sids[:, 1],
                  sids[:, 2]):
            h = h * jnp.uint32(0x9E3779B1) + \
                lax.bitcast_convert_type(f, jnp.uint32)
        for c in range(vals.shape[1]):
            h = h * jnp.uint32(0x9E3779B1) + vals[:, c]
        cand = has & (op != _SSTORE_BYTE) \
            & (op != symstep.REC_SLOAD_RW)
        bucket = jnp.where(cand, (h % _DEDUP_H).astype(jnp.int32),
                           _DEDUP_H)
        win = jnp.full((_DEDUP_H,), intmax, jnp.int32)
        win = win.at[bucket].min(
            jnp.where(cand, lanes, intmax).astype(jnp.int32),
            mode="drop")
        w = jnp.clip(win[jnp.clip(bucket, 0, _DEDUP_H - 1)], 0, n - 1)
        eq = (
            cand & has[w] & (op == op[w]) & (pc == pc[w])
            & (fen == fen[w]) & (grp == grp[w])
            & jnp.all(sids == sids[w], axis=1)
            & jnp.all(vals == vals[w], axis=1)
        )
        canon_lane = jnp.where(eq, w, lanes)
        canon_slot = jnp.where(eq, slot[w], slot)
        pid = -(canon_lane * d_recs + canon_slot + 1)
        canon_pid = canon_pid.at[lanes, slot].set(
            jnp.where(has, pid, canon_pid[lanes, slot]))
        return dlog_sid, canon_pid

    canon0 = jnp.zeros((n, d_recs), jnp.int32)
    dlog_sid, canon_pid = lax.fori_loop(
        jnp.where(any_rec, lo, 0), jnp.where(any_rec, hi + 1, 0),
        round_s, (st.dlog_sid, canon0))
    return dlog_sid, canon_pid


def _canon_remap(st: SymLaneState, canon_pid, d_recs: int
                 ) -> SymLaneState:
    """Rewrite this window's provisional sids in the persistent planes
    to their canonical pids (the host only builds/publishes canonical
    records)."""

    def remap(plane):
        negm = plane < 0
        idx = jnp.where(negm, -plane - 1, 0)
        mapped = canon_pid[idx // d_recs, idx % d_recs]
        return jnp.where(negm, mapped, plane)

    return st._replace(
        ssid=remap(st.ssid),
        sval_sid=remap(st.sval_sid),
        skey_sid=remap(st.skey_sid),
        mlog_sid=remap(st.mlog_sid),
        flog_sid=remap(st.flog_sid),
    )


def _unique_table(st: SymLaneState, canon_pid, d_recs: int, urb: int):
    """Compact the canonical records into an (urb, 9+24) i32 table:
    [lane, slot, op, pc, step, fentry, sid0..2, vals]; rows beyond the
    count are padding. Also returns the count (host escalates when it
    exceeds urb)."""
    from jax import lax

    n = st.pc.shape[0]
    live = jnp.arange(d_recs)[None, :] < st.dlog_count[:, None]
    self_pid = -(jnp.arange(n)[:, None] * d_recs
                 + jnp.arange(d_recs)[None, :] + 1)
    is_canon = (live & (canon_pid == self_pid)).reshape(-1)
    ucount = jnp.sum(is_canon.astype(jnp.int32))
    # first-urb selection via sort (ascending flat order; padding
    # clamps to row 0 as before — the host reads only ucount rows):
    # the cumsum+scatter form mis-partitions under a mesh when the
    # index count equals the operand length (see pick_mesh)
    rows = jnp.sort(jnp.where(is_canon, jnp.arange(n * d_recs),
                              n * d_recs))[:urb]
    rows = jnp.where(rows < n * d_recs, rows, 0)
    l, sl = rows // d_recs, rows % d_recs
    tab = jnp.concatenate([
        l[:, None], sl[:, None], st.dlog_op[l, sl][:, None],
        st.dlog_pc[l, sl][:, None], st.dlog_step[l, sl][:, None],
        st.dlog_fentry[l, sl][:, None], st.dlog_sid[l, sl],
        lax.bitcast_convert_type(st.dlog_val[l, sl], jnp.int32)
        .reshape(urb, 3 * bv256.NLIMBS),
    ], axis=1)
    return tab, ucount


def _fork_table(st: SymLaneState, fb: int):
    """First fb fork rows as an (fb, 9) i32 table: [parent, child,
    step, pc, sid, gmin, gmax, fentry, dest]."""
    from jax import lax

    r = jnp.arange(fb)
    return jnp.stack([
        st.flog_parent[r], st.flog_child[r], st.flog_step[r],
        st.flog_pc[r], st.flog_sid[r],
        lax.bitcast_convert_type(st.flog_gmin[r], jnp.int32),
        lax.bitcast_convert_type(st.flog_gmax[r], jnp.int32),
        st.flog_fentry[r], st.flog_dest[r],
    ], axis=1)


@functools.partial(jax.jit, static_argnums=(1,))
def _unique_table_big(st: SymLaneState, urb: int):
    """Escalation: recompute the canonical set (idempotent — the sid
    planes are already canonical) and pull it at `urb` rows, for the
    window whose distinct-record count exceeds the fused pull's URB.
    The caller sizes urb geometrically from the ucount it already has
    (the old fixed worst-case budget shipped a 35 MB table to deliver
    a few thousand rows); beyond the worst case the explore raises and
    the sweep reroutes the batch to the host interpreter — degraded,
    never wrong."""
    d_recs = st.dlog_op.shape[1]
    _, canon_pid = _dedup_canon(st, d_recs)
    return _unique_table(st, canon_pid, d_recs, urb)


@jax.jit
def _gather_full_flog(st: SymLaneState):
    return _fork_table(st, st.flog_parent.shape[0])


def _remap_reset_core(st: SymLaneState, prov_pairs) -> SymLaneState:
    """Remap provisional sids to resolved object ids (device-side — the
    sid planes never leave the device) and reset the per-window logs.
    Runs at the START of the next window's fused dispatch: the encoding
    (lane, record-slot) of the previous window's log is still unique
    until that window's run mints new records, and rows that retired in
    between are dead (their planes are never read again). The
    resolutions arrive as sparse (encoded-slot, oid) pairs — a dense
    (N, R) plane cost 1 MB of transfer per window at 4096 lanes — and
    are scattered into the dense table here (padding pairs carry an
    out-of-range slot and drop). Unresolved slots hold int32 min so a
    leaked sid fails loudly instead of aliasing a real record."""
    d_recs = st.dlog_op.shape[1]
    n = st.pc.shape[0]
    dense = jnp.full((n * d_recs,), np.iinfo(np.int32).min, jnp.int32)
    dense = dense.at[prov_pairs[:, 0]].set(prov_pairs[:, 1],
                                           mode="drop")
    prov_arr = dense.reshape(n, d_recs)

    def remap(plane):
        negm = plane < 0
        idx = jnp.where(negm, -plane - 1, 0)
        mapped = prov_arr[idx // d_recs, idx % d_recs]
        return jnp.where(negm, mapped, plane)

    return st._replace(
        ssid=remap(st.ssid),
        sval_sid=remap(st.sval_sid),
        skey_sid=remap(st.skey_sid),
        mlog_sid=remap(st.mlog_sid),
        dlog_count=jnp.zeros_like(st.dlog_count),
        flog_count=jnp.zeros_like(st.flog_count),
    )


def _sm32(x):
    """splitmix32 finisher: per-column pseudorandom multipliers for the
    lane-fingerprint folds."""
    x = (x + jnp.uint32(0x9E3779B9)).astype(jnp.uint32)
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x85EBCA6B)
    x = x ^ (x >> 13)
    x = x * jnp.uint32(0xC2B2AE35)
    return x ^ (x >> 16)


@jax.jit
def _merge_fingerprint(st: SymLaneState, prov_pairs):
    """Per-lane FRONTIER fingerprint for the window-boundary merge pass
    (docs/lane_merge.md): the lane-dedup extension of the _dedup_canon/
    _unique_table record-dedup machinery. Folds everything a lane's
    future execution (and its materialization) can read — pc, depth,
    fork group, fentry, gas limit, the live stack (canonical sids +
    concrete limbs), memory bytes + overlay records, the storage slot
    table with write-ORDER ranks (absolute s_wstep values differ between
    gas-balanced rejoin arms and must not block a merge), and the
    calldata/env shape scalars — into two independent 32-bit
    multilinear hashes. Provisional (negative) sids from the window
    just drained remap through the same sparse resolution pairs the
    next dispatch will apply, so record identity is canonical across
    lanes. Deliberately EXCLUDED: steps (budget accounting), status,
    the drained dlog/flog planes, and last_jump (which jump entered a
    rejoin differs per disjunct; the survivor's value represents one
    witness path). Equal fingerprints + equal host context
    (template/swrites/promos) define an exact-frontier twin group.

    Returns (N, 4) uint32: the two hash columns plus the raw gas
    interval (min, max) for host-side grouping / widening."""
    n = st.pc.shape[0]
    d_recs = st.dlog_op.shape[1]
    dense = jnp.full((n * d_recs,), np.iinfo(np.int32).min, jnp.int32)
    dense = dense.at[prov_pairs[:, 0]].set(prov_pairs[:, 1],
                                           mode="drop")
    prov_arr = dense.reshape(n, d_recs)

    def remap(plane):
        negm = plane < 0
        idx = jnp.where(negm, -plane - 1, 0)
        mapped = prov_arr[idx // d_recs, idx % d_recs]
        return jnp.where(negm, mapped, plane)

    h1 = jnp.full((n,), 2166136261, jnp.uint32)
    h2 = jnp.full((n,), 0x9E3779B9, jnp.uint32)
    seed = [0]

    def fold(h1, h2, arr, mask=None):
        seed[0] += 1
        arr = arr.reshape(n, -1).astype(jnp.uint32)
        if mask is not None:
            arr = jnp.where(mask.reshape(n, -1), arr, 0)
            # the mask pattern itself is part of the frontier only
            # through planes that are folded separately (sp, counts,
            # sid planes), so masked slots contribute exactly 0
        k = arr.shape[1]
        idx = (jnp.arange(k, dtype=jnp.uint32)
               + jnp.uint32((seed[0] * 0x632BE59B) & 0xFFFFFFFF))
        w1 = _sm32(idx)
        w2 = _sm32(idx ^ jnp.uint32(0x7F4A7C15))
        s1 = jnp.sum(arr * w1[None, :], axis=1, dtype=jnp.uint32)
        s2 = jnp.sum((arr ^ w2[None, :]) * w1[None, :], axis=1,
                     dtype=jnp.uint32)
        h1 = (h1 ^ s1) * jnp.uint32(16777619)
        h2 = (h2 + s2) * jnp.uint32(2654435761)
        h2 = h2 ^ (h2 >> 15)
        return h1, h2

    # gas interval deliberately NOT folded (since the gas-widening
    # merge, docs/lane_merge.md): the host groups on it exactly when
    # widening is off, and widens the survivor's ctx offsets to cover
    # every arm when on — so uneven-gas rejoin arms fingerprint equal.
    # gas_limit stays in the hash: widening covers usage, not budget.
    for scalar in (st.pc, st.sp, st.depth, st.group, st.fentry,
                   st.msize, st.mlog_count, st.scount, st.s_mode,
                   st.sbase, st.cd_size, st.cd_sym, st.cd_size_sid,
                   st.gas_limit):
        h1, h2 = fold(h1, h2, scalar)

    depth_cap = st.stack.shape[1]
    slot_live = jnp.arange(depth_cap)[None, :] < st.sp[:, None]
    ssid_r = remap(st.ssid)
    h1, h2 = fold(h1, h2, ssid_r, slot_live)
    conc = slot_live & (ssid_r == 0)
    h1, h2 = fold(h1, h2, st.stack,
                  jnp.repeat(conc, bv256.NLIMBS, axis=1))

    # memory: the kind plane in full; byte content only where a
    # concrete byte/word actually lives (symbolic-word bytes are stale
    # — their content is the overlay log, folded below)
    h1, h2 = fold(h1, h2, st.mkind)
    conc_mem = (st.mkind != 0) & (st.mkind != symstep.KIND_SYM_WORD)
    h1, h2 = fold(h1, h2, st.memory, conc_mem)
    mr = st.mlog_off.shape[1]
    mlog_live = jnp.arange(mr)[None, :] < st.mlog_count[:, None]
    h1, h2 = fold(h1, h2, st.mlog_off, mlog_live)
    h1, h2 = fold(h1, h2, st.mlog_len, mlog_live)
    h1, h2 = fold(h1, h2, remap(st.mlog_sid), mlog_live)

    # storage slot table: keys/values by canonical sid or limbs, the
    # read/write flags, and the write ORDER as a rank (not the raw
    # step stamp)
    s_slots = st.skeys.shape[1]
    srow = jnp.arange(s_slots)[None, :] < st.scount[:, None]
    skey_r = remap(st.skey_sid)
    sval_r = remap(st.sval_sid)
    h1, h2 = fold(h1, h2, skey_r, srow)
    h1, h2 = fold(h1, h2, sval_r, srow)
    h1, h2 = fold(h1, h2, st.s_written, srow)
    h1, h2 = fold(h1, h2, st.s_read, srow)
    h1, h2 = fold(h1, h2, st.skeys,
                  jnp.repeat(srow & (skey_r == 0), bv256.NLIMBS,
                             axis=1))
    h1, h2 = fold(h1, h2, st.svals,
                  jnp.repeat(srow & (sval_r == 0), bv256.NLIMBS,
                             axis=1))
    written = srow & (st.s_written != 0)
    ws = jnp.where(written, st.s_wstep, np.iinfo(np.int32).max)
    # rank of each written slot among the lane's writes (stable by
    # slot index for equal stamps)
    earlier = (ws[:, :, None] > ws[:, None, :]) | (
        (ws[:, :, None] == ws[:, None, :])
        & (jnp.arange(s_slots)[None, :, None]
           > jnp.arange(s_slots)[None, None, :]))
    rank = jnp.sum(earlier & written[:, None, :], axis=2,
                   dtype=jnp.int32)
    h1, h2 = fold(h1, h2, jnp.where(written, rank, -1))

    return jnp.stack([h1, h2, st.min_gas.astype(jnp.uint32),
                      st.max_gas.astype(jnp.uint32)], axis=1)


#: fast-retire row budget and column floors (stack slots, memory bytes,
#: memory-overlay records, storage slots) for the in-dispatch retire
#: gather; lanes over a floor (or past the row budget) stay NEEDS_HOST
#: and retire through the escalation dispatch instead
RCAP = 16
RETIRE_FLOORS = (24, 512, 8, 8)
#: in-place-resume hold budget per window (slim rows ride the fused
#: output; ~1.2 KB each). Wider than RCAP: resumed lanes cost ~60 B of
#: patch, while a force-retired lane pays a full retire row + host
#: interpreter step + re-seed. The host still only patches what the
#: next dispatch's seed-buffer resume section can carry (`small` until
#: the full-width seed variant is warm).
HOLD_CAP = 64

#: device-seed column caps: a seed row ships only this much stack /
#: concrete-memory / concrete-calldata content per lane. States past a
#: cap stay on the host interpreter (lane_seedable) — a dense full-width
#: seed buffer would cost ~44 MB of transfer per 4096-lane window, and
#: mid-path states this deep are rare enough that host execution is
#: cheaper than shipping them
SEED_STACK = 16
SEED_MEM = 256
SEED_CD = 160
#: provisional-sid resolutions ship as sparse (encoded-slot, oid) pairs
#: scattered into the dense table on device; this bucket covers every
#: realistic window (records/window is bounded by the drain), and only
#: a pathological >PROV_BUCKET window compiles the dense-sized variant
PROV_BUCKET = 4096

#: in-place resume envelope: a lane parked at SHA3 whose state fits
#: these bounds is HELD on device — the host pulls a slim row (top-2
#: stack entries + the memory prefix + overlay records), builds the
#: keccak term itself, and uploads a ~60-byte patch with the next
#: window instead of paying a full retire + GlobalState materialize +
#: interpreter step + full re-seed round trip
RESUME_MEM = SEED_MEM
RESUME_MLOG = 8
#: the SHA3 opcode byte (the only resumable op today; the mechanism
#: generalizes to any pop-k/push-term instruction the host can model)
_SHA3_BYTE = 0x20


def _unpack_i32_sections(buf, sections):
    """Split a flat i32 buffer into named (shape, dtype) sections
    (offsets are static — XLA fuses the slices away)."""
    from jax import lax

    out = {}
    off = 0
    for name, shape, dtype in sections:
        size = int(np.prod(shape)) if shape else 1
        part = buf[off:off + size]
        part = part.reshape(shape) if shape else part[0]
        if dtype == jnp.uint32:
            part = lax.bitcast_convert_type(part, jnp.uint32)
        out[name] = part
        off += size
    return out


def _seed_sections(n, k, n_env, sd, pv):
    """Layout of the packed per-window i32 buffer (host+device agree).
    The kill section is lane-count-sized so a window can never overflow
    it — a capped bucket would let a dead-but-running lane's slot be
    re-seeded before its deferred kill lands. One layout serves fresh
    AND mid-path seeds (fresh rows carry zero stack/memory sections):
    a second jit variant costs another window compile, the extra
    padding costs little at SEED_* widths."""
    return [
        ("idx", (k,), jnp.int32),
        ("i32p", (k, 8 + n_env), jnp.int32),
        ("u32p", (k, 1 + n_env * bv256.NLIMBS), jnp.uint32),
        ("fs", (n,), jnp.int32),
        ("fcount", (), jnp.int32),
        ("prov", (pv, 2), jnp.int32),
        ("kill", (n,), jnp.int32),
        ("stack_v", (k, sd * bv256.NLIMBS), jnp.uint32),
        ("stack_s", (k, sd), jnp.int32),
        # in-place SHA3 resumes (same k bucket as seeds): lane index
        # (padding n), then [pc, sp, msize, min_gas, max_gas, sid] and
        # the concrete-result limbs
        ("r_idx", (k,), jnp.int32),
        ("r_i32", (k, 6), jnp.int32),
        ("r_limbs", (k, bv256.NLIMBS), jnp.uint32),
    ]


@functools.partial(jax.jit, donate_argnums=(0, 10),
                   static_argnums=tuple(range(6, 10)))
def _window_exec(st: SymLaneState, cc, i32buf, u8buf, exec_table,
                 taint_table, window: int, k: int, budget: int,
                 pv: int, visited, resume_on):
    """The whole per-window device work in ONE dispatch with TWO packed
    host->device buffers: every dispatch is a host-device round trip
    and every input array a separate transfer. Sequence:

    1. remap the previous window's provisional sids, reset the logs,
       and kill lanes the host found trivially-false at the last drain;
    2. seed this window's k entries from the packed buffers (fresh
       tx-entry seeds carry zero stack/memory sections);
    3. run the window;
    4. canonicalize the window's deferred records (_dedup_canon) and
       rewrite the persistent sid planes to canonical pids;
    5. select up to RCAP parked lanes whose rows fit the retire column
       floors, gather their rows, and mark them DEAD (the host gets
       back lane indices in ridx; over-budget/over-floor lanes stay
       NEEDS_HOST for the escalation dispatch);
    6. return counters, the canonical-record table, and the fork
       table (one escalation gather in the rare over-budget window).
    """
    from jax import lax

    n = st.pc.shape[0]
    n_env = st.env.shape[1]
    cap = st.calldata.shape[1]
    n_depth = st.stack.shape[1]
    mem_cap = st.memory.shape[1]
    d_recs = st.dlog_op.shape[1]
    sd = min(SEED_STACK, n_depth)
    mc = min(SEED_MEM, mem_cap)
    ccw = min(SEED_CD, cap)
    sec = _seed_sections(n, k, n_env, sd, pv)
    a = _unpack_i32_sections(i32buf, sec)
    stack_v, stack_s = a["stack_v"], a["stack_s"]
    u8p = u8buf[:k * ccw].reshape(k, ccw)
    mem_v = u8buf[k * ccw:k * (ccw + mc)].reshape(k, mc)
    mem_k = u8buf[k * (ccw + mc):
                  k * (ccw + 2 * mc)].reshape(k, mc)

    st = _remap_reset_core(st, a["prov"])
    st = st._replace(status=st.status.at[a["kill"]].set(
        DEAD, mode="drop"))
    # apply in-place SHA3 resumes: held lanes get the host-built hash
    # pushed (sid or concrete limbs), gas/msize accounted, and run on
    r = a["r_idx"]
    ri = a["r_i32"]
    slot = jnp.clip(ri[:, 1] - 1, 0, n_depth - 1)
    st = st._replace(
        pc=st.pc.at[r].set(ri[:, 0], mode="drop"),
        sp=st.sp.at[r].set(ri[:, 1], mode="drop"),
        msize=st.msize.at[r].set(ri[:, 2], mode="drop"),
        min_gas=st.min_gas.at[r].set(
            ri[:, 3].astype(st.min_gas.dtype), mode="drop"),
        max_gas=st.max_gas.at[r].set(
            ri[:, 4].astype(st.max_gas.dtype), mode="drop"),
        ssid=st.ssid.at[r, slot].set(ri[:, 5], mode="drop"),
        stack=st.stack.at[r, slot].set(a["r_limbs"], mode="drop"),
        status=st.status.at[r].set(Status.RUNNING, mode="drop"),
    )
    st = _prologue_core(st, a["idx"], a["i32p"], a["u32p"], u8p,
                        stack_v, stack_s, mem_v, mem_k, a["fs"],
                        a["fcount"])
    st, visited = symstep.sym_run(cc, st, window, exec_table,
                                  taint_table, visited)

    # 4. canonicalize records; planes reference canonical pids only
    dlog_sid2, canon_pid = _dedup_canon(st, d_recs)
    st = st._replace(dlog_sid=dlog_sid2)
    st = _canon_remap(st, canon_pid, d_recs)

    # 5. in-dispatch fast retire
    dstack, dmem, dmlog, dslot = RETIRE_FLOORS
    rcap = min(RCAP, n)
    parked = (st.status == Status.NEEDS_HOST) | (
        (st.status == Status.RUNNING) & (st.steps >= budget))
    fits = (
        (st.sp <= dstack) & (st.msize <= dmem)
        & (st.mlog_count <= dmlog) & (st.scount <= dslot))
    # SHA3-parked lanes inside the resume envelope stay on device for
    # in-place resume: their slim rows ride THIS dispatch's output, the
    # host builds the keccak term, and the patch rides the NEXT
    # dispatch's seed buffer — no separate round trip in either
    # direction. Any the host declines retire through escalation.
    # resume_on is a traced scalar so toggling it forks no jit variant.
    hcap = min(HOLD_CAP, n)
    op_at_pc = cc.opcode[jnp.clip(st.pc, 0, cc.packed.shape[0] - 1)]
    hold = (
        (resume_on != 0) & (st.status == Status.NEEDS_HOST)
        & (op_at_pc == _SHA3_BYTE) & (st.sp >= 2)
        & (st.msize <= RESUME_MEM) & (st.mlog_count <= RESUME_MLOG))
    horder = jnp.cumsum(hold.astype(jnp.int32)) - 1
    hold = hold & (horder < hcap)  # excess candidates retire instead
    # selection-to-bucket via sort (ascending lane order == cumsum
    # order, padding n sorts last): a scatter whose index count equals
    # the plane length mis-partitions under a mesh (see pick_mesh)
    hidx = jnp.sort(
        jnp.where(hold, jnp.arange(n), n).astype(jnp.int32))[:hcap]
    hrows = _resume_gather_core(st, jnp.clip(hidx, 0, n - 1))
    elig = parked & fits & ~hold
    order = jnp.cumsum(elig.astype(jnp.int32)) - 1
    take = elig & (order < rcap)
    ridx = jnp.sort(
        jnp.where(take, jnp.arange(n), n).astype(jnp.int32))[:rcap]
    rc = jnp.clip(ridx, 0, n - 1)
    rows = _retire_gather_core(st, rc, rcap, dstack, dmem, dmlog,
                               dslot)
    st = st._replace(status=st.status.at[ridx].set(DEAD, mode="drop"))

    misc, scal = _counts_core(st)
    utab, ucount = _unique_table(st, canon_pid, d_recs, min(URB,
                                                           n * d_recs))
    ftab = _fork_table(st, min(FB, n))
    scal = jnp.concatenate([scal, ucount[None]])
    return st, visited, (misc, scal, utab, ftab, ridx) + rows \
        + (hidx,) + hrows


def _limbs_int(limbs) -> int:
    return bv256.limbs_to_int(np.asarray(limbs))


def lane_seedable(gs, stack_depth: int = SEED_STACK,
                  memory_bytes: int = SEED_MEM,
                  exec_table=None) -> bool:
    """True when the lane engine can seed this state: tx-entry states
    and mid-path states with device-representable stack/memory (the
    host spill/refill path — over-capacity forks park to the host and
    their descendants re-enter the device here). Mid-path limits:
    every stack item is an int/term, memory bytes are concrete, the
    state advanced past the instruction it parked at, and the
    stack/memory content fits the SEED_* columns of the packed seed
    buffer (deeper states stay on the host — shipping full-width seed
    planes costs more transfer than the interpretation it saves)."""
    from .transaction import MessageCallTransaction

    ms = gs.mstate
    storage = gs.environment.active_account.storage
    ilist = gs.environment.code.instruction_list
    if (
        gs.environment.static
        or ms.subroutine_stack
        or not isinstance(gs.current_transaction, MessageCallTransaction)
        or (storage.dynld and storage.dynld.active)
        or getattr(gs, "_lane_parked_pc", None) == ms.pc
        or ms.pc >= len(ilist)
        or len(ms.stack) > stack_depth
        or int(ms.memory_size) > memory_bytes
    ):
        return False
    table = symstep.SYM_EXECUTABLE if exec_table is None else exec_table
    op_byte = _OPB.get(ilist[ms.pc]["opcode"])
    if op_byte is None or not table[op_byte]:
        return False  # would park on the first device step anyway
    for key, val in ms.memory._memory.items():
        if not isinstance(key, int):
            return False
        if isinstance(val, int):
            continue
        if not (isinstance(val, BitVec) and val.value is not None):
            return False
    return True


def code_to_bytes(code_obj) -> Optional[bytes]:
    """Concrete bytecode of a Disassembly, or None when it holds
    symbolic bytes (runtime code returned by a creation tx can,
    disassembler/disassembly.py assign_bytecode)."""
    bc = getattr(code_obj, "bytecode", None)
    if isinstance(bc, str):
        try:
            return bytes.fromhex(bc.replace("0x", ""))
        except ValueError:
            return None
    if isinstance(bc, (bytes, bytearray)):
        return bytes(bc)
    if isinstance(bc, tuple):
        from ..support.support_utils import fold_concrete_bytes

        norm = fold_concrete_bytes(bc)
        if all(isinstance(b, int) for b in norm):
            return bytes(norm)
    return None


def _storage_read_term(seed_raw: "T.Term", key: BitVec) -> BitVec:
    """The exact term Storage.__getitem__ builds for an in-memory read
    (state/account.py:37-67 minus the dynamic-loader path): a select over
    the storage array, simplified. Read-over-write folding makes the
    select against the seed array identical to the interpreter's select
    against the current array for any key that misses the write log."""
    idx = key.raw
    return simplify(BitVec(T.mk_select(seed_raw, idx), key.annotations))


# ---------------------------------------------------------------------------
# deferred-record resolution
# ---------------------------------------------------------------------------

#: CompiledCode per (bytecode, function entries) — the code planes stay
#: resident on device across transactions, sweeps, and contracts (each
#: compile_code call costs host decode + five H2D transfers).
_CC_CACHE: Dict[tuple, object] = {}

#: daemon request epoch (docs/daemon.md): the resident daemon bumps
#: this once per request, and a jit-cache hit (code plane or warmed
#: window variant) whose compile landed in an EARLIER epoch counts as
#: compile_reuse_hits — the cross-request amortization the daemon
#: exists for. One-shot processes never bump it, so every hit stays
#: same-epoch and the counter (and behavior) is bit-for-bit unchanged.
REQUEST_EPOCH = [0]
_CC_EPOCH: Dict[tuple, int] = {}
_WARM_EPOCH: Dict[tuple, int] = {}


def _note_cross_request_hit(epochs: Dict[tuple, int], key) -> None:
    """Book a cache hit against the epoch its compile was paid in."""
    if epochs.get(key, REQUEST_EPOCH[0]) != REQUEST_EPOCH[0]:
        from ..smt.solver.solver_statistics import SolverStatistics

        SolverStatistics().bump(compile_reuse_hits=1)

#: all-DEAD SymLaneState pool keyed by shape config: a finished engine
#: parks its device buffers here and the next engine (same shapes —
#: possibly a different contract) adopts them instead of paying the
#: init dispatch. A pooled state is interchangeable because every live
#: field of a lane is fully rewritten when the row is seeded.
_STATE_POOL: Dict[tuple, List[SymLaneState]] = {}


def _compiled_code(code_bytes: bytes, fentries) -> "CompiledCode":
    from ..analysis import static_pass
    from ..analysis.static_pass import loop_summary

    static_on = static_pass.enabled()
    info = static_pass.info_for(code_bytes) if static_on else None
    det_mask = info.reach_mask if info is not None else None
    # verified loop-summary park plane (docs/static_pass.md,
    # MTPU_LOOPSUM): lanes arriving at a summarizable head park so the
    # host applies the closed form instead of the device unrolling the
    # loop. The cache key carries the marked head set — bench/tests
    # flip the gate mid-process and must not adopt a stale plane.
    loopsum_heads = ()
    try:
        if info is not None and loop_summary.enabled():
            loopsum_heads = tuple(
                sorted(loop_summary.summarizable_heads(info)))
    except Exception as e:
        log.debug("loop-summary heads unavailable: %s", e)
    key = (code_bytes, tuple(sorted(fentries)), static_on,
           loopsum_heads)
    cc = _CC_CACHE.get(key)
    if cc is None:
        loopsum_plane = (loop_summary.device_park_pcs(info)
                         if loopsum_heads else None)
        with trace.span("xla.compile_code", code_len=len(code_bytes)):
            cc = compile_code(code_bytes, func_entries=key[1],
                              det_mask=det_mask,
                              loopsum_pcs=loopsum_plane)
        if len(_CC_CACHE) >= 64:  # bound device-resident code tensors
            evicted = next(iter(_CC_CACHE))
            _CC_CACHE.pop(evicted)
            _CC_EPOCH.pop(evicted, None)
        _CC_CACHE[key] = cc
        _CC_EPOCH[key] = REQUEST_EPOCH[0]
    else:
        _note_cross_request_hit(_CC_EPOCH, key)
    return cc


# -- cross-tenant wave packing (docs/daemon.md §wave packing) ---------------


class _PackMember:
    """One member of a packed explore: the owner tag (request id), its
    code bytes, arena base, and function-name map. The verified
    loop-summary park planes pack per member (the owning svm applies
    the closed forms — solo behavior); the det-mask plane ships empty
    and the host static retire / jump patching stand down under
    packing (documented in PARITY.md) — issue identity is gated by
    those layers' own on/off equivalence."""

    __slots__ = ("owner", "code", "base", "func_names")

    def __init__(self, owner, code, base, func_names):
        self.owner = owner
        self.code = code
        self.base = base
        self.func_names = func_names


#: packed CompiledCode per member-key tuple (code bytes + sorted
#: function entries per member). Bounded like _CC_CACHE; the arena /
#: segment-count pow2 bucketing makes the underlying jit variants
#: repeat across distinct packs of the same shape.
_PACK_CC_CACHE: Dict[tuple, tuple] = {}
_PACK_CC_EPOCH: Dict[tuple, int] = {}


def _compiled_packed(member_keys: tuple):
    """(CompiledCode, bases) for a tuple of (code_bytes, fentries,
    loopsum_heads) member keys — the head set is part of the cache key
    for the same reason _compiled_code's is (gate flips mid-process
    must not adopt a stale park plane)."""
    key = tuple(member_keys)
    hit = _PACK_CC_CACHE.get(key)
    if hit is None:
        from ..analysis import static_pass
        from ..analysis.static_pass import loop_summary
        from ..ops.stepper import compile_packed_code

        spec = []
        for code, fentries, heads in key:
            plane = None
            if heads:
                try:
                    plane = loop_summary.device_park_pcs(
                        static_pass.info_for(code))
                except Exception:
                    plane = None
            spec.append((code, fentries, plane))
        with trace.span(
                "xla.compile_code",
                code_len=sum(len(c) for c, _f, _h in key),
                members=len(key)):
            cc, bases = compile_packed_code(spec)
        if len(_PACK_CC_CACHE) >= 32:
            evicted = next(iter(_PACK_CC_CACHE))
            _PACK_CC_CACHE.pop(evicted)
            _PACK_CC_EPOCH.pop(evicted, None)
        hit = _PACK_CC_CACHE[key] = (cc, bases)
        _PACK_CC_EPOCH[key] = REQUEST_EPOCH[0]
    else:
        _note_cross_request_hit(_PACK_CC_EPOCH, key)
    return hit


# -- jit warmup --------------------------------------------------------------
#
# The fused window dispatch compile only depends on SHAPES, so one
# all-dead window per variant compiles it before the sweep dispatches
# real work. (The data-dependent escalation gathers compile on first
# use.)

_WARM: Dict[tuple, str] = {}  # variant key -> "pending" | "ready"
_WARM_LOCK = None


def _mesh_key(mesh) -> Optional[tuple]:
    return None if mesh is None \
        else tuple(d.id for d in mesh.devices.flat)


def _variant_key(n_lanes: int, code_len: int, lane_kwargs: dict,
                 window: int, seed_bucket: int, mesh=None) -> tuple:
    from ..ops.stepper import _code_bucket

    return (n_lanes, _code_bucket(code_len),
            tuple(sorted(lane_kwargs.items())), window, seed_bucket,
            _mesh_key(mesh))


def _warm_one(n_lanes: int, code_len: int, lane_kwargs: dict,
              window: int, step_budget: int,
              seed_bucket: int = 16, mesh=None) -> None:
    """Compile one window-dispatch variant by running an all-dead
    window of the exact production shapes, on the sweep's mesh."""
    with trace.span("xla.compile_variant", n_lanes=n_lanes,
                    code_len=code_len, window=window,
                    seed_bucket=seed_bucket):
        _warm_one_inner(n_lanes, code_len, lane_kwargs, window,
                        step_budget, seed_bucket, mesh)


def _warm_one_inner(n_lanes: int, code_len: int, lane_kwargs: dict,
                    window: int, step_budget: int,
                    seed_bucket: int = 16, mesh=None) -> None:
    from ..ops.stepper import _code_bucket

    eng = LaneEngine(n_lanes=n_lanes, window=window,
                     step_budget=step_budget, mesh=mesh, **lane_kwargs)
    st = eng._acquire_state()
    # dummy code at the bucket length: shared across warms of the bucket
    code = b"\x00" * _code_bucket(max(code_len, 1))
    cc = eng._placed_code(code, _compiled_code(code, ()))
    big = seed_bucket > min(16, n_lanes)
    i32buf, u8buf, k, pv = eng._pack_window(
        [], [None] * n_lanes, list(range(n_lanes)), [],
        int(st.calldata.shape[1]), big=big)
    visited = eng._new_visited(cc)
    st, visited, out = _window_exec(
        st, cc, i32buf, u8buf, eng.exec_table, eng.taint_table,
        window, k, step_budget, pv, visited, eng._resume_flag)
    jax.block_until_ready(out)
    eng._release_state(st)


def warm_variant(n_lanes: int, code_len: int, lane_kwargs: dict,
                 window: int, step_budget: int,
                 seed_bucket: int = 16, mesh=None) -> bool:
    """Compile the (shape-)variant of the fused window dispatch on
    `mesh` (None: one device), once per process. True when it is
    compiled; False while another thread is compiling it. Thread-safe;
    a failed warm-up is counted (SolverStatistics.device_warmup_errors)
    and the sweep then meets the same error itself."""
    global _WARM_LOCK
    import threading

    if _WARM_LOCK is None:
        _WARM_LOCK = threading.Lock()
    key = _variant_key(n_lanes, code_len, lane_kwargs, window,
                       seed_bucket, mesh)
    with _WARM_LOCK:
        state = _WARM.get(key)
        if state == "ready":
            _note_cross_request_hit(_WARM_EPOCH, key)
            return True
        if state == "pending":
            return False
        _WARM[key] = "pending"
        _WARM_EPOCH[key] = REQUEST_EPOCH[0]
    try:
        _warm_one(n_lanes, code_len, lane_kwargs, window,
                  step_budget, seed_bucket, mesh)
    except Exception as e:
        from ..support.devices import note_device_error

        note_device_error("device_warmup_errors",
                          f"lane warm-up of {n_lanes} lanes", e)
    finally:
        with _WARM_LOCK:
            _WARM[key] = "ready"
    return True


# ops whose alu resolver takes pop-coerced bitvec args, keyed by arity
_ALU2 = {
    "ADD": alu.add, "SUB": alu.sub, "MUL": alu.mul, "DIV": alu.div,
    "SDIV": alu.sdiv, "MOD": alu.mod, "SMOD": alu.smod,
    "SIGNEXTEND": alu.signextend, "LT": alu.lt, "GT": alu.gt,
    "SLT": alu.slt, "SGT": alu.sgt, "AND": alu.and_, "OR": alu.or_,
    "XOR": alu.xor, "BYTE": alu.byte_op, "SHL": alu.shl,
    "SHR": alu.shr, "SAR": alu.sar,
}
_ALU3 = {"ADDMOD": alu.addmod, "MULMOD": alu.mulmod}

# pop arity per deferrable op (memo keys must ignore the unused operand
# slots — they hold whatever sat below the live operands on the stack)
_ARITY = {name: 2 for name in _ALU2}
_ARITY.update({name: 3 for name in _ALU3})
_ARITY.update({"EQ": 2, "EXP": 2, "ISZERO": 1, "NOT": 1,
               "SLOAD": 1, "CALLDATALOAD": 1, "SHA3": 3,
               "BALANCE": 1})


#: steps per fused dispatch. The in-dispatch while_loop exits as soon
#: as no lane is RUNNING, so a large window costs nothing when paths
#: park early — but every extra dispatch pays a host-device round
#: trip. Deep device paths (SHA3 defer + symbolic-storage
#: mode keep token transfers on-device end-to-end) want whole
#: transactions inside ONE window. Bounded by the deferred-log
#: capacity only in the worst case (dlog_full parks, degraded not
#: wrong).
DEFAULT_WINDOW = 256
DEFAULT_STEP_BUDGET = 8192

#: in-explore safety caps for the engine's id-keyed memos (they also
#: clear wholesale at every explore — persistent corpus engines
#: otherwise grow them without bound; see _reset_explore_memos)
_CDL_CACHE_CAP = 1 << 16
_RECORD_MEMO_CAP = 1 << 20


#: per-code fork-scale observations: code -> peak width demand (lanes
#: concurrently occupied + entries waiting for a slot) in any one
#: explore. Feeds pick_width so a contract that demonstrably forks
#: wide gets a wide engine on the next sweep, while small analyses
#: stay on narrow (cheap) planes.
PATH_HISTORY: Dict[bytes, int] = {}

#: benchmark/test hook: pin the autotuned width so a timed run never
#: cold-compiles a new variant mid-measurement (bench.py warms exactly
#: this width before the clock starts). None = autotune normally.
FORCE_WIDTH: Optional[int] = None


def pick_mesh(width: int):
    """Device mesh for a sweep under the args.tpu_mesh policy, or None
    for single-device execution. Auto (-1) shards over every local
    device when more than one exists; the width must divide evenly and
    leave at least 8 lanes per shard (narrower shards pay collective
    overhead for no batching win). A 16-lane engine sharded 2x8 used
    to trip an XLA SPMD partitioner bug — the select-to-bucket
    cumsum+scatter sites whose index count equals the plane length
    partitioned their operand but not their indices, failing HLO
    verification ("updates bound is 8, scatter_indices bound is 16");
    those sites now select via sort (see _unique_table/_window_exec),
    which partitions cleanly. Single-chip hosts always resolve to
    None."""
    from ..support.support_args import args

    setting = getattr(args, "tpu_mesh", -1)
    if setting == 0:
        return None
    nd = jax.device_count()
    if setting > 0:
        nd = min(setting, nd)
    while nd > 1 and (width % nd or width // nd < 8):
        nd -= 1
    if nd <= 1:
        return None
    from ..parallel.mesh import make_mesh

    return make_mesh(nd)


def pick_width(cap: int, n_entries: int,
               code: Optional[bytes] = None,
               headroom: int = 8) -> int:
    """Engine width for a sweep: the smallest power-of-two bucket with
    generous fork headroom over the entry batch (and over the code's
    observed fork scale), bounded by the configured lane cap. The cap
    is CAPACITY, not a mandate — a 4096-wide plane set for a 30-path
    contract pays init, transfers and per-window compute for lanes
    that never run. Correctness never depends on the width: fork
    pressure stalls parents until slots free, and the host
    spill/refill path absorbs overflow
    (tests/test_lane_spill_refill.py). Worklists that genuinely grow
    pick a wider engine on the next sweep. A capacity-autoprobe clamp
    (CAPACITY_CLAMPS / stats.json via cost_model) caps the width below
    any live-plane size that kernel-faulted this worker class AT THE
    REQUESTED SHAPE — clamps are per pow2 shape, so a 256k fault's
    clamp never narrows a 32k sweep — and the engine degrades through
    the spill/refill path instead of faulting (logged at WARNING once
    when the clamp actually binds)."""
    global _CLAMP_WARNED
    if FORCE_WIDTH is not None:
        return max(min(cap, FORCE_WIDTH), 1)
    clamp = capacity_clamp(cap)
    if clamp is not None and clamp < cap:
        if not _CLAMP_WARNED:
            _CLAMP_WARNED = True
            log.warning(
                "lane width capped at %d by the capacity autoprobe "
                "(configured cap %d kernel-faulted a worker at that "
                "shape; overflow degrades via spill/refill)",
                clamp, cap)
        cap = max(clamp, 1)
    if cap <= 64:
        return max(cap, 1)
    demand = max(n_entries * headroom,
                 PATH_HISTORY.get(code, 0) if code else 0)
    want = 64
    while want < cap and want < demand:
        want *= 2
    return min(want, cap)


class LaneEngine:
    """Owns one lane batch + object table for a single contract's
    exploration."""

    def __init__(self, n_lanes: int = 256, window: Optional[int] = None,
                 step_budget: int = DEFAULT_STEP_BUDGET,
                 blocked_ops=None, adapters=None, mesh=None,
                 slim_stop: bool = False, **lane_kwargs):
        self.n_lanes = n_lanes
        # resolve at call time: bench.py --smoke (and tests) retune the
        # module-level DEFAULT_WINDOW before svm builds the engine
        self.window = DEFAULT_WINDOW if window is None else window
        self.step_budget = step_budget
        self.lane_kwargs = lane_kwargs
        #: svm guarantees no essential hook watches STOP: lanes parked
        #: at a top-level STOP materialize without the stack/memory
        #: rebuild the STOP transaction-end path never reads
        self.slim_stop = slim_stop
        # multi-device SPMD: when a jax.sharding.Mesh is supplied, the
        # lane planes are built sharded over its `lanes` axis
        # (parallel/mesh.init_planes) and every fused dispatch runs
        # SPMD under GSPMD partitioning — the SAME jitted programs,
        # with XLA inserting the cross-device collectives the
        # cumsum/scatter phases need. Host buffers go in replicated
        # (_to_device); each pull gathers from every chip (_pull).
        self.mesh = mesh
        self._rep_sh = None
        if mesh is not None:
            from ..parallel.mesh import replicated

            if n_lanes % mesh.devices.size:
                raise ValueError(
                    f"{n_lanes} lanes not divisible by "
                    f"{mesh.devices.size} mesh devices")
            self._rep_sh = replicated(mesh)
        #: per-code replicated compiled-code tensors (engines persist
        #: across explores; re-broadcasting cc each sweep is waste)
        self._cc_rep: Dict[bytes, object] = {}
        #: device-resident / host coverage bitmaps per code (see explore)
        self._visited_dev: Dict[bytes, object] = {}
        self.visited_by_code: Dict[bytes, np.ndarray] = {}
        # opcodes with registered detector hooks must park so the hooks
        # fire host-side; remove them from the device-executable set.
        # Modules with a lane adapter (analysis/module/lane_adapters.py)
        # are instead served at drain time and their hooks stay lifted.
        import jax.numpy as jnp

        from ..support.devices import enable_compile_cache

        enable_compile_cache()

        table = symstep.SYM_EXECUTABLE.copy()
        for name in blocked_ops or ():
            if name in _OPB:
                table[_OPB[name]] = False
        #: the hook-blocked opcode set, kept for the wave-pack
        #: coordinator to replicate this config on a packed engine
        self.blocked_ops = frozenset(blocked_ops or ())
        self.exec_table = jnp.asarray(table)
        self.adapters = list(adapters or ())
        taint = np.zeros(256, bool)
        for ad in self.adapters:
            for name in ad.taint_ops:
                if name in _OPB:
                    taint[_OPB[name]] = True
        self.taint_table = jnp.asarray(taint)
        # arithmetic records get their pc in the memo key when an
        # adapter annotates them (the annotation site is per-pc)
        self._annot_ops = {
            op for ad in self.adapters
            for op in ("ADD", "SUB", "MUL", "EXP")
            if op in ad.taint_ops
        }
        self.objects = ObjectTable()
        # (lane, record-slot) -> object id for the most recent window's
        # deferred records; the device-side remap of these lands at the
        # NEXT window's fused dispatch, so retired-row resolution (_obj)
        # reads this map directly in the meantime
        self._prov: Dict[Tuple[int, int], int] = {}
        self._group_seq = 0
        self._func_names: Dict[int, str] = {}
        # repeated CALLDATALOADs at the same offset across lanes resolve
        # to the same word term; building it once matters (32 If+select
        # terms per word). All three memos below key on id()s and
        # per-window (step, pc) tuples, which alias across codes once
        # the owning objects die — they reset at every explore() (see
        # _reset_explore_memos) and values pin the id-keyed owners so
        # an id cannot be recycled while its entry is live.
        self._cdl_cache: Dict[Tuple[int, int], tuple] = {}
        self._record_memo: Dict[tuple, int] = {}
        self._fired_sites: set = set()
        self._memo_pins: list = []
        #: static pre-analysis of the current explore's code (None =
        #: gate off / unavailable) + per-template pending-PI memo
        self._static_info = None
        self._static_clean: Dict[int, bool] = {}
        self.stats = {
            "seeded": 0, "reseeded": 0, "forks": 0, "records": 0,
            "parked": 0, "dead": 0, "device_steps": 0, "windows": 0,
            "resumed": 0, "overlap_mat": 0, "overlap_mat_ms": 0,
            # solver time of the overlapped fork screen
            # (docs/drain_pipeline.md)
            "overlap_solve_ms": 0,
            "fork_screened": 0, "fork_killed": 0,
            # window-boundary merge/subsume pass (docs/lane_merge.md)
            "lanes_merged": 0, "lanes_subsumed": 0, "merge_rounds": 0,
            # static pre-analysis consumers (docs/static_pass.md)
            "static_retired": 0, "static_jump_patches": 0,
            # streaming retire pipeline (docs/drain_pipeline.md):
            # bounded gathers issued, D2H pull wall hidden behind the
            # next window's execution, spill candidates merged before
            # materialization, and the deferral ring's peak occupancy
            "retire_chunks": 0, "retire_overlap_ms": 0,
            "spill_merged": 0, "ring_high_water": 0,
        }
        # static-pass run context, set by svm per sweep (the engine is
        # cached across sweeps and transactions): the active-detector
        # anchor mask (None = screen off), whether the current round is
        # the run's last (open states unused afterwards), and whether
        # patching a statically-resolved symbolic JUMP dest is safe
        # (off while an arbitrary-jump-class detector is active — its
        # issue PREDICATE is the dest's symbolicness)
        self.static_active_mask = None
        self.static_final_tx = False
        self.static_jump_patch_ok = False
        #: active-module names for the taint-refined reach plane
        #: (docs/static_pass.md; None = refinement off, raw mask)
        self.static_module_names = None
        # in-place SHA3 resume: off whenever a detector hooks SHA3
        # (the hook must fire host-side; no adapter lifts SHA3 today)
        self.resume_on = "SHA3" not in set(blocked_ops or ())
        self._resume_flag = jnp.asarray(
            1 if self.resume_on else 0, jnp.int32)
        self.last_run_stats: Optional[dict] = None
        #: mid-flight wave export client (docs/checkpoint.md; set by
        #: svm from the migration bus): polled at every window
        #: boundary — `want(live)` lanes retire through the escalation
        #: gather, materialize, and hand to `deliver(states)` as an
        #: in-flight migration batch. None = seam off (the default).
        self.export_client = None
        #: live lane ctxs of an explore in progress (SIGTERM dump
        #: path: support/checkpoint.snapshot_live_states)
        self._explore_ctxs = None
        #: packed-wave issue attribution (docs/daemon.md §wave
        #: packing): owner tag -> context manager activating that
        #: request's RunContext, so drain-time site firing lands
        #: issues in the OWNING member's detector lists. None (the
        #: default, incl. every plain explore) fires sites under the
        #: caller's context — bit-for-bit today's behavior.
        self.owner_context = None
        #: per-boundary _merge_fingerprint cache (None = not computed
        #: this boundary, False = kernel failed) shared by the window
        #: merge and the merge-before-spill pass — ONE dispatch serves
        #: both (docs/drain_pipeline.md)
        self._fp_boundary = None
        #: deferred retire/materialize ring of the explore in progress
        #: (laser/retire_ring.py); None between explores
        self._ring = None
        #: materialize() bumps stats off-thread under MTPU_MAT_WORKERS>1
        self._stats_lock = threading.Lock()

    # -- placement on the mesh ---------------------------------------------

    def _to_device(self, x):
        """A host array for a dispatch: replicated over the mesh, so
        every meshed program meets one fixed placement and none is
        compiled twice; on one device, the default device as before."""
        if self._rep_sh is None:
            return jnp.asarray(x)
        return jax.device_put(x, self._rep_sh)

    def _pull(self, x):
        """jax.device_get of device arrays. On a mesh it gathers from
        every chip: a mesh.pull span with the bytes it brings to the
        host, which SolverStatistics.mesh_pull_bytes also counts."""
        if self.mesh is None:
            return jax.device_get(x)
        nbytes = sum(a.nbytes for a in jax.tree_util.tree_leaves(x))
        with trace.span("mesh.pull", bytes=nbytes):
            out = jax.device_get(x)
        from ..smt.solver.solver_statistics import SolverStatistics

        SolverStatistics().bump(mesh_pull_bytes=nbytes)
        return out

    def _placed_code(self, code_bytes: bytes, cc):
        """cc for this engine's dispatches. On a mesh, the code tensors
        and the op tables are replicated over every chip, memoized per
        code: engines persist across explores and must not re-broadcast
        every sweep."""
        if self._rep_sh is None:
            return cc
        cc_r = self._cc_rep.get(code_bytes)
        if cc_r is None:
            cc_r = jax.tree_util.tree_map(
                lambda x: jax.device_put(x, self._rep_sh), cc)
            self._cc_rep[code_bytes] = cc_r
            self.exec_table, self.taint_table, self._resume_flag = \
                jax.device_put((self.exec_table, self.taint_table,
                                self._resume_flag), self._rep_sh)
        return cc_r

    def _new_visited(self, cc):
        """A cleared coverage bitmap, replicated over the mesh as the
        window program returns it."""
        visited = jnp.zeros(cc.packed.shape[0], bool)
        if self._rep_sh is None:
            return visited
        return jax.device_put(visited, self._rep_sh)

    def _full_bucket(self) -> int:
        """Full-width seed bucket for backlog drains, kept strictly
        below the plane width under a mesh: a k == n seed scatter
        trips the SPMD partitioner (operand sharded, indices not —
        see pick_mesh)."""
        return self.n_lanes if self.mesh is None \
            else max(self.n_lanes // 2, 1)

    # -- seeding ------------------------------------------------------------
    # (eligibility is decided by the caller: svm._lane_engine_sweep)

    def _env_words(self, gs: GlobalState):
        """(slot -> (concrete value | None, sid)) for the env plane,
        mirroring the corresponding instruction handlers."""
        env = gs.environment
        ms = gs.mstate

        def entry(val):
            # (concrete value, None) | (None, symbolic wrapper) — the
            # object slot must be None for concrete values: downstream
            # consumers test `obj is not None`
            if isinstance(val, int):
                return val, None
            if isinstance(val, BitVec) and val.value is not None:
                return val.value, None
            return None, val  # symbolic: sid assigned after adapters

        out = {}
        out["ADDRESS"] = entry(env.address)
        out["ORIGIN"] = entry(env.origin)
        out["CALLER"] = entry(env.sender)
        out["CALLVALUE"] = entry(env.callvalue)
        out["GASPRICE"] = entry(env.gasprice)
        out["COINBASE"] = entry(gs.new_bitvec("coinbase", 256))
        out["TIMESTAMP"] = entry(
            symbol_factory.BitVecSym("timestamp", 256))
        out["NUMBER"] = entry(env.block_number)
        out["DIFFICULTY"] = entry(gs.new_bitvec("block_difficulty", 256))
        out["GASLIMIT"] = entry(ms.gas_limit)
        out["CHAINID"] = entry(env.chainid)
        out["SELFBALANCE"] = entry(env.active_account.balance())
        out["BASEFEE"] = entry(env.basefee)
        return out

    def _seed_spec(self, gs: GlobalState, calldata_cap: int,
                   member=None):
        """(LaneCtx, host-side per-lane values) for one entry state.
        ``member`` is the packed-wave member record (owner tag, arena
        base, function-name map) or None for a plain explore."""
        env = gs.environment
        acct = env.active_account
        ms = gs.mstate

        # instruction index <-> byte address maps
        ilist = env.code.instruction_list
        code_len = len(code_to_bytes(env.code) or b"")
        addr2idx = np.full(max(code_len + 2, 2), len(ilist),
                           dtype=np.int32)
        for i, ins in enumerate(ilist):
            if ins["address"] < addr2idx.shape[0]:
                addr2idx[ins["address"]] = i

        storage_raw = acct.storage._standard_storage.raw
        virgin_zero = (
            storage_raw.op == T.CONST_ARRAY
            and T.is_const(storage_raw.args[0])
            and storage_raw.args[0].val == 0
        )

        calldata = env.calldata
        concrete_cd = (
            isinstance(calldata, ConcreteCalldata)
            and all(isinstance(x, int)
                    for x in calldata._concrete_calldata)
            and len(calldata._concrete_calldata)
            <= min(calldata_cap, SEED_CD)
        )

        gas0_min, gas0_max = ms.min_gas_used, ms.max_gas_used
        self._group_seq += 1
        dev_limit = max(int(ms.gas_limit) - int(gas0_min), 0) \
            if isinstance(ms.gas_limit, int) else 0xFFFFFFF

        if member is None:
            ctx = LaneCtx(gs, addr2idx, storage_raw, calldata,
                          gas0_min, gas0_max)
        else:
            ctx = LaneCtx(gs, addr2idx, storage_raw, calldata,
                          gas0_min, gas0_max, owner=member.owner,
                          code_base=member.base,
                          func_names=member.func_names)

        envw = self._env_words(gs)
        if self.adapters:
            # taint seeding: annotating the env source terms once per
            # seed is host-equivalent — the interpreter's post-hooks
            # annotate the same shared wrapper the handlers push.
            # Adapters may also REPLACE an entry (e.g. ORIGIN gets its
            # own wrapper so the shared sender object isn't tainted)
            env_objects = {
                name: obj for name, (val, obj) in envw.items()
                if obj is not None
            }
            for ad in self.adapters:
                ad.seed_env(env_objects, gs)
            envw = {
                name: (val, env_objects.get(name, obj))
                for name, (val, obj) in envw.items()
            }
        env_vals = np.zeros((symstep.N_ENV, bv256.NLIMBS), np.uint32)
        env_sids = np.zeros(symstep.N_ENV, np.int32)
        for name, slot in symstep.ENV_SLOTS.items():
            val, obj = envw[name]
            if obj is not None:
                env_sids[slot] = self.objects.add(obj)
            else:
                env_vals[slot] = bv256.int_to_limbs(val or 0)

        cd_buf = np.zeros(calldata_cap, np.uint8)
        cd_size = 0
        cd_sym = 0
        cd_size_sid = 0
        if concrete_cd:
            data = calldata._concrete_calldata
            cd_buf[: len(data)] = np.asarray(data, np.uint8)
            cd_size = len(data)
        else:
            cd_sym = 1
            size = calldata.calldatasize
            if isinstance(size, BitVec) and size.value is not None:
                cd_size = min(int(size.value), 1 << 29)
            else:
                cd_size_sid = self.objects.add(size)

        # mid-path seeds (host spill/refill): device pc is a byte
        # address; stack objects become sids; memory must be concrete
        # bytes (ints or concrete 8-bit terms — eligibility checked by
        # svm.lane_seedable)
        n_depth = self.lane_kwargs.get("stack_depth", 64)
        mem_cap = self.lane_kwargs.get("memory_bytes", 4096)
        if len(ms.stack) > min(SEED_STACK, n_depth) or int(
            ms.memory_size
        ) > min(SEED_MEM, mem_cap):
            # callers gate on lane_seedable; packing would silently
            # truncate a deeper state into wrong execution
            raise ValueError("seed exceeds SEED_STACK/SEED_MEM columns")
        byte_pc = 0
        if ms.pc:
            byte_pc = ilist[ms.pc]["address"]
        if member is not None:
            byte_pc += member.base  # seed in arena coordinates
        stack_v = np.zeros((n_depth, bv256.NLIMBS), np.uint32)
        stack_s = np.zeros(n_depth, np.int32)
        for i, item in enumerate(ms.stack):
            if isinstance(item, int):
                stack_v[i] = bv256.int_to_limbs(item)
            elif isinstance(item, BitVec) and item.value is not None:
                stack_v[i] = bv256.int_to_limbs(item.value)
            else:
                if isinstance(item, Bool):
                    item = If(item, _bv_val(1), _bv_val(0))
                stack_s[i] = self.objects.add(item)
        mem_v = np.zeros(mem_cap, np.uint8)
        mem_k = np.zeros(mem_cap, np.uint8)
        for key, val in ms.memory._memory.items():
            if isinstance(val, int):
                mem_v[key] = val & 0xFF
                mem_k[key] = symstep.KIND_BYTE_INT
            else:  # concrete 8-bit term (eligibility guarantees)
                mem_v[key] = val.value & 0xFF
                mem_k[key] = symstep.KIND_CONC_WORD

        return ctx, dict(
            group=self._group_seq,
            sbase=0 if virgin_zero else 1,
            calldata=cd_buf, cd_size=cd_size, cd_sym=cd_sym,
            cd_size_sid=cd_size_sid, env=env_vals, env_sid=env_sids,
            gas_limit=dev_limit,
            pc=byte_pc, sp=len(ms.stack), msize=int(ms.memory_size),
            stack_v=stack_v, stack_s=stack_s, mem_v=mem_v, mem_k=mem_k,
        )

    def _pack_window(self, entries, ctxs: List[Optional[LaneCtx]],
                     free, kill, calldata_cap: int, big: bool = False,
                     resumes=()):
        """Pack EVERYTHING the next window dispatch needs from the host
        into two flat buffers (one i32, one u8): seed rows, free-slot
        stack, the previous drain's provisional-sid resolutions, and
        the kill list — each host->device array pays its own transfer
        latency, so the count is what matters.
        Returns (i32buf, u8buf, statics) with the layout of
        _seed_sections."""
        n = self.n_lanes
        n_env = symstep.N_ENV
        lanes, specs = [], []
        for lane, gs, member in entries:
            ctx, spec = self._seed_spec(gs, calldata_cap, member)
            ctxs[lane] = ctx
            lanes.append(lane)
            specs.append(spec)
        n_depth = self.lane_kwargs.get("stack_depth", 64)
        mem_cap = self.lane_kwargs.get("memory_bytes", 4096)
        d_recs = self.lane_kwargs.get("dlog_records", 64)
        sd = min(SEED_STACK, n_depth)
        mc = min(SEED_MEM, mem_cap)
        ccw = min(SEED_CD, calldata_cap)
        # two seed buckets only: the small one covers the common
        # trickle (always compiled — a second jit variant costs far
        # more than all-padding seed sections); the full-width one
        # drains seed floods in one window. explore() only requests
        # `big` once that variant is warm.
        k = n if big else min(16, n)
        if self.mesh is not None and k >= n and n > 1:
            # a k == n seed scatter trips the SPMD partitioner (the
            # plane operand shards, the index vector stays replicated
            # — see pick_mesh); keep the bucket strictly below the
            # plane width and drain floods over two windows instead
            k = max(n // 2, 1)
        assert len(lanes) <= k and len(resumes) <= k

        idx = np.full(k, n, np.int32)  # padding -> out of range -> drop
        idx[: len(lanes)] = lanes
        i32p = np.zeros((k, 8 + n_env), np.int32)
        u32p = np.zeros((k, 1 + n_env * bv256.NLIMBS), np.uint32)
        u8p = np.zeros((k, ccw), np.uint8)
        stack_v = np.zeros((k, sd * bv256.NLIMBS), np.uint32)
        stack_s = np.zeros((k, sd), np.int32)
        mem_v = np.zeros((k, mc), np.uint8)
        mem_k = np.zeros((k, mc), np.uint8)
        for i, s in enumerate(specs):
            i32p[i, 0] = s["sbase"]
            i32p[i, 1] = s["cd_size"]
            i32p[i, 2] = s["cd_sym"]
            i32p[i, 3] = s["cd_size_sid"]
            i32p[i, 4] = s["pc"]
            i32p[i, 5] = s["sp"]
            i32p[i, 6] = s["msize"]
            i32p[i, 7] = s["group"]
            i32p[i, 8:] = s["env_sid"]
            u32p[i, 0] = s["gas_limit"]
            u32p[i, 1:] = s["env"].reshape(-1)
            u8p[i] = s["calldata"][:ccw]
            stack_v[i] = s["stack_v"][:sd].reshape(-1)
            stack_s[i] = s["stack_s"][:sd]
            mem_v[i] = s["mem_v"][:mc]
            mem_k[i] = s["mem_k"][:mc]
        fs = np.zeros(n, np.int32)
        fs[: len(free)] = free
        # sparse provisional-sid resolutions: padding pairs hold an
        # out-of-range encoded slot (dropped by the device scatter)
        pv = min(PROV_BUCKET, n * d_recs) \
            if len(self._prov) <= PROV_BUCKET else n * d_recs
        prov_pairs = np.full((pv, 2), n * d_recs, np.int32)
        for j, ((lane, slot), oid) in enumerate(self._prov.items()):
            prov_pairs[j, 0] = lane * d_recs + slot
            prov_pairs[j, 1] = oid
        kl = np.full(n, n, np.int32)
        kl[: len(kill)] = kill
        r_idx = np.full(k, n, np.int32)
        r_i32 = np.zeros((k, 6), np.int32)
        r_limbs = np.zeros((k, bv256.NLIMBS), np.uint32)
        for i, (lane, pc, sp, msize, ming, maxg, sid, limbs) \
                in enumerate(resumes):
            r_idx[i] = lane
            r_i32[i] = (pc, sp, msize, ming, maxg, sid)
            if limbs is not None:
                r_limbs[i] = limbs

        parts = [idx, i32p.reshape(-1), u32p.reshape(-1).view(np.int32),
                 fs, np.array([len(free)], np.int32),
                 prov_pairs.reshape(-1), kl,
                 stack_v.reshape(-1).view(np.int32),
                 stack_s.reshape(-1),
                 r_idx, r_i32.reshape(-1),
                 r_limbs.reshape(-1).view(np.int32)]
        i32buf = np.concatenate([np.ascontiguousarray(p, np.int32)
                                 for p in parts])
        u8buf = np.concatenate([u8p.reshape(-1), mem_v.reshape(-1),
                                mem_k.reshape(-1)])

        self.stats["seeded"] += len(entries)
        # mid-path re-entries (the spill/refill path) vs fresh tx seeds
        self.stats["reseeded"] += sum(1 for s in specs if s["pc"])
        return (self._to_device(i32buf), self._to_device(u8buf), k, pv)

    # -- drain ---------------------------------------------------------------

    def _resolve_arg(self, sid: int, val_limbs, prov: Dict[Tuple[int, int],
                                                           int], d_recs):
        if sid == 0:
            return _bv_val(_limbs_int(val_limbs))
        if sid > 0:
            return self.objects[sid]
        idx = -sid - 1
        key = (idx // d_recs, idx % d_recs)
        return self.objects[prov[key]]

    def _resolve_record(self, ctx: LaneCtx, opname: str, args):
        """args: raw resolved operand objects in pop order."""
        if opname in _ALU2:
            return _ALU2[opname](alu.to_bitvec(args[0]),
                                 alu.to_bitvec(args[1]))
        if opname in _ALU3:
            return _ALU3[opname](alu.to_bitvec(args[0]),
                                 alu.to_bitvec(args[1]),
                                 alu.to_bitvec(args[2]))
        if opname == "EQ":
            return alu.eq(args[0], args[1])
        if opname == "ISZERO":
            return alu.iszero(args[0])
        if opname == "NOT":
            return alu.not_(alu.to_bitvec(args[0]))
        if opname == "EXP":
            result, constraint = alu.exp(alu.to_bitvec(args[0]),
                                         alu.to_bitvec(args[1]))
            assert constraint is None, \
                "device deferred an impure EXP (stepper bug)"
            return result
        if opname == "CALLDATALOAD":
            off = alu.to_bitvec(args[0])
            key = (id(ctx.calldata), off.raw.tid)
            hit = self._cdl_cache.get(key)
            if hit is not None:
                return hit[1]
            cached = ctx.calldata.get_word_at(off)
            if len(self._cdl_cache) > _CDL_CACHE_CAP:
                self._cdl_cache.clear()
            # the value pins the calldata object: its id (the key) can
            # never be recycled onto a different calldata while the
            # entry is live
            self._cdl_cache[key] = (ctx.calldata, cached)
            return cached
        if opname == "SLOAD":
            return _storage_read_term(ctx.storage_seed_raw,
                                      alu.to_bitvec(args[0]))
        if opname == "BALANCE":
            # symbolic address: the interpreter reads the global
            # balances array directly (instructions.py balance_)
            return ctx.template.world_state.balances[
                alu.to_bitvec(args[0])]
        if opname == "SHA3":
            # device-read input words + packed meta (length + per-byte
            # memory kinds). Rebuild the hash input byte-for-byte the
            # way the interpreter's sha3_ handler reads Memory (ints
            # for untouched/MSTORE8 bytes, 8-bit const terms for
            # concrete-word bytes, Extract slices for symbolic words):
            # the keccak input term tids then match the host exactly.
            from ..smt import Concat
            from .function_managers import keccak_function_manager

            meta = alu.to_bitvec(args[2]).value
            length = meta & 0xFFFFFFFF
            all_sym_kinds = (1 << 64) - 1  # every 2-bit field == 3
            byte_list: list = []
            for w in range(length // 32):
                kinds = (meta >> (32 + w * 64)) & all_sym_kinds
                if kinds == all_sym_kinds:  # sid-carried word term
                    word = args[w]
                    if isinstance(word, Bool):
                        word = If(word, _bv_val(1), _bv_val(0))
                    byte_list.extend(
                        simplify(Extract(255 - 8 * j, 248 - 8 * j,
                                         word))
                        for j in range(32))
                    continue
                word_int = alu.to_bitvec(args[w]).value or 0
                raw = word_int.to_bytes(32, "big")
                for j in range(32):
                    kind = (kinds >> (2 * j)) & 3
                    if kind == symstep.KIND_CONC_WORD:
                        byte_list.append(
                            symbol_factory.BitVecVal(raw[j], 8))
                    else:
                        byte_list.append(raw[j])
            if all(isinstance(bb, int) for bb in byte_list):
                data = symbol_factory.BitVecVal(
                    int.from_bytes(bytes(byte_list), "big"),
                    length * 8)
            else:
                parts = [
                    bb if isinstance(bb, BitVec)
                    else symbol_factory.BitVecVal(bb, 8)
                    for bb in byte_list
                ]
                data = simplify(Concat(parts))
            return keccak_function_manager.create_keccak(data)
        raise AssertionError(f"unresolvable deferred op {opname}")

    def _jumpi_site_work(self, ctx, lane, cond, step, byte_pc,
                         fentry, gmin, gmax, dest=0):
        """Drain-time detector work for one path-condition record:
        per-lane sink promotions, plus site-firing modules deduped
        across the sibling lanes sharing the record (the interpreter
        fires its pre-hook once per JUMPI execution; issue identity is
        per (site, condition, path prefix)). The site's stack tail is
        the real pre-hook stack [-2]=condition, [-1]=jump destination
        (always concrete on device — forks require dest_ok)."""
        prefix = [c for (_, c) in ctx.conds]
        site = _DrainSite(self, ctx, step, byte_pc, fentry, gmin, gmax,
                          stack_tail=(cond, _bv_val(dest)),
                          prefix=prefix)
        for ad in self.adapters:
            anns = ad.on_jumpi(cond, site)
            if anns:
                ctx.promos.setdefault(id(ad), []).extend(
                    (step, a) for a in anns)
        key = (step, byte_pc, cond.raw.tid,
               tuple(c.raw.tid for c in prefix))
        if key in self._fired_sites:
            return
        self._fired_sites.add(key)
        if self.owner_context is not None:
            # packed wave: site-firing modules append to the global
            # detector singletons, so fire under the lane OWNER's
            # RunContext (per-request issue attribution)
            from .retire_ring import owner_of

            with self.owner_context(owner_of(ctx)):
                for ad in self.adapters:
                    ad.on_jumpi_site(cond, site)
            return
        for ad in self.adapters:
            ad.on_jumpi_site(cond, site)

    def _drain_host(self, recs, forks,
                    ctxs: List[Optional[LaneCtx]]
                    ) -> Tuple[Dict[Tuple[int, int], int], List[int]]:
        """Resolve one window's canonical records and fork table into
        facade terms; returns (provisional-sid resolutions, dead
        lanes). Pure host work — the provisional remap + log reset
        ride the NEXT window's fused dispatch.

        recs: [(step, lane, slot, op, pc, fentry, sids(3), vals(3,8))]
        — one entry per DISTINCT term (device-deduped; `lane` is the
        canonical instance's lane). forks: [(step, parent, child, pc,
        sid, gmin, gmax, fentry)]. Events interleave in global step
        order, so a fork clones its parent's context exactly as
        accumulated at that step — condition prefixes, sink
        promotions, and annotations inherit by construction (the
        interpreter's deepcopy-at-JUMPI semantics)."""
        d_recs = self.lane_kwargs.get("dlog_records", 64)
        prov: Dict[Tuple[int, int], int] = {}
        dead: List[int] = []
        dead_set: set = set()
        events = [(r[0], 0, r) for r in recs] \
            + [(f[0], 1, f) for f in forks]
        events.sort(key=lambda e: (e[0], e[1]))
        for _, kind, ev in events:
            if kind == 0:
                step, lane, slot, op, pc, fentry, sids, vals = ev
                opname = "SLOAD_RW" if op == symstep.REC_SLOAD_RW \
                    else _OPN[op]
                ctx = ctxs[lane]
                if opname == "SSTORE":
                    # write-mirror + taint-sink record (never deduped,
                    # per-lane): the mirror feeds SLOAD_RW resolution
                    value = self._resolve_arg(sids[1], vals[1], prov,
                                              d_recs)
                    key = self._resolve_arg(sids[0], vals[0], prov,
                                            d_recs)
                    ctx.swrites.append((alu.to_bitvec(key),
                                        alu.to_bitvec(value)))
                    if lane in dead_set:
                        continue
                    site = _DrainSite(self, ctx, step, pc, fentry)
                    for ad in self.adapters:
                        for ann in ad.on_sstore(alu.to_bitvec(value),
                                                site,
                                                alu.to_bitvec(key)):
                            ctx.promos.setdefault(id(ad), []).append(
                                (step, ann))
                    continue
                if opname == "SLOAD_RW":
                    # mode SLOAD: read-over-write over the per-path
                    # mirror, folded onto the seed storage (the lane's
                    # write history at this step is exactly
                    # ctx.swrites — records replay in step order).
                    # Never memoized: identical (key, pc) records on
                    # different paths see different mirrors.
                    key = alu.to_bitvec(self._resolve_arg(
                        sids[0], vals[0], prov, d_recs))
                    term = _storage_read_term(ctx.storage_seed_raw,
                                              key)
                    for wk, wv in ctx.swrites:
                        term = If(wk == key, wv, term)
                    term = simplify(term)
                    if isinstance(term, Bool):
                        term = If(term, _bv_val(1), _bv_val(0))
                    prov[(lane, slot)] = self.objects.add(term)
                    continue
                # cross-WINDOW dedup via the memo (the device already
                # deduped within the window)
                key_parts = [opname]
                for j in range(_ARITY[opname]):
                    sid = sids[j]
                    if sid == 0:
                        key_parts.append(("c", _limbs_int(vals[j])))
                    elif sid > 0:
                        key_parts.append(("o", sid))
                    else:
                        idx = -sid - 1
                        key_parts.append(
                            ("o", prov[(idx // d_recs,
                                        idx % d_recs)]))
                # SLOAD/CALLDATALOAD resolve against per-seed context;
                # pin the template so its id (part of the key) cannot
                # be recycled while the memo entry is live
                if opname in ("SLOAD", "CALLDATALOAD", "BALANCE"):
                    key_parts.append(("ctx", id(ctx.template)))
                    self._memo_pins.append(ctx.template)
                # annotated arithmetic is per-site AND per-seed: two
                # executions at different pcs (or from different entry
                # states) must annotate separately — the interpreter
                # captures a distinct ostate per execution
                if opname in self._annot_ops:
                    key_parts.append(("pc", pc, "ctx",
                                      id(ctx.template)))
                    self._memo_pins.append(ctx.template)
                key = tuple(key_parts)
                oid = self._record_memo.get(key)
                if oid is None:
                    args = [
                        self._resolve_arg(sids[j], vals[j], prov,
                                          d_recs)
                        for j in range(3)
                    ]
                    if opname in self._annot_ops:
                        site = _DrainSite(self, ctx, step, pc, fentry)
                        cargs = [alu.to_bitvec(x)
                                 if not isinstance(x, int)
                                 else _bv_val(x) for x in args[:2]]
                        for ad in self.adapters:
                            ad.pre_resolve(opname, cargs, site)
                        args[:2] = cargs
                    obj = self._resolve_record(ctx, opname, args)
                    # sids model stack slots: apply MachineStack
                    # .append's coercion (state/machine_state.py)
                    if isinstance(obj, Bool):
                        obj = If(obj, _bv_val(1), _bv_val(0))
                    elif isinstance(obj, int):
                        obj = _bv_val(obj)
                    oid = self.objects.add(obj)
                    if len(self._record_memo) > _RECORD_MEMO_CAP:
                        self._record_memo.clear()
                    self._record_memo[key] = oid
                prov[(lane, slot)] = oid
            else:
                (step, parent, child, pc, sid, gmin, gmax, fentry,
                 dest) = ev
                ctx = ctxs[parent]
                if parent in dead_set:
                    # descendants of a trivially-false path die with it
                    ctxs[child] = ctx.clone()
                    dead_set.add(child)
                    dead.append(child)
                    continue
                if sid > 0:
                    cond = self.objects[sid]
                else:
                    idx = -sid - 1
                    cond = self.objects[prov[(idx // d_recs,
                                              idx % d_recs)]]
                if self.adapters:
                    self._jumpi_site_work(ctx, parent, cond, step, pc,
                                          fentry, gmin, gmax, dest)
                ctxs[child] = cctx = ctx.clone()
                if isinstance(cond, Bool):
                    chosen_p = simplify(cond)
                    chosen_c = simplify(Not(cond))
                else:
                    chosen_p = cond != 0
                    chosen_c = cond == 0
                if chosen_p.is_false:
                    dead_set.add(parent)
                    dead.append(parent)
                else:
                    ctx.conds.append((step, chosen_p))
                if chosen_c.is_false:
                    dead_set.add(child)
                    dead.append(child)
                else:
                    cctx.conds.append((step, chosen_c))
        self.stats["records"] += len(recs)
        self.stats["forks"] += len(forks)
        self.stats["dead"] += len(dead)
        return prov, dead

    # -- materialization -----------------------------------------------------

    def _obj(self, sid: int, prov: Optional[dict] = None):
        """Object for a retired-row sid: positive sids index the table;
        negative sids are this window's provisional records, resolved
        through the drain's (lane, slot) map (the device-side remap only
        lands at the NEXT window's dispatch — retired rows are pulled
        before that). `prov` is an explicit snapshot of that map for
        ring-deferred materialization: the next drain REPLACES
        self._prov, and a chunk materializing after that boundary (a
        worker-pool build, or a deep ring) must resolve against the
        map of the window it retired in."""
        if sid > 0:
            return self.objects[sid]
        d_recs = self.lane_kwargs.get("dlog_records", 64)
        idx = -sid - 1
        table = self._prov if prov is None else prov
        return self.objects[table[(idx // d_recs, idx % d_recs)]]

    def _try_resume(self, rows: dict, i: int, byte_pc: int, sp: int
                    ) -> Optional[tuple]:
        """Replay sha3_ semantics (laser/instructions.py:395-448) for a
        held lane from its slim row; returns the device patch
        (pc, sp, msize, min_gas, max_gas, sid, limbs) or None to
        decline (symbolic length, out-of-gas, oversized hash — the
        escalation path then hands the lane to the interpreter, which
        owns the constraint-adding and exception semantics)."""
        from ..support.eth_constants import (
            GAS_MEMORY, GAS_MEMORY_QUADRATIC_DENOMINATOR, ceil32,
        )
        from .function_managers import keccak_function_manager
        from .instruction_data import calculate_sha3_gas
        from .transaction import tx_id_manager

        if int(rows["sid_sub"][i]):
            return None  # symbolic length: interpreter concretizes
        length = _limbs_int(rows["sub"][i])
        if length > 4096:
            return None  # oversized: not worth modeling off-row
        min_gas = int(rows["min_gas"][i])
        max_gas = int(rows["max_gas"][i])
        sha3_min, sha3_max = calculate_sha3_gas(length)
        min_gas += sha3_min
        max_gas += sha3_max

        msize = int(rows["msize"][i])
        new_msize = msize
        sid_top = int(rows["sid_top"][i])
        index = None
        if sid_top == 0:
            index = _limbs_int(rows["top"][i])
            if index + length > 1 << 20:
                return None
            if length > 0 and msize <= index + length:
                # mem_extend: word-aligned growth + quadratic fee
                # (state/machine_state.py:96-142)
                new_msize = ceil32(index + length)
                for size, sign in ((new_msize, 1), (msize, -1)):
                    words = size // 32
                    fee = words * GAS_MEMORY + words ** 2 \
                        // GAS_MEMORY_QUADRATIC_DENOMINATOR
                    min_gas += sign * fee
                    max_gas += sign * fee
                if new_msize > self.lane_kwargs.get(
                        "memory_bytes", 4096):
                    return None  # outgrows the device planes
        if min_gas >= int(rows["gas_limit"][i]):
            return None  # OOG: the interpreter owns the exception

        if length == 0:
            result = keccak_function_manager.get_empty_keccak_hash()
        elif index is None:
            # symbolic offset: hash a fresh per-site symbolic input
            # (instructions.py:421-432)
            result = keccak_function_manager.create_keccak(
                symbol_factory.BitVecSym(
                    f"sha3_input_{tx_id_manager.get_next_tx_id()}",
                    length * 8,
                ))
        else:
            mem = rows["memory"][i]
            kind = rows["mkind"][i]
            sym_cover: Dict[int, Tuple[object, int]] = {}
            for r in range(int(rows["mlog_count"][i])):
                off = int(rows["mlog_off"][i, r])
                for j in range(int(rows["mlog_len"][i, r])):
                    sym_cover[off + j] = (
                        self._obj(int(rows["mlog_sid"][i, r])), j)
            byte_list = []
            for j in range(index, index + length):
                k = int(kind[j]) if j < RESUME_MEM else 0
                if k == symstep.KIND_SYM_WORD:
                    obj, jj = sym_cover[j]
                    if isinstance(obj, Bool):
                        obj = If(obj, _bv_val(1), _bv_val(0))
                    byte_list.append(simplify(
                        Extract(255 - 8 * jj, 248 - 8 * jj, obj)))
                elif k == symstep.KIND_CONC_WORD:
                    byte_list.append(
                        symbol_factory.BitVecVal(int(mem[j]), 8))
                else:  # written int byte, or the default-zero region
                    byte_list.append(int(mem[j]) if j < RESUME_MEM
                                     else 0)
            if all(isinstance(b, int) for b in byte_list):
                data = symbol_factory.BitVecVal(
                    int.from_bytes(bytes(byte_list), "big"),
                    length * 8)
            else:
                from ..smt import Concat

                parts = [
                    b if isinstance(b, BitVec)
                    else symbol_factory.BitVecVal(b, 8)
                    for b in byte_list
                ]
                data = simplify(Concat(parts))
            result = keccak_function_manager.create_keccak(data)

        if result.value is not None and not result.annotations:
            sid, limbs = 0, bv256.int_to_limbs(result.value)
        else:
            sid, limbs = self.objects.add(result), None
        return (byte_pc + 1, sp - 1, new_msize, min_gas, max_gas,
                sid, limbs)

    def materialize(self, st_host: dict, lane: int,
                    ctx: LaneCtx,
                    prov: Optional[dict] = None) -> GlobalState:
        """Rebuild a host GlobalState for a parked lane. `st_host` is a
        device_get of the SymLaneState; `prov` is an optional snapshot
        of the provisional-sid map for ring-deferred builds (see
        _obj)."""
        # copy(), not deepcopy() — interpreter-fork sharing semantics;
        # per-lane Account/Storage instances keep mutations independent
        gs = copy(ctx.template)
        ms = gs.mstate

        for _, cond in ctx.conds:
            gs.world_state.constraints.append(cond)

        # device pcs are arena coordinates under a packed wave (the
        # ctx carries its member segment's base, 0 unpacked); fentry
        # values are member-local by construction (symstep records the
        # pushed destination, not the arena pc)
        byte_pc = int(st_host["pc"][lane]) - ctx.code_base
        ms.pc = int(ctx.addr2idx[min(max(byte_pc, 0),
                                     ctx.addr2idx.shape[0] - 1)])
        ms.depth += int(st_host["depth"][lane])
        # active function from the last function-entry jump the device
        # took (svm._new_node_state parity for host-executed jumps)
        fentry = int(st_host["fentry"][lane])
        fnames = ctx.func_names if ctx.func_names is not None \
            else self._func_names
        if fentry >= 0 and fentry in fnames:
            gs.environment.active_function_name = fnames[fentry]
        ms.min_gas_used = ctx.gas0_min + int(st_host["min_gas"][lane])
        ms.max_gas_used = ctx.gas0_max + int(st_host["max_gas"][lane])

        # top-level STOP park with slim_stop: the transaction-end path
        # (svm._fast_terminal, or the normal STOP path when it
        # declines) reads neither the stack nor memory bytes — skip
        # both rebuilds. Storage, constraints, gas, promotions, and
        # annotations below still rebuild in full.
        slim = (
            self.slim_stop
            and ms.pc < len(gs.environment.code.instruction_list)
            and gs.environment.code.instruction_list[ms.pc]["opcode"]
            == "STOP"
            and gs.transaction_stack
            and gs.transaction_stack[-1][1] is None
        )

        # stack: the device planes hold the COMPLETE current stack
        # (mid-path re-seeds arrive with the template's entries already
        # on device) — rebuild from scratch, never append to the
        # template's copy
        del ms.stack[:]
        sp = 0 if slim else int(st_host["sp"][lane])
        for s in range(sp):
            sid = int(st_host["ssid"][lane, s])
            if sid:
                ms.stack.append(self._obj(sid, prov))
            else:
                ms.stack.append(
                    _bv_val(_limbs_int(st_host["stack"][lane, s])))

        # memory: reproduce the byte-level representation the Memory
        # class would hold after the same writes — MSTORE8 bytes as
        # ints, concrete-word bytes as 8-bit const terms, symbolic-word
        # bytes as Extract slices (state/memory.py:61-88). Like the
        # stack, the device planes are the complete state: reset the
        # template's copy before rebuilding
        ms.memory._memory.clear()
        ms.memory._msize = 0
        msize = int(st_host["msize"][lane])
        if slim:
            ms.memory._msize = msize  # size for fidelity, no content
            msize = 0
        if msize:
            ms.memory.extend(msize)
            mem = st_host["memory"][lane]
            kind = st_host["mkind"][lane]
            sym_cover: Dict[int, Tuple[object, int]] = {}
            for r in range(int(st_host["mlog_count"][lane])):
                off = int(st_host["mlog_off"][lane, r])
                ln = int(st_host["mlog_len"][lane, r])
                obj = self._obj(int(st_host["mlog_sid"][lane, r]),
                                prov)
                for j in range(ln):
                    sym_cover[off + j] = (obj, j)
            for i in np.nonzero(kind)[0]:
                i = int(i)
                k = int(kind[i])
                if k == symstep.KIND_BYTE_INT:
                    ms.memory[i] = int(mem[i])
                elif k == symstep.KIND_CONC_WORD:
                    ms.memory[i] = BitVec(_bv8_raw(int(mem[i])))
                else:  # KIND_SYM_WORD
                    obj, j = sym_cover[i]
                    if isinstance(obj, Bool):
                        obj = If(obj, _bv_val(1), _bv_val(0))
                    ms.memory[i] = simplify(
                        Extract(255 - 8 * j, 248 - 8 * j, obj))

        # storage: replay reads/writes in keys_get/keys_set parity order
        # — the interpreter records *every* read, so a slot read before
        # its first write (s_read bit 1) replays a read ahead of the
        # store, and one read after a write (bit 2) replays one behind
        acct = gs.environment.active_account
        any_written = False
        scount = int(st_host["scount"][lane])
        entries = []
        for r in range(scount):
            sidk = int(st_host["skey_sid"][lane, r])
            key = alu.to_bitvec(self._obj(sidk, prov)) if sidk else \
                _bv_val(_limbs_int(st_host["skeys"][lane, r]))
            entries.append((
                key,
                int(st_host["s_written"][lane, r]),
                int(st_host["s_read"][lane, r]),
                int(st_host["sval_sid"][lane, r]),
                r,
                int(st_host["s_wstep"][lane, r]),
                sidk,
            ))

        def _sval(r, sid):
            if sid:
                return self._obj(sid, prov)
            return _bv_val(_limbs_int(st_host["svals"][lane, r]))

        if not any(e[6] for e in entries):
            # concrete keys only: slot order == the historical replay
            for key, written, sread, sid, r, _w, _k in entries:
                if sread & 1:
                    _ = acct.storage[key]
                if written:
                    any_written = True
                    acct.storage[key] = _sval(r, sid)
                if sread & 2:
                    _ = acct.storage[key]
        else:
            # symbolic keys may alias: the host Storage builds the
            # read-over-write term, so writes must replay in device
            # step order (s_wstep) for later writes to shadow earlier
            # maybe-equal ones
            for key, written, sread, sid, r, _w, _k in entries:
                if sread & 1:
                    _ = acct.storage[key]
            for key, written, sread, sid, r, _w, _k in sorted(
                    (e for e in entries if e[1]), key=lambda e: e[5]):
                any_written = True
                acct.storage[key] = _sval(r, sid)
            for key, written, sread, sid, r, _w, _k in entries:
                if sread & 2:
                    _ = acct.storage[key]
        if any_written:
            # device-executed SSTOREs must leave the same mark the
            # mutation-pruner's SSTORE hook would have left, or clean-
            # path pruning drops the mutated end state
            from .plugin.plugins.plugin_annotations import (
                MutationAnnotation,
            )
            if not list(gs.get_annotations(MutationAnnotation)):
                gs.annotate(MutationAnnotation())

        # adapter state transfer (sink promotions, last-jump tracking)
        if self.adapters:
            last_jump = int(st_host["last_jump"][lane]) \
                if "last_jump" in st_host else -1
            if last_jump >= 0:
                last_jump -= ctx.code_base  # arena -> member-local
            for ad in self.adapters:
                plist = ctx.promos.get(id(ad), ())
                ad.attach(gs, [a for (_, a) in plist], last_jump)

        # spill/refill marker: the state parked AT this instruction
        # because the device could not execute it — it must take at
        # least one host step before becoming re-seedable (the marker
        # does not survive GlobalState.__copy__, so the post-step
        # states are eligible again)
        gs._lane_parked_pc = ms.pc

        # guarded: ring workers (MTPU_MAT_WORKERS>1) materialize off
        # the engine thread, and `+= 1` is not GIL-atomic
        with self._stats_lock:
            self.stats["parked"] += 1
        return gs

    # -- per-explore memo hygiene --------------------------------------------

    def _reset_explore_memos(self) -> None:
        """Clear the id-/site-keyed memos at every explore. Persistent
        engines (corpus runs) otherwise grow them without bound, and
        their keys — object ids, (step, pc) tuples — alias across
        codes once the owning objects die. Within one explore the
        memo values/pins keep the id-keyed owners alive, so id reuse
        cannot corrupt a live entry."""
        self._cdl_cache.clear()
        self._record_memo.clear()
        self._fired_sites.clear()
        self._memo_pins.clear()
        self._static_clean.clear()

    # -- overlapped fork-feasibility screening -------------------------------

    def _screen_forks(self, queries, registry):
        """Batched feasibility discharge for still-running forked
        lanes' condition prefixes (smt/solver/batch.py): runs in the
        OVERLAPPED phase — the device is already executing the next
        window — so the solver work that used to serialize behind the
        drain now hides behind device execution. Returns the lanes
        whose prefix is provably UNSAT; they join the next dispatch's
        kill list. Sound: only proved-infeasible paths die (the same
        guarantee as the host's prune_feasible_states, and engaged
        under the same args.pruning_factor gate — the default-off host
        policy keeps lane/host path counts identical by default).
        Screening a lane's conds WITHOUT the keccak axioms is sound
        for killing: an UNSAT subset implies an UNSAT superset. The
        discharge also consults the RUN-WIDE verdict cache
        (smt/solver/verdicts.py): a prefix refuted in any earlier
        window or call site kills its descendants here without a
        solve, and prefixes this screen refutes kill the open-state
        screen's supersets later. With MTPU_PROPAGATE on the
        discharge additionally runs the bidirectional propagation
        prescreen FIRST (ops/propagate.py): product-domain kills
        before any solver work, and harvested facts hint the solves
        that survive (docs/propagation.md)."""
        from ..smt import Model
        from ..smt.solver import batch as solver_batch
        from ..support.model import model_cache

        term_sets = [[c.raw for c in conds] for _, conds in queries]

        def quick_sat(conj):
            return model_cache.check_quick_sat(conj)

        def on_sat_model(md):
            # feed the shared ModelCache: sibling lanes (and later
            # open-state screens) quick-sat against this model
            model_cache.put(Model([md]), 1)

        t0 = time.perf_counter()
        try:
            with trace.span("lane.fork_screen", n=len(queries)):
                verdicts = solver_batch.discharge(
                    term_sets, timeout_s=2.0, conflict_budget=16384,
                    quick_sat=quick_sat, on_sat_model=on_sat_model,
                    registry=registry)
        except Exception as e:  # a screen, never an error path
            log.debug("fork-feasibility screen failed: %s", e)
            return []
        self.stats["overlap_solve_ms"] += int(
            (time.perf_counter() - t0) * 1000)
        self.stats["fork_screened"] += len(queries)
        return [lane for (lane, _), v in zip(queries, verdicts)
                if v == solver_batch.UNSAT]

    def _submit_fork_screen(self, queries, registry):
        """Start the fork-feasibility screen for this window's touched
        lanes. With the solver pool parallel (smt/solver/pool.py) the
        batch goes through `discharge_async` right away at the drain —
        the pool's workers solve it while this thread packs and
        dispatches the next window and blocks in the device pull — and
        the returned token is collected one boundary later
        (_collect_fork_screen), booking the hidden wall as
        async_overlap_ms. With the pool serial the token defers the
        whole screen to collection time, which lands in the overlapped
        phase exactly where the synchronous screen ran before — the
        K=1 path is behavior-identical to PR 1-3."""
        from ..smt.solver import pool as pool_mod

        if not pool_mod.get_pool().parallel:
            return (queries, registry, None)
        from ..smt import Model
        from ..smt.solver import batch as solver_batch
        from ..support.model import model_cache

        term_sets = [[c.raw for c in conds] for _, conds in queries]

        def quick_sat(conj):
            return model_cache.check_quick_sat(conj)

        def on_sat_model(md):
            model_cache.put(Model([md]), 1)

        try:
            fut = solver_batch.discharge_async(
                term_sets, timeout_s=2.0, conflict_budget=16384,
                quick_sat=quick_sat, on_sat_model=on_sat_model,
                registry=registry)
        except Exception as e:  # a screen, never an error path
            log.debug("async fork screen submit failed: %s", e)
            return (queries, registry, None)
        return (queries, registry, fut)

    def _collect_fork_screen(self, token):
        """Verdicts for a screen started at the previous boundary;
        returns the proved-UNSAT lanes for the next dispatch's kill
        list (same protocol as the synchronous screen)."""
        queries, registry, fut = token
        if fut is None:
            return self._screen_forks(queries, registry)
        from ..smt.solver import batch as solver_batch

        try:
            verdicts = fut.result()
        except Exception as e:  # a screen, never an error path
            log.debug("async fork screen failed: %s", e)
            return []
        self.stats["overlap_solve_ms"] += int(fut.duration_ms)
        self.stats["fork_screened"] += len(queries)
        return [lane for (lane, _), v in zip(queries, verdicts)
                if v == solver_batch.UNSAT]

    # -- window-boundary lane merge / subsumption ----------------------------

    def _template_static_clean(self, ctx: LaneCtx) -> bool:
        """No pending PotentialIssues ride the lane's seed state (a
        statically-dead lane carrying one must still reach a terminator
        to discharge it). Memoized per template per explore."""
        key = id(ctx.template)
        cached = self._static_clean.get(key)
        if cached is None:
            try:
                from ..analysis.potential_issues import (
                    PotentialIssuesAnnotation,
                )

                cached = not any(
                    isinstance(a, PotentialIssuesAnnotation)
                    and a.potential_issues
                    for a in ctx.template.annotations)
            except Exception:
                cached = False
            self._static_clean[key] = cached
            self._memo_pins.append(ctx.template)
        return cached

    def _static_retire(self, status, ctxs, dead_set, kill,
                       counts_h, resumes) -> None:
        """Window-boundary static retire (docs/static_pass.md): a lane
        whose per-PC reachable-detector mask has no bit in common with
        the run's active-detector mask can never mint another issue; if
        additionally no open-state terminator is reachable — or no
        later round consumes open states and nothing is pending on the
        lane — it retires on the next dispatch's kill list with ZERO
        solver or materialization work (`statically_retired`). Runs
        BEFORE the merge pass so retired lanes never cost a fingerprint
        dispatch. Gated by MTPU_STATIC via the info lookup and by svm
        actually setting an active mask."""
        info = self._static_info
        active = self.static_active_mask
        if info is None or active is None:
            return
        from ..analysis.static_pass import TERMINATOR_BIT

        # taint-refined plane for the active-module set (PR 8): anchor
        # sites whose trigger operands are provably
        # attacker-independent stop holding lanes alive; None falls
        # back to the raw reach mask (MTPU_TAINT=0, unconverged taint
        # fixpoint, or a module with unknown trigger semantics)
        plane = None
        if self.static_module_names is not None:
            try:
                from ..analysis import static_pass

                plane = static_pass.refined_plane(
                    info, self.static_module_names)
            except Exception:
                plane = None

        active = int(active)
        final_tx = bool(self.static_final_tx)
        excluded = dead_set | set(kill) | {r[0] for r in resumes}
        pcs = counts_h["pc"]
        retired = 0
        for lane in range(self.n_lanes):
            ctx = ctxs[lane]
            if (ctx is None or lane in excluded
                    or status[lane] != Status.RUNNING):
                continue
            if ctx.promos:
                continue  # pending drain promotions: must materialize
            mask = info.mask_at(int(pcs[lane]), plane)
            if mask & active:
                continue
            if mask & int(TERMINATOR_BIT):
                if not final_tx or not self._template_static_clean(ctx):
                    continue
            kill.append(lane)
            retired += 1
        if retired:
            self.stats["static_retired"] += retired
            from ..smt.solver.solver_statistics import SolverStatistics

            SolverStatistics().bump(static_retired_lanes=retired)
            trace.event("static.retire", retired=retired)
            log.info("static pass retired %d lanes at the window "
                     "boundary", retired)

    def _patch_jump_parks(self, results: List[GlobalState]
                          ) -> List[GlobalState]:
        """Consult the static jump table before a symbolic-dest JUMP
        park falls back to the host interpreter (which ends the path —
        instructions.jump_ raises on a symbolic dest). A site whose
        value-set resolved to EXACTLY one target continues there, with
        the dest == target equality appended as a path condition
        (implied true by the resolution's soundness, so the issue set
        cannot grow; and were the resolution ever wrong, the constraint
        makes the wrong continuation infeasible rather than unsound).
        Disabled while an arbitrary-jump-class detector is active."""
        info = self._static_info
        if info is None or not self.static_jump_patch_ok \
                or not info.jump_table:
            return results
        patched = 0
        for gs in results:
            try:
                ilist = gs.environment.code.instruction_list
                pc = gs.mstate.pc
                if pc >= len(ilist) or ilist[pc]["opcode"] != "JUMP":
                    continue
                stack = gs.mstate.stack
                if not stack:
                    continue
                dest = stack[-1]
                if getattr(dest, "symbolic", False) is not True:
                    continue
                targets = info.jump_table.get(ilist[pc]["address"])
                if not targets or len(targets) != 1:
                    continue
                target = symbol_factory.BitVecVal(targets[0], 256)
                gs.world_state.constraints.append(dest == target)
                stack[-1] = target
                patched += 1
            except Exception:
                continue
        if patched:
            self.stats["static_jump_patches"] += patched
            log.info("static jump table resolved %d symbolic JUMP "
                     "parks in place", patched)
        return results

    def _window_merge(self, st, status, ctxs, dead_set, kill,
                      counts_h, resumes) -> None:
        """Collapse exact-frontier twin lanes at the window boundary
        (docs/lane_merge.md). Runs AFTER the drain (canonical sids and
        this window's conds are final) and BEFORE the next dispatch's
        kill list closes, so a retired lane never executes another
        step. Cheap host pre-grouping (pc/sp/counters/template/write
        mirror) decides whether the device fingerprint dispatch is
        worth issuing at all; groups that survive the full fingerprint
        hand their condition lists to merge.plan_group — duplicates and
        implied siblings retire subsumed, the incomparable rest merges
        into one lane under an OR'd suffix with disjunct provenance.
        Gated by MTPU_MERGE (default on). Mesh-safe: the fingerprint
        kernel is row-parallel over the sharded lane axis (elementwise
        folds + per-lane reductions; the prov table and pair inputs
        stay replicated), so unlike the full-plane seed scatters (see
        pick_mesh) it partitions cleanly — and any kernel failure is
        caught below and skips the pass, never the window."""
        from . import merge as merge_mod

        if not merge_mod.enabled():
            return
        excluded = dead_set | set(kill) | {r[0] for r in resumes}
        pcs, sps = counts_h["pc"], counts_h["sp"]
        pre: Dict[tuple, List[int]] = {}
        for lane in range(self.n_lanes):
            ctx = ctxs[lane]
            if (ctx is None or lane in excluded
                    or status[lane] != Status.RUNNING):
                continue
            if ctx.promos:
                continue  # adapter sink promotions are per-path
            key = (
                id(ctx.template), int(pcs[lane]), int(sps[lane]),
                int(counts_h["msize"][lane]),
                int(counts_h["scount"][lane]),
                int(counts_h["mlog_count"][lane]),
                tuple((k.raw.tid, v.raw.tid) for k, v in ctx.swrites),
            )
            pre.setdefault(key, []).append(lane)
        if not any(len(v) > 1 for v in pre.values()):
            return
        fp = self._boundary_fp(st, groups=len(pre))
        if fp is None:
            return
        merged, subsumed, widened, dropped = \
            self._collapse_twins(pre, fp, ctxs)
        kill.extend(dropped)
        if merged or subsumed:
            self.stats["lanes_merged"] += merged
            self.stats["lanes_subsumed"] += subsumed
            self.stats["merge_rounds"] += 1
            self.stats["gas_widened"] = (
                self.stats.get("gas_widened", 0) + widened)
            from ..smt.solver.solver_statistics import SolverStatistics

            SolverStatistics().bump(
                lanes_merged=merged, lanes_subsumed=subsumed,
                merge_rounds=1, gas_widened_lanes=widened)
            merge_mod.note_retired(merged + subsumed)
            trace.event("merge.window", merged=merged,
                        subsumed=subsumed)
            log.info("lane merge: %d merged, %d subsumed at window "
                     "boundary", merged, subsumed)

    def _boundary_fp(self, st, groups: int = 0):
        """Per-lane frontier fingerprint for THIS window boundary
        (_merge_fingerprint over the full plane), computed at most once
        and shared by the live-lane window merge AND the
        merge-before-spill pass — the two passes cost ONE dispatch
        between them. None on kernel failure (both passes then skip —
        a screen, never an error path). The cache resets at every
        window (explore loop)."""
        if self._fp_boundary is None:
            d_recs = self.lane_kwargs.get("dlog_records", 64)
            n = self.n_lanes
            pv = min(PROV_BUCKET, n * d_recs) \
                if len(self._prov) <= PROV_BUCKET else n * d_recs
            prov_pairs = np.full((pv, 2), n * d_recs, np.int32)
            for j, ((lane, slot), oid) in enumerate(self._prov.items()):
                prov_pairs[j, 0] = lane * d_recs + slot
                prov_pairs[j, 1] = oid
            try:
                with trace.span("merge.fingerprint", groups=groups):
                    self._fp_boundary = np.asarray(
                        self._pull(_merge_fingerprint(
                            st, self._to_device(prov_pairs))))
            except Exception as e:  # a screen, never an error path
                log.debug("merge fingerprint failed: %s", e)
                self._fp_boundary = False
        return None if self._fp_boundary is False else self._fp_boundary

    def _collapse_twins(self, pre, fp, ctxs):
        """Shared twin-collapse body of the window merge and the
        merge-before-spill pass: within each host pre-group, lanes
        whose device fingerprints match hand their condition lists to
        merge.plan_group; the survivor's ctx takes the OR'd suffix
        (and, under MTPU_MERGE_GASWIDEN, gas offsets widened to the
        group hull — gas-widening merge, docs/lane_merge.md: with
        widening OFF the gas interval joins the exact twin key, the
        historical behavior). Returns (merged, subsumed, widened,
        dropped lane list)."""
        from . import merge as merge_mod

        gas_widen = merge_mod.gas_widen_enabled()
        merged = subsumed = widened = 0
        dropped_lanes: List[int] = []
        from .retire_ring import owner_of as _owner_of

        for _key, lanes in pre.items():
            if len(lanes) < 2:
                continue
            # cross-tenant lanes must never OR-merge (docs/daemon.md
            # §wave packing): the pre-group keys on id(template) and
            # arena pc, both per-member by construction, so a mixed
            # group is a routing bug — assert rather than merge wrong
            assert len({_owner_of(ctxs[lane])
                        for lane in lanes}) == 1, \
                "cross-tenant lanes reached one merge group"
            twins: Dict[tuple, List[int]] = {}
            for lane in lanes:
                tkey = (int(fp[lane, 0]), int(fp[lane, 1]))
                if not gas_widen:
                    tkey += (int(fp[lane, 2]), int(fp[lane, 3]))
                twins.setdefault(tkey, []).append(lane)
            for group in twins.values():
                if len(group) < 2:
                    continue
                cond_lists = [[c for (_s, c) in ctxs[g].conds]
                              for g in group]
                try:
                    plan = merge_mod.plan_group(cond_lists)
                except Exception:
                    log.debug("merge planning failed", exc_info=True)
                    continue
                if plan is None:
                    continue
                survivor = group[plan.keep]
                if plan.new_conds is not None:
                    sc = ctxs[survivor].conds
                    stamp = max((cl[-1][0] for cl in
                                 (ctxs[g].conds for g in group) if cl),
                                default=0)
                    ctxs[survivor].conds = (
                        sc[:plan.prefix_len]
                        + [(stamp, c)
                           for c in plan.new_conds[plan.prefix_len:]])
                if gas_widen:
                    # the survivor now represents every dropped arm:
                    # widen its host gas offsets so the effective
                    # interval (materialize/_DrainSite add gas0_* to
                    # the device values) covers the group's hull
                    members = [survivor] + [group[mi]
                                            for mi in plan.dropped]
                    dmin = min(int(fp[m, 2]) for m in members) \
                        - int(fp[survivor, 2])
                    dmax = max(int(fp[m, 3]) for m in members) \
                        - int(fp[survivor, 3])
                    if dmin or dmax:
                        ctxs[survivor].gas0_min += dmin
                        ctxs[survivor].gas0_max += dmax
                        widened += len(plan.dropped)
                for mi, reason in plan.dropped.items():
                    dropped_lanes.append(group[mi])
                    if reason == "merged":
                        merged += 1
                    else:
                        subsumed += 1
        return merged, subsumed, widened, dropped_lanes

    def _spill_merge(self, st, lanes, ctxs, dead_set, counts_h) -> set:
        """Merge-before-spill (docs/drain_pipeline.md): the window's
        retired SPILL CANDIDATES — parked lanes about to materialize
        into the host worklist — run the same fingerprint twin-collapse
        the live-lane merge runs, BEFORE any GlobalState is built. A
        rejoin twin that would have merged at the next dispatch instead
        re-executed host-side in the spill/refill regime (one
        interpreter step + re-seed + full device re-execution per twin,
        every spill generation); collapsing it here is why the overflow
        regime stops paying rejoin storms twice. The dropped lanes are
        already DEAD on device (the retire gather marked them); they
        are simply never materialized, and the survivor materializes
        with the OR'd constraint suffix (witness re-concretization
        preserved — the same soundness argument as docs/lane_merge.md).
        Returns the dropped-lane set. Gated by MTPU_MERGE +
        MTPU_STREAM (merge.spill_merge_enabled)."""
        from . import merge as merge_mod

        if not merge_mod.spill_merge_enabled():
            return set()
        pcs, sps = counts_h["pc"], counts_h["sp"]
        pre: Dict[tuple, List[int]] = {}
        for lane in lanes:
            ctx = ctxs[lane]
            if ctx is None or lane in dead_set or ctx.promos:
                continue
            key = (
                id(ctx.template), int(pcs[lane]), int(sps[lane]),
                int(counts_h["msize"][lane]),
                int(counts_h["scount"][lane]),
                int(counts_h["mlog_count"][lane]),
                tuple((k.raw.tid, v.raw.tid) for k, v in ctx.swrites),
            )
            pre.setdefault(key, []).append(lane)
        if not any(len(v) > 1 for v in pre.values()):
            return set()
        fp = self._boundary_fp(st, groups=len(pre))
        if fp is None:
            return set()
        merged, subsumed, widened, dropped = \
            self._collapse_twins(pre, fp, ctxs)
        if not dropped:
            return set()
        n = merged + subsumed
        self.stats["spill_merged"] += n
        self.stats["gas_widened"] = (
            self.stats.get("gas_widened", 0) + widened)
        from ..smt.solver.solver_statistics import SolverStatistics

        SolverStatistics().bump(spill_merged_lanes=n,
                                gas_widened_lanes=widened)
        merge_mod.note_retired(n)
        trace.event("retire.spill_merge", merged=merged,
                    subsumed=subsumed)
        log.info("merge-before-spill: %d of %d spill candidates "
                 "collapsed at the window boundary", n, len(lanes))
        return set(dropped)

    # -- chunked escalation retire (docs/drain_pipeline.md) ------------------

    def _retire_chunked(self, st, lanes_sel, retire_floors):
        """The ONE sanctioned escalation-retire gather seam
        (tools/lint_static.py rule "unbounded-retire-gather"): retiring
        k lanes issues ceil(k/chunk) gathers of at most
        MTPU_RETIRE_CHUNK rows each into bounded device buffers — live
        width is no longer a single-allocation limit (the 64k-LIVE
        kernel-fault shape, BENCH_r08). Chunk buckets are pow2 capped
        at the chunk bound, so compile keys repeat across windows and
        widths. Each chunk's D2H copy starts async at dispatch; a
        deferred pull (the retire ring) overlaps the next window's
        device execution. With chunking off (MTPU_RETIRE_CHUNK=0 or
        MTPU_STREAM=0) this is bit-for-bit the old monolithic gather.
        Returns (st, [(lanes, device rows, floors, dispatch time)])."""
        ch = retire_chunk()
        if ch <= 0 or len(lanes_sel) <= ch:
            parts = [list(lanes_sel)]
        else:
            parts = [list(lanes_sel[i:i + ch])
                     for i in range(0, len(lanes_sel), ch)]
        cap = min(ch, self.n_lanes) if ch > 0 else self.n_lanes
        chunks = []
        with trace.span("lane.retire_dispatch", lanes=len(lanes_sel),
                        chunks=len(parts)):
            for part in parts:
                floors = retire_floors(part)
                kp = _geo_bucket(len(part), cap, min(64, cap))
                idx = np.full(kp, self.n_lanes, np.int32)
                idx[: len(part)] = part
                st, rows = _retire_rows(st, self._to_device(idx),
                                        *floors)
                for arr in rows:
                    try:
                        arr.copy_to_host_async()
                    except Exception:
                        break  # backend without async copies
                chunks.append((part, rows, floors, time.perf_counter()))
        if ch > 0:
            self.stats["retire_chunks"] += len(parts)
            from ..smt.solver.solver_statistics import SolverStatistics

            SolverStatistics().bump(retire_chunks=len(parts))
            if len(parts) > 1:
                trace.event("retire.chunked", lanes=len(lanes_sel),
                            chunks=len(parts))
        return st, chunks

    def live_seed_states(self) -> List[GlobalState]:
        """Host-only snapshot of every live lane as (seed template +
        accumulated path conditions) — the lane's state at the window
        boundary where it was seeded, restricted to its recorded
        branch. Safe from a signal handler (no device access), so the
        SIGTERM/fatal live dump can capture lanes mid-window
        (support/checkpoint.snapshot_live_states); the device progress
        since the seed re-executes on resume, and issue dedup absorbs
        any re-detection. Empty when no explore is running.

        Retired-but-unmaterialized lanes parked in the retire ring
        (chunks whose pull is still deferred behind the next window)
        are covered too: their ctxs ride the pending jobs'
        introspection hook, so a SIGTERM mid-boundary loses no
        in-flight subtree to the deferral."""
        ctxs = self._explore_ctxs
        if not ctxs:
            return []
        ctxs = list(ctxs)
        ring = self._ring
        if ring is not None:
            try:
                ctxs.extend(ring.pending_ctx_sources())
            except Exception:
                pass  # best-effort, signal-safe
        out = []
        for ctx in list(ctxs):
            if ctx is None:
                continue
            try:
                gs = copy(ctx.template)
                for _step, cond in list(ctx.conds):
                    gs.world_state.constraints.append(cond)
                out.append(gs)
            except Exception:
                continue  # best-effort: the lane re-runs from the
                #           round checkpoint instead
        return out

    def _window_export(self, st, status, ctxs, dead_set, kill,
                       resumes, steps, free, results,
                       retire_floors):
        """Mid-flight wave export at the window boundary
        (docs/checkpoint.md): when the export client asks for n lanes,
        the TAIL of the live set retires through the escalation gather
        and materializes into ordinary mid-path GlobalStates — the
        complete per-lane plane (pc, depth, call frame, stack, memory,
        storage slots, gas interval, constraints, pending promotions)
        — which `deliver` ships as an in-flight migration batch. The
        exported lanes are DEAD on device the moment the gather runs
        (same protocol as the escalation retire), so a shipped lane
        never executes another step: kill-then-import. A declined
        delivery parks the states locally instead — work can move,
        but never be lost. Runs AFTER the merge pass so a lane about
        to collapse is never shipped."""
        client = self.export_client
        excluded = dead_set | set(kill) | {r[0] for r in resumes}
        live = [lane for lane in range(self.n_lanes)
                if (ctxs[lane] is not None and lane not in excluded
                    and status[lane] == Status.RUNNING)]
        if len(live) < 2:
            return st
        try:
            want = int(client.want(len(live)))
        except Exception:
            want = 0
        want = min(want, len(live) - 1)
        if want < 1:
            return st
        sel = live[len(live) - want:]
        try:
            # the export retires through the SAME chunked gather seam
            # as the escalation retire (docs/drain_pipeline.md): a
            # migration client asking for half a 64k wave must not
            # recreate the single-allocation shape chunking removed
            with trace.span("ckpt.export", lanes=len(sel)):
                st, chunks = self._retire_chunked(st, sel,
                                                  retire_floors)
                exported = []
                for part, rows, floors, _t in chunks:
                    rows_host = _unpack_rows(self._pull(rows),
                                             *floors)
                    exported.extend(
                        self.materialize(rows_host, row, ctxs[lane])
                        for row, lane in enumerate(part))
        except Exception as e:  # a seam, never an error path
            log.warning("mid-flight lane export failed (%s); lanes "
                        "stay local", e)
            return st
        # the gather marked the rows DEAD on device: recycle the slots
        # now, exactly like the escalation retire
        for lane in sel:
            self.stats["device_steps"] += int(steps[lane])
            ctxs[lane] = None
            free.append(lane)
        status[np.asarray(sel, np.int32)] = DEAD
        delivered = False
        try:
            delivered = bool(client.deliver(exported))
        except Exception as e:
            log.debug("export delivery failed: %s", e)
        if delivered:
            self.stats["exported"] = (
                self.stats.get("exported", 0) + len(sel))
            log.info("mid-flight export: %d live lanes shipped at the "
                     "window boundary", len(sel))
        else:
            # undeliverable (no thief claimed / save failed): the
            # states are ordinary parked mid-path states — they
            # continue locally through the spill/refill path
            results.extend(exported)
        return st

    # -- top-level loop ------------------------------------------------------

    def explore(self, code_bytes: bytes,
                entry_states: List[GlobalState]) -> List[GlobalState]:
        """Run entry states on device until every path parks or dies;
        returns the materialized parked states (each positioned at the
        first instruction the device could not execute)."""
        return self._explore_members(
            ((code_bytes, entry_states, None),))[None]

    def explore_packed(self, members) -> Dict[object, list]:
        """Cross-tenant packed explore (docs/daemon.md §wave packing):
        ``members`` is [(code_bytes, entry_states, owner)] with
        distinct owner tags; every member's lanes ride the SAME window
        dispatches over one segment-arena CompiledCode, and retires
        route back per tenant (retire_ring.TenantRouter) in submit
        order. Returns {owner: parked states}. Member execution is
        independent by construction — per-seed group ids key the
        device record dedup, arena pcs are disjoint across segments,
        and the merge pre-groups key on per-member templates — so
        per-tenant results are identical to running each member's
        explore alone (gated by tests/test_wave_pack.py)."""
        owners = [owner for _c, _s, owner in members]
        assert len(set(owners)) == len(owners), \
            "packed members need distinct owner tags"
        assert self.mesh is None, "packed waves do not shard (yet)"
        return self._explore_members(tuple(members))

    def _explore_members(self, members) -> Dict[object, list]:
        packed = len(members) > 1
        code_bytes = members[0][0]
        mems: List[Optional[_PackMember]] = []
        stats0 = dict(self.stats)  # engines persist across explores
        self._reset_explore_memos()
        if not packed:
            entry_states = members[0][1]
            self._func_names = dict(
                getattr(entry_states[0].environment.code,
                        "address_to_function_name", {}) or {}
            ) if entry_states else {}
            # static pre-analysis (docs/static_pass.md): memoized per
            # code hash; feeds the window-boundary retire, the
            # jump-table consult on symbolic JUMP parks, and the
            # det-mask plane the compile below ships with the code
            # tensors
            try:
                from ..analysis import static_pass

                self._static_info = static_pass.info_for(code_bytes)
            except Exception as e:  # a screen, never an error path
                log.debug("static pass unavailable: %s", e)
                self._static_info = None
            cc = _compiled_code(code_bytes, self._func_names.keys())
            mems.append(None)
        else:
            # packed wave: per-member function maps ride the lane
            # ctxs. The verified loop-summary park planes pack per
            # member (lanes park at summarizable heads and the OWNING
            # svm applies the closed form after the sweep, exactly the
            # solo path — without this, packed waves UNROLL the loops
            # PR 12 closed, measured a 75 s regression on a
            # metacoin+underflow pack). The remaining per-code host
            # consumers (static retire, jump patching) stand down —
            # their gates' own on/off identity covers the parity.
            self._func_names = {}
            self._static_info = None
            member_keys = []
            for code, states, owner in members:
                fnames = dict(
                    getattr(states[0].environment.code,
                            "address_to_function_name", {}) or {}
                ) if states else {}
                heads = ()
                try:
                    from ..analysis import static_pass
                    from ..analysis.static_pass import loop_summary

                    if static_pass.enabled() \
                            and loop_summary.enabled():
                        info = static_pass.info_for(code)
                        if info is not None:
                            heads = tuple(sorted(
                                loop_summary.summarizable_heads(
                                    info)))
                except Exception as e:
                    log.debug("packed loop-summary heads "
                              "unavailable: %s", e)
                member_keys.append(
                    (code, tuple(sorted(fnames.keys())), heads))
                mems.append(_PackMember(owner, code, 0, fnames))
            cc, bases = _compiled_packed(tuple(member_keys))
            for m, base in zip(mems, bases):
                m.base = base
            from ..smt.solver.solver_statistics import (
                SolverStatistics as _SSP,
            )

            _SSP().bump(waves_packed=1, pack_members=len(members))
        cc = self._placed_code(code_bytes, cc)
        # per-byte-address coverage bitmap, device-resident across
        # windows AND explores of the same code (the interpreter's
        # execute_state coverage hook cannot see device steps; this is
        # its device twin — svm merges it into the coverage plugin)
        visited = self._visited_dev.pop(code_bytes, None) \
            if not packed else None
        if visited is None:
            visited = self._new_visited(cc)
        #: arena length drives the window-variant compile keys (the
        #: pow2 code buckets make packed and plain variants share)
        code_len = len(code_bytes) if not packed \
            else int(cc.packed.shape[0]) - 1
        st = self._acquire_state()
        ctxs: List[Optional[LaneCtx]] = [None] * self.n_lanes
        # expose the live ctx table for the SIGTERM live dump
        # (live_seed_states); cleared in the finally below
        self._explore_ctxs = ctxs
        queue = deque((midx, gs) for midx, (_c, states, _o)
                      in enumerate(members) for gs in states)
        n_entries = len(queue)
        free = list(range(self.n_lanes - 1, -1, -1))
        results: List[GlobalState] = []
        from .retire_ring import TenantRouter, owner_of

        if packed:
            router = TenantRouter([m.owner for m in mems])
            sink = router
            deliver = router.deliver
        else:
            router = None
            sink = results
            deliver = lambda _owner, gs: results.append(gs)  # noqa: E731
        calldata_cap = int(st.calldata.shape[1])
        n = self.n_lanes

        kill: List[int] = []
        resumes: List[tuple] = []
        small = min(16, self.n_lanes)
        if self.mesh is not None and small >= self.n_lanes:
            # under a mesh the seed bucket stays strictly BELOW the
            # plane width: a k == n seed scatter trips the SPMD
            # partitioner (operand sharded, indices not — see
            # pick_mesh); half-plane seeding costs one extra window
            # only on narrow meshed engines
            small = max(self.n_lanes // 2, 1)
        peak_demand = len(queue)
        # streaming retire/materialize pipeline
        # (docs/drain_pipeline.md "streaming retire"): window k's
        # retired lanes leave the device as bounded CHUNKS
        # (_retire_chunked) whose D2H pulls and GlobalState rebuilds
        # run AFTER window k+1 is dispatched — the host's biggest
        # per-window costs (transfer + materialize) overlap device
        # execution. The deferral structure is a bounded ring
        # (laser/retire_ring.py) feeding a K-worker materialization
        # pool (K=1 default: inline at flush, bit-identical to the old
        # pending_mat list) with delivery order into `results` pinned
        # to submit order. Each job snapshots this window's
        # provisional-sid map — the next drain REPLACES self._prov.
        from .retire_ring import RetireRing

        ring = RetireRing(workers=mat_workers(), sink=sink)
        self._ring = ring
        from ..smt.solver.solver_statistics import SolverStatistics \
            as _SS

        def _submit_mat(rows_ref, floors, items, t_disp) -> None:
            """Queue one retired chunk: rows_ref is a host dict when
            already pulled (floors None) or the device arrays of a
            deferred gather; items = [(row index, ctx snapshot)]."""
            prov = self._prov

            def pull():
                if floors is None:
                    return rows_ref
                t0 = time.perf_counter()
                hidden_ms = (t0 - t_disp) * 1000.0
                with self._stats_lock:
                    # wall the D2H copy had to progress behind the
                    # next window's execution before anyone blocked
                    # on it — the measured hide of the deferred pull
                    self.stats["retire_overlap_ms"] += hidden_ms
                _SS().bump(retire_overlap_ms=hidden_ms)
                with trace.span("retire.pull", rows=len(items)):
                    return _unpack_rows(self._pull(rows_ref),
                                        *floors)

            def build(rows_host):
                t0 = time.perf_counter()
                with trace.span("retire.materialize", n=len(items)):
                    if packed:
                        # retire chunks carry the owner tag: the ring
                        # sink (TenantRouter) routes each state into
                        # its request's worklist in submit order
                        out = [
                            (owner_of(ctx),
                             self.materialize(rows_host, row, ctx,
                                              prov=prov))
                            for row, ctx in items]
                    else:
                        out = [self.materialize(rows_host, row, ctx,
                                                prov=prov)
                               for row, ctx in items]
                with self._stats_lock:
                    self.stats["overlap_mat"] += len(items)
                    self.stats["overlap_mat_ms"] += int(
                        (time.perf_counter() - t0) * 1000)
                return out

            build.ring_items = items  # SIGTERM live-dump introspection
            # already-pulled chunks hand the ring their host rows so
            # it can park them codec-encoded (state_codec.encode_rows)
            # instead of holding raw planes until flush
            ring.submit(pull, build,
                        payload=rows_ref if floors is None else None)

        # overlapped fork-feasibility screening (batched discharge,
        # gated like the host's fork pruning): queries collected at
        # drain k discharge while window k+1 executes; UNSAT lanes
        # ride the kill list of dispatch k+2
        from ..smt.solver.solver_statistics import SolverStatistics
        from ..support.support_args import args as _args

        _solver_stats = SolverStatistics()
        screen_on = bool(getattr(_args, "pruning_factor", None))
        screen_registry = None
        if screen_on:
            from ..smt.solver.batch import SubsetRegistry

            screen_registry = SubsetRegistry()
        pending_screen: List[tuple] = []
        screen_future = None
        screen_dead: List[int] = []
        if self.mesh is not None:
            _solver_stats.bump(mesh_sweeps=1)
            _solver_stats.mesh_devices = int(self.mesh.devices.size)
        trace.begin("lane.explore", n_lanes=self.n_lanes,
                    entries=n_entries, code_len=code_len,
                    pack_members=len(members) if packed else 0)
        try:
            while True:
                # per-boundary fingerprint cache: the window merge and
                # the merge-before-spill pass share ONE dispatch
                self._fp_boundary = None
                # a seed backlog beyond the small bucket drains in ONE
                # window through the full-width midpath variant — but only
                # once that variant is compiled (warm_variant kicks a
                # background compile and the small bucket carries on)
                seed_cap = small
                full_bucket = self._full_bucket()
                if (len(queue) > small or len(resumes) > small) \
                        and full_bucket > small and warm_variant(
                    self.n_lanes, code_len, self.lane_kwargs,
                    self.window, self.step_budget,
                    seed_bucket=full_bucket, mesh=self.mesh,
                ):
                    seed_cap = full_bucket
                with trace.span("lane.seed_pack"):
                    entries = []
                    while queue and free and len(entries) < seed_cap:
                        midx, gs = queue.popleft()
                        if self.adapters and not all(
                            ad.seed_ok(gs) for ad in self.adapters
                        ):
                            # host handles this entry
                            deliver(mems[midx].owner if packed
                                    else None, gs)
                            continue
                        entries.append((free.pop(), gs, mems[midx]))
                    i32buf, u8buf, k, pv = self._pack_window(
                        entries, ctxs, free, kill, calldata_cap,
                        big=seed_cap > small, resumes=resumes)
                resumes = []
                n_free_written = len(free)
                with trace.span("lane.window_dispatch",
                                seeds=k, window=self.window):
                    st, visited, out = _window_exec(
                        st, cc, i32buf, u8buf, self.exec_table,
                        self.taint_table, self.window, k,
                        self.step_budget, pv, visited,
                        self._resume_flag)
                # start the fused outputs' D2H copies now: the transfer
                # overlaps the host work below instead of serializing
                # into the blocking pull
                for arr in out:
                    try:
                        arr.copy_to_host_async()
                    except Exception:
                        break  # backend without async copies
                # the kill landed at the dispatch's reset phase: only now
                # may the slots be recycled (they enter the free stack the
                # device sees at the NEXT dispatch)
                for lane in kill:
                    ctxs[lane] = None
                    free.append(lane)
                kill = []
                # the dispatch above is asynchronous: while this window
                # executes, pull+rebuild the LAST window's retired
                # GlobalStates and discharge its fork-feasibility batch
                ring.flush()
                if screen_future is not None:
                    # started at the previous drain: with the pool
                    # parallel the verdicts are usually already done
                    # (they solved behind the pull + this dispatch);
                    # serial tokens run the whole screen here, exactly
                    # where the synchronous screen used to
                    screen_dead = self._collect_fork_screen(
                        screen_future)
                    screen_future = None
                self.stats["windows"] += 1
                # device-dispatch accounting (docs/daemon.md §wave
                # packing): window count feeds the bench "strictly
                # fewer dispatches" gate; occupancy is the live-lane
                # share of the wave — packed waves carry several
                # tenants' lanes through the same dispatches
                _solver_stats.bump(lane_windows=1)
                live_now = n - len(free)
                if live_now > 0:
                    _solver_stats.bump_max(pack_occupancy_pct=round(
                        100.0 * live_now / n, 1))
                if packed:
                    from .retire_ring import owner_of as _oof

                    owners_live = {_oof(c) for c in ctxs
                                   if c is not None}
                    if len(owners_live) > 1:
                        _solver_stats.bump(
                            dispatches_saved=len(owners_live) - 1)
                with trace.span("lane.window_pull"):
                    (misc, scal, utab, ftab, ridx, r_i32, r_u32,
                     r_u8, hidx, h_i32, h_u32, h_u8) = [
                        np.asarray(x) for x in self._pull(out)]
                counts_h = {
                    "dlog_count": misc[:, 0], "status": misc[:, 1],
                    "steps": misc[:, 2], "sp": misc[:, 3],
                    "scount": misc[:, 4], "mlog_count": misc[:, 5],
                    "msize": misc[:, 6], "pc": misc[:, 7],
                    "flog_count": int(scal[0]),
                    "free_count": int(scal[1]),
                    "ucount": int(scal[2]),
                }
                self.last_counts = counts_h
                nf = counts_h["flog_count"]
                ucount = counts_h["ucount"]
                if ucount > utab.shape[0]:
                    # more distinct records than the fused pull budget:
                    # re-pull at the smallest geometric bucket that
                    # fits the count we already have (a few compiles,
                    # cached per bucket; the table ships right-sized)
                    cap = self.n_lanes * self.lane_kwargs.get(
                        "dlog_records", 64)
                    urb_big = utab.shape[0]
                    while urb_big < ucount and urb_big < cap:
                        urb_big *= 2
                    urb_big = min(urb_big, cap)
                    with trace.span("lane.escalate", log="records",
                                    rows=urb_big):
                        utab, uc2 = self._pull(
                            _unique_table_big(st, urb_big))
                    utab = np.asarray(utab)
                    ucount = int(uc2)
                    if ucount > utab.shape[0]:
                        raise RuntimeError(
                            f"{ucount} distinct records in one window "
                            f"exceed the escalation budget")
                if nf > ftab.shape[0]:
                    with trace.span("lane.escalate", log="forks",
                                    rows=nf):
                        ftab = np.asarray(self._pull(
                            _gather_full_flog(st)))
                # decoding the window's record and fork tables is the
                # first half of the drain; _drain_host below the second
                with trace.span("lane.drain", records=ucount, forks=nf):
                    recs = []
                    for i in range(ucount):
                        row = utab[i]
                        recs.append((
                            int(row[4]), int(row[0]), int(row[1]),
                            int(row[2]), int(row[3]), int(row[5]),
                            (int(row[6]), int(row[7]), int(row[8])),
                            np.ascontiguousarray(row[9:]).view(np.uint32)
                            .reshape(3, bv256.NLIMBS),
                        ))
                    forks = []
                    for i in range(nf):
                        r = ftab[i]
                        forks.append((
                            int(r[2]), int(r[0]), int(r[1]), int(r[3]),
                            int(r[4]), int(np.uint32(r[5])),
                            int(np.uint32(r[6])), int(r[7]), int(r[8]),
                        ))
                status = counts_h["status"].copy()
                steps = counts_h["steps"]
                # forked children consumed slots from the top (tail) of the
                # free stack; reconcile before re-seeding
                consumed = n_free_written - counts_h["free_count"]
                if consumed:
                    free = free[: n_free_written - consumed]

                # fast-retired lanes: the window dispatch already
                # gathered their rows and marked them DEAD (ridx row i
                # is the i-th retired lane; padding entries hold n)
                fast = [int(x) for x in ridx if x < n]
                # escalation set: parked lanes past the fast budget or
                # over a column floor (status still NEEDS_HOST), plus
                # runaways
                runaway = (status == Status.RUNNING) \
                    & (steps >= self.step_budget)
                rest = np.nonzero(
                    (status == Status.NEEDS_HOST) | runaway)[0].tolist()
                # in-place resume candidates: the device held SHA3-
                # parked lanes in the envelope and shipped their slim
                # rows with this window's output. Resolving them needs
                # the drain's provisional-sid map, so the actual
                # _try_resume runs AFTER the drain below; here the
                # held set is only carved out of the escalation retire
                # (optimistically — a declined lane retires through
                # the supplementary dispatch afterwards).
                held = [int(x) for x in hidx if x < n]
                cap_r = small
                full_r = self._full_bucket()
                if len(held) > small and full_r > small \
                        and warm_variant(
                    self.n_lanes, code_len,
                    self.lane_kwargs, self.window,
                    self.step_budget, seed_bucket=full_r,
                    mesh=self.mesh,
                ):
                    cap_r = full_r
                held = held[:cap_r]
                if held:
                    held_set = set(held)
                    rest = [l for l in rest if l not in held_set]
                # DISPATCH the escalation retire before the host drain:
                # the device gathers and ships the rows (the largest
                # per-window transfer) while the host resolves this
                # window's records and forks — the two biggest
                # per-window costs overlap instead of serializing
                def _retire_floors(lanes_sel):
                    lk = self.lane_kwargs
                    c = counts_h
                    sel = np.asarray(lanes_sel, np.int32)
                    return (
                        _geo_bucket(max(int(c["sp"][sel].max()), 1),
                                    lk.get("stack_depth", 64), 8),
                        _geo_bucket(max(int(c["msize"][sel].max()), 1),
                                    lk.get("memory_bytes", 4096), 64),
                        _geo_bucket(
                            max(int(c["mlog_count"][sel].max()), 1),
                            lk.get("mem_records", 64), 8),
                        _geo_bucket(max(int(c["scount"][sel].max()), 1),
                                    lk.get("storage_slots", 64), 8),
                    )

                def _materialize_rows(lanes_sel, rows_host):
                    with trace.span("lane.materialize",
                                    n=len(lanes_sel)):
                        for row, lane in enumerate(lanes_sel):
                            self.stats["device_steps"] += \
                                int(steps[lane])
                            if lane not in dead_set:
                                deliver(owner_of(ctxs[lane]),
                                        self.materialize(
                                            rows_host, row,
                                            ctxs[lane]))
                            ctxs[lane] = None
                            free.append(lane)
                    status[np.asarray(lanes_sel, np.int32)] = DEAD

                rest_chunks = []
                if rest:
                    st, rest_chunks = self._retire_chunked(
                        st, rest, _retire_floors)

                with trace.span("lane.drain", records=len(recs),
                                forks=len(forks)):
                    self._prov, dead = self._drain_host(recs, forks,
                                                        ctxs)
                dead_set = set(dead)

                # merge-before-spill (docs/drain_pipeline.md): the
                # retired spill candidates — fast + escalation sets,
                # now with their condition lists final — collapse
                # exact-frontier twins BEFORE any GlobalState is
                # built; dropped twins are never materialized, so the
                # spill/refill regime stops re-executing rejoins it
                # would have merged at the next dispatch
                spill_dropped: set = set()
                if fast or rest:
                    with trace.span("lane.spill_merge",
                                    lanes=len(fast) + len(rest)):
                        spill_dropped = self._spill_merge(
                            st, fast + rest, ctxs, dead_set, counts_h)

                # in-place resume (needs self._prov): patches ride the
                # next dispatch's seed buffer — zero extra round trips.
                # A trivially-false (dead) lane must NOT resume: the
                # next dispatch's kill would race its patch (kill sets
                # DEAD before patches set RUNNING) while the host has
                # already freed its slot — route dead lanes to the
                # supplementary retire instead.
                declined: List[int] = []
                if held:
                    pcs = counts_h["pc"]
                    rrows = _unpack_resume((h_i32, h_u32, h_u8))
                    with trace.span("lane.resume_host", held=len(held)):
                        for row_i, lane in enumerate(held):
                            patch = None
                            if lane not in dead_set:
                                patch = self._try_resume(
                                    rrows, row_i,
                                    int(pcs[lane]),
                                    int(counts_h["sp"][lane]))
                            if patch is not None:
                                resumes.append((lane,) + patch)
                                status[lane] = Status.RUNNING
                                self.stats["resumed"] += 1
                            else:
                                declined.append(lane)

                if fast:
                    # decode the fast-retired rows and park them in the
                    # ring (codec-encoded); they materialize at flush
                    with trace.span("retire.park", n=len(fast)):
                        st_fast = _unpack_rows((r_i32, r_u32, r_u8),
                                               *RETIRE_FLOORS)
                        items = []
                        for row, lane in enumerate(fast):
                            self.stats["device_steps"] += int(steps[lane])
                            if lane not in dead_set \
                                    and lane not in spill_dropped:
                                items.append((row, ctxs[lane]))
                            ctxs[lane] = None
                            free.append(lane)
                        if items:
                            _submit_mat(st_fast, None, items,
                                        time.perf_counter())
                for part, rows_ref, floors_c, t_disp in rest_chunks:
                    # pipelined: each chunk's pull rides the NEXT
                    # window's execution (the gathers were dispatched
                    # before the drain and are ordered ahead of any
                    # re-seed by the st dependency chain); slots free
                    # NOW — the device already marked the rows DEAD.
                    # ctx refs snapshot here: the slot may be
                    # re-seeded before the ring delivers.
                    items = []
                    for row, lane in enumerate(part):
                        self.stats["device_steps"] += int(steps[lane])
                        if lane not in dead_set \
                                and lane not in spill_dropped:
                            items.append((row, ctxs[lane]))
                        ctxs[lane] = None
                        free.append(lane)
                    status[np.asarray(part, np.int32)] = DEAD
                    _submit_mat(rows_ref, floors_c, items, t_disp)
                if declined:
                    # rare: held lanes the host would not resume
                    # (symbolic length, OOG, oversize, trivially-false
                    # path) retire through a supplementary dispatch —
                    # they must not stay held forever
                    st, dchunks = self._retire_chunked(
                        st, declined, _retire_floors)
                    for part, drows, dfloors, _t in dchunks:
                        with trace.span("retire.pull", rows=len(part)):
                            d_host = _unpack_rows(
                                self._pull(drows), *dfloors)
                        _materialize_rows(part, d_host)
                # 3. trivially-false lanes still RUNNING on device: kill
                # them at the next dispatch (before it seeds anything) and
                # recycle their slots after it. Their host status stays
                # RUNNING so the loop always runs that dispatch.
                retired = set(fast) | set(rest) | set(declined)
                for lane in dead:
                    if lane not in retired:
                        kill.append(lane)
                # solver-killed lanes from the overlapped fork screen:
                # proved-UNSAT prefixes die at the next dispatch, same
                # protocol as trivially-false lanes. A lane that parked
                # or died in the meantime is skipped (its state already
                # materialized; the open-state screen prunes it later).
                for lane in screen_dead:
                    if (lane not in retired and lane not in dead_set
                            and status[lane] == Status.RUNNING
                            and ctxs[lane] is not None
                            and lane not in kill):
                        kill.append(lane)
                        self.stats["fork_killed"] += 1
                screen_dead = []
                # window-boundary STATIC retire (MTPU_STATIC,
                # docs/static_pass.md): lanes whose remaining
                # reachable-detector mask is dead against the active
                # mask ride the next dispatch's kill list with zero
                # solver/materialize work. Runs BEFORE the merge pass,
                # which then never pays fingerprint work for them.
                with trace.span("lane.static_retire"):
                    self._static_retire(status, ctxs, dead_set, kill,
                                        counts_h, resumes)
                # window-boundary lane merge/subsume (MTPU_MERGE,
                # docs/lane_merge.md): exact-frontier twins collapse
                # under an OR'd constraint suffix, implied siblings
                # retire subsumed — their kills ride the next dispatch
                # (same protocol as trivially-false lanes), BEFORE that
                # window executes, so a merged-away lane never runs
                # another step
                with trace.span("lane.window_merge"):
                    self._window_merge(st, status, ctxs, dead_set, kill,
                                       counts_h, resumes)
                # mid-flight wave export (MTPU_CKPT,
                # docs/checkpoint.md): a work-stealing client can take
                # the tail of the live wave at this boundary — the
                # lanes retire into complete mid-path GlobalStates and
                # ship; their slots free for the next dispatch
                if self.export_client is not None:
                    st = self._window_export(
                        st, status, ctxs, dead_set, kill, resumes,
                        steps, free, results, _retire_floors)
                # collect the NEXT overlapped screen batch: lanes that
                # gained path conditions this window and are still
                # running (their descendants subset-kill through the
                # per-explore registry once a prefix is refuted)
                if screen_on and forks:
                    touched = sorted({f[1] for f in forks}
                                     | {f[2] for f in forks})
                    pending_screen = [
                        (lane, [c for (_, c) in ctxs[lane].conds])
                        for lane in touched
                        if (status[lane] == Status.RUNNING
                            and lane not in dead_set
                            and lane not in kill
                            and ctxs[lane] is not None
                            and ctxs[lane].conds)
                    ][:256]
                    if pending_screen:
                        # submit NOW: a parallel pool solves while this
                        # thread packs/dispatches the next window and
                        # waits on the device pull (collected at the
                        # next overlapped phase — kills still land at
                        # dispatch k+2, same protocol as before)
                        screen_future = self._submit_fork_screen(
                            pending_screen, screen_registry)
                        pending_screen = []

                # width-demand sample: lanes concurrently occupied plus
                # entries still queued for a slot (what a wide-enough
                # engine would have run this window)
                peak_demand = max(peak_demand,
                                  n - len(free) + len(queue))
                running = int(np.sum(status == Status.RUNNING))
                if not running and not queue:
                    break
            # the last window has no successor dispatch to hide behind
            ring.flush()
        finally:
            self._explore_ctxs = None
            self._ring = None
            try:
                # exception mid-sweep: pending ring chunks are
                # deliberately NOT flushed (svm re-runs the entry
                # states host-side) — just stop the workers and book
                # the occupancy high-water mark
                ring.close()
                if ring.high_water > self.stats.get(
                        "ring_high_water", 0):
                    self.stats["ring_high_water"] = ring.high_water
                _SS().bump_max(ring_high_water=ring.high_water)
            except Exception:  # telemetry only
                pass
            trace.end("lane.explore",
                      windows=self.stats["windows"]
                      - stats0.get("windows", 0))
            # an exception mid-sweep (svm falls back to the host)
            # must not lose coverage accumulated in prior windows;
            # a donated-then-failed dispatch can leave the bitmap
            # deleted, in which case drop it rather than crash
            try:
                if not packed:
                    self._visited_dev[code_bytes] = visited
                    self.visited_by_code[code_bytes] = np.asarray(
                        self._pull(visited))[: cc.size]
                else:
                    # per-member coverage: slice each segment out of
                    # the arena bitmap and OR into the per-code map
                    vh = np.asarray(self._pull(visited))
                    for m in mems:
                        cur = vh[m.base: m.base + len(m.code)]
                        prev = self.visited_by_code.get(m.code)
                        if prev is not None \
                                and prev.shape == cur.shape:
                            cur = cur | prev
                        self.visited_by_code[m.code] = cur
            except Exception:
                if not packed:
                    self._visited_dev.pop(code_bytes, None)
        self._release_state(st)
        # static jump-table consult (docs/static_pass.md): a symbolic-
        # dest JUMP park with a statically-proved singleton target
        # continues in place instead of dying in the interpreter
        # (per-code — stands down under a packed wave)
        if not packed:
            results = self._patch_jump_parks(results)
        global LAST_RUN_STATS
        delta = {k: v - stats0.get(k, 0) for k, v in self.stats.items()}
        if not packed \
                and peak_demand > PATH_HISTORY.get(code_bytes, 0):
            PATH_HISTORY[code_bytes] = peak_demand
        LAST_RUN_STATS = self.last_run_stats = delta
        for key, val in delta.items():
            RUN_STATS_TOTAL[key] = RUN_STATS_TOTAL.get(key, 0) + val
        if packed:
            return router.lists
        return {None: results}

    # -- device-state pooling ------------------------------------------------

    def _shape_key(self) -> tuple:
        return (self.n_lanes, _mesh_key(self.mesh)) \
            + tuple(sorted(self.lane_kwargs.items()))

    def _acquire_state(self) -> SymLaneState:
        pool = _STATE_POOL.get(self._shape_key())
        if pool:
            return pool.pop()
        with trace.span("lane.init", n_lanes=self.n_lanes,
                        shards=1 if self.mesh is None
                        else self.mesh.devices.size):
            if self.mesh is None:
                return symstep.init_sym_lanes(self.n_lanes,
                                              **self.lane_kwargs)
            from ..parallel.mesh import init_planes

            return init_planes(self.mesh, self.n_lanes,
                               **self.lane_kwargs)

    def _release_state(self, st: SymLaneState) -> None:
        """Park the (all-DEAD) device buffers for the next explore —
        possibly by a different engine or contract. Stale plane contents
        are unreachable: seeding rewrites every live field of a row, and
        log counters were reset by the window dispatches."""
        pool = _STATE_POOL.setdefault(self._shape_key(), [])
        if len(pool) < 2:  # bound device memory held by idle batches
            pool.append(st)
