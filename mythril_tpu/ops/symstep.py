"""Symbolic lane stepper: batched *symbolic* EVM execution on device.

This is the symbolic lift of the concrete lane engine (ops/stepper.py) —
the bridge that makes the TPU the primary execution substrate for `myth
analyze` workloads (SURVEY.md §7 step 4). Where the reference forks and
evaluates one `GlobalState` at a time in Python with z3 terms on the stack
(mythril/laser/ethereum/svm.py:293-337, instructions.py:1520-1636), here N
paths execute per device step and symbolic values are *handles*:

- every value plane (stack, storage values, env words, calldata size)
  carries a parallel i32 **sid plane**: 0 = the 8xu32 limbs are the
  concrete value; >0 = index into the host bridge's object table (a
  facade BitVec/Bool built at a previous drain); <0 = *provisional* id
  minted this window, encoding (lane, deferred-record slot);
- ops over all-concrete operands execute exactly like the concrete
  stepper; any symbolic operand instead appends a **deferred record**
  (op, pc, step, three operand sids/values) to the lane's bounded log and
  pushes a provisional sid. The host drains logs each sync window and
  builds the same terms the interpreter would have built — via the shared
  mythril_tpu/laser/alu.py semantics, so divergence is impossible by
  construction;
- a symbolic JUMPI **forks the lane**: the parent takes the jump, a copy
  written into a free slot takes the fall-through, and both append the
  condition to their path-condition log (the device analog of the
  reference's two deepcopies + constraint append,
  instructions.py:1597-1633). Fork slots come from a device-side free
  list refilled by the host;
- memory keeps three planes: concrete bytes, a per-byte **writer-kind**
  plane (never-written / MSTORE8-int / concrete-word / symbolic-word —
  the distinction state/memory.py makes between int and 8-bit-term
  entries), and a bounded symbolic **overlay log** (offset, len, sid)
  recording only symbolic word stores. Aligned 32-byte symbolic
  store/load pairs (the dominant Solidity scratch-space pattern) resolve
  on device; loads mixing symbolic and concrete bytes park;
- storage entries carry value sids and a `written` flag; misses against a
  symbolic base array defer to a select() built at drain time and are
  cached in the log so repeated loads are device-local;
- anything the device cannot model *parks* the lane (NEEDS_HOST) with the
  pc still pointing at the unexecuted instruction: the host engine
  re-executes that instruction with full hook dispatch, so detector and
  transaction semantics are exactly the host's. Terminal ops
  (STOP/RETURN/REVERT/INVALID/SELFDESTRUCT) always park — paths end once,
  and ending them host-side keeps tx-end signals and issue checks intact.

Gas is the host's [min, max] interval accounting (static opcode costs +
the quadratic memory-expansion fee of machine_state.calculate_memory_gas),
so materialized states carry exactly the gas the interpreter would have.
"""

import functools
import os
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..support.opcodes import ADDRESS, GAS, OPCODES
from . import bv256
from .stepper import (
    ENV_SLOTS,
    N_ENV,
    NPOP_TABLE,
    NPUSH_TABLE,
    RESULT_CLASSES,
    RESULT_CLASS_ID,
    RESULT_CLASS_TABLE,
    ENV_TABLE,
    CompiledCode,
    Status,
    _onehot_gather,
    _peek,
    _scatter_word,
    _u32_of,
    bytes_be_to_word,
    compile_code,
    word_to_bytes_be,
)

_OP = {name: data[ADDRESS] for name, data in OPCODES.items()}

# status additions
DEAD = 7  # free slot (never executed / retired)

GAS_MEMORY = 3
GAS_MEMORY_QUAD_DENOM = 512

# memory writer kinds (per byte): the host Memory stores MSTORE8 bytes as
# ints but word-store bytes as 8-bit terms (state/memory.py:61-88,111-132);
# materialization must reproduce that representation exactly
KIND_NONE = 0
KIND_BYTE_INT = 1    # MSTORE8 with concrete value
KIND_CONC_WORD = 2   # MSTORE with concrete value
KIND_SYM_WORD = 3    # MSTORE with symbolic value (overlay log has sid)


def _build_sym_tables():
    gas_min = np.zeros(256, dtype=np.uint32)
    gas_max = np.zeros(256, dtype=np.uint32)
    for name, data in OPCODES.items():
        byte = data[ADDRESS]
        gas_min[byte] = data[GAS][0]
        gas_max[byte] = data[GAS][1]

    executable = np.zeros(256, dtype=bool)
    deferrable = np.zeros(256, dtype=bool)

    defer_ops = (
        "ADD MUL SUB DIV SDIV MOD SMOD ADDMOD MULMOD EXP SIGNEXTEND "
        "LT GT SLT SGT EQ ISZERO AND OR XOR NOT BYTE SHL SHR SAR "
        "BALANCE"
    ).split()
    for name in defer_ops:
        deferrable[_OP[name]] = True
        executable[_OP[name]] = True

    for name in (
        "POP MLOAD MSTORE MSTORE8 SLOAD SSTORE SHA3 JUMP JUMPI "
        "JUMPDEST PC MSIZE GAS CALLDATALOAD CALLDATASIZE CODESIZE"
    ).split():
        executable[_OP[name]] = True
    for name in ENV_SLOTS:
        executable[_OP[name]] = True
    for b in range(0x60, 0xA0):  # PUSH1-32, DUP1-16, SWAP1-16
        executable[b] = True

    return gas_min, gas_max, executable, deferrable


# numpy masters (host-side consumers must NOT pull the jnp versions
# back — every device_get is a host-device round trip)
(GAS_MIN_TABLE, GAS_MAX_TABLE, SYM_EXECUTABLE, DEFERRABLE) = \
    _build_sym_tables()

#: pseudo-op byte (outside the 0-255 opcode space) marking a deferred
#: read-over-write SLOAD record minted by a symbolic-storage-mode lane.
#: Distinct from the plain SLOAD record (seed-storage select) because
#: its resolution depends on the lane's per-path write mirror — such
#: records must never dedup across lanes.
REC_SLOAD_RW = 0x154

#: triage kill-switches (read at trace time — set before the first
#: window compiles): disable the SHA3 defer / symbolic-storage-mode
#: fast paths to fall back to park-and-materialize behavior
NO_SHA3_DEFER = os.environ.get("MTPU_NO_SHA3_DEFER") == "1"
NO_STORAGE_MODE = os.environ.get("MTPU_NO_STORAGE_MODE") == "1"


class SymLaneState(NamedTuple):
    """Struct-of-arrays symbolic lane batch. Shapes:
    N lanes, D stack, M memory bytes, MR memory-overlay records,
    S storage slots, C calldata bytes, R deferred records, P path conds,
    F fork-log entries."""

    pc: jnp.ndarray            # (N,) i32 — byte address
    sp: jnp.ndarray            # (N,) i32
    depth: jnp.ndarray         # (N,) i32 — JUMPI fork depth (host parity)
    group: jnp.ndarray         # (N,) i32 — seed cohort (same entry
    #                            template); forks inherit it. Device-side
    #                            record dedup never merges across groups
    fentry: jnp.ndarray        # (N,) i32 — last function-entry jump dest
    #                            (-1 = none; svm._new_node_state parity)
    last_jump: jnp.ndarray     # (N,) i32 — byte pc of the last executed
    #                            JUMP (-1 = none; feeds the exceptions
    #                            module's LastJumpAnnotation at drain)
    stack: jnp.ndarray         # (N, D, 8) u32
    ssid: jnp.ndarray          # (N, D) i32
    memory: jnp.ndarray        # (N, M) u8
    mkind: jnp.ndarray         # (N, M) u8 — KIND_* per byte
    msize: jnp.ndarray         # (N,) i32
    mlog_off: jnp.ndarray      # (N, MR) i32
    mlog_len: jnp.ndarray      # (N, MR) i32
    mlog_sid: jnp.ndarray      # (N, MR) i32 — symbolic word stores only
    mlog_count: jnp.ndarray    # (N,) i32
    skeys: jnp.ndarray         # (N, S, 8) u32
    svals: jnp.ndarray         # (N, S, 8) u32
    sval_sid: jnp.ndarray      # (N, S) i32
    s_written: jnp.ndarray     # (N, S) i32 (1 = SSTORE, 0 = read cache)
    s_read: jnp.ndarray        # (N, S) i32 bitmask: 1 = read before any
    #                            write, 2 = read after a write (both can
    #                            be set; drives keys_get replay parity)
    skey_sid: jnp.ndarray      # (N, S) i32 — 0 = concrete key (limbs in
    #                            skeys), else the key term's sid
    s_wstep: jnp.ndarray       # (N, S) i32 — step_no of the slot's last
    #                            SSTORE (materialize replays writes in
    #                            this order: with maybe-aliasing symbolic
    #                            keys, write order decides the term)
    s_mode: jnp.ndarray        # (N,) i32 — 1 = symbolic-storage mode:
    #                            the lane has touched a symbolic storage
    #                            key; every SSTORE emits a mirror record
    #                            and every SLOAD defers to a host-built
    #                            read-over-write term (REC_SLOAD_RW)
    scount: jnp.ndarray        # (N,) i32
    sbase: jnp.ndarray         # (N,) i32 (0 = zero K-array base, else sym)
    calldata: jnp.ndarray      # (N, C) u8
    cd_size: jnp.ndarray       # (N,) i32
    cd_sym: jnp.ndarray        # (N,) i32 (1 = calldata is symbolic)
    cd_size_sid: jnp.ndarray   # (N,) i32
    env: jnp.ndarray           # (N, N_ENV, 8) u32
    env_sid: jnp.ndarray       # (N, N_ENV) i32
    min_gas: jnp.ndarray       # (N,) u32
    max_gas: jnp.ndarray       # (N,) u32
    gas_limit: jnp.ndarray     # (N,) u32
    status: jnp.ndarray        # (N,) i32
    steps: jnp.ndarray         # (N,) i32
    dlog_op: jnp.ndarray       # (N, R) i32
    dlog_pc: jnp.ndarray       # (N, R) i32
    dlog_step: jnp.ndarray     # (N, R) i32
    dlog_fentry: jnp.ndarray   # (N, R) i32 — fentry at record time
    dlog_sid: jnp.ndarray      # (N, R, 3) i32
    dlog_val: jnp.ndarray      # (N, R, 3, 8) u32
    dlog_count: jnp.ndarray    # (N,) i32
    # fork table: ONE row per symbolic-JUMPI fork carrying everything
    # the host drain needs about the fork site (there is no per-lane
    # path-condition plane: a lane's conditions are reconstructed from
    # its fork genealogy, and every condition append coincides with a
    # fork). gmin/gmax are the parent's PRE-execution gas interval at
    # the JUMPI (hook parity).
    flog_parent: jnp.ndarray   # (F,) i32
    flog_child: jnp.ndarray    # (F,) i32
    flog_step: jnp.ndarray     # (F,) i32
    flog_pc: jnp.ndarray       # (F,) i32 — byte pc of the JUMPI
    flog_sid: jnp.ndarray      # (F,) i32 — condition sid (may be
    #                            provisional until the window-end remap)
    flog_gmin: jnp.ndarray     # (F,) u32
    flog_gmax: jnp.ndarray     # (F,) u32
    flog_fentry: jnp.ndarray   # (F,) i32
    flog_dest: jnp.ndarray     # (F,) i32 — concrete jump destination
    flog_count: jnp.ndarray    # () i32
    free_slots: jnp.ndarray    # (N,) i32 — stack of free slot indices
    free_count: jnp.ndarray    # () i32
    step_no: jnp.ndarray       # () i32 — global step counter


#: per-step fork fan-out budget. This bounds the row-copy the fork
#: phase scatters across every lane-axis plane, but more importantly it
#: sets how many STEPS a wide fork level needs: a population of P lanes
#: reaching their JUMPI in lockstep forks in ceil(P / budget) steps,
#: and every stall step pays the full fused-step wall. At 64 (the old
#: value) a 16k-wide level burned 256 ~100 ms steps just fanning out —
#: raising the budget to 2048 ran the same 32k-path tree 10x faster
#: with the fork-phase copy cost still noise (a few MB per fork step).
#: Clamped to the lane count at trace time (narrow engines keep small
#: copies).
MAX_FORKS_PER_STEP = 2048


@functools.partial(jax.jit, static_argnums=tuple(range(9)))
def _init_sym_lanes_dev(
    n_lanes, stack_depth, memory_bytes, mem_records, storage_slots,
    calldata_bytes, dlog_records, pc_records, gas_limit,
) -> SymLaneState:
    # one jitted (and persistently cached) executable builds the whole
    # zero state on device: per-field jnp.zeros would compile ~40 tiny
    # fill kernels, and numpy+device_put pays ~40 H2D transfers
    z = jnp.zeros
    n = n_lanes
    return SymLaneState(
        pc=z((n,), jnp.int32),
        sp=z((n,), jnp.int32),
        depth=z((n,), jnp.int32),
        group=z((n,), jnp.int32),
        fentry=jnp.full((n,), -1, jnp.int32),
        last_jump=jnp.full((n,), -1, jnp.int32),
        stack=z((n, stack_depth, bv256.NLIMBS), jnp.uint32),
        ssid=z((n, stack_depth), jnp.int32),
        memory=z((n, memory_bytes), jnp.uint8),
        mkind=z((n, memory_bytes), jnp.uint8),
        msize=z((n,), jnp.int32),
        mlog_off=z((n, mem_records), jnp.int32),
        mlog_len=z((n, mem_records), jnp.int32),
        mlog_sid=z((n, mem_records), jnp.int32),
        mlog_count=z((n,), jnp.int32),
        skeys=z((n, storage_slots, bv256.NLIMBS), jnp.uint32),
        svals=z((n, storage_slots, bv256.NLIMBS), jnp.uint32),
        sval_sid=z((n, storage_slots), jnp.int32),
        s_written=z((n, storage_slots), jnp.int32),
        s_read=z((n, storage_slots), jnp.int32),
        skey_sid=z((n, storage_slots), jnp.int32),
        s_wstep=z((n, storage_slots), jnp.int32),
        s_mode=z((n,), jnp.int32),
        scount=z((n,), jnp.int32),
        sbase=z((n,), jnp.int32),
        calldata=z((n, calldata_bytes), jnp.uint8),
        cd_size=z((n,), jnp.int32),
        cd_sym=z((n,), jnp.int32),
        cd_size_sid=z((n,), jnp.int32),
        env=z((n, N_ENV, bv256.NLIMBS), jnp.uint32),
        env_sid=z((n, N_ENV), jnp.int32),
        min_gas=z((n,), jnp.uint32),
        max_gas=z((n,), jnp.uint32),
        gas_limit=jnp.full((n,), gas_limit, jnp.uint32),
        status=jnp.full((n,), DEAD, jnp.int32),
        steps=z((n,), jnp.int32),
        dlog_op=z((n, dlog_records), jnp.int32),
        dlog_pc=z((n, dlog_records), jnp.int32),
        dlog_step=z((n, dlog_records), jnp.int32),
        dlog_fentry=z((n, dlog_records), jnp.int32),
        dlog_sid=z((n, dlog_records, 3), jnp.int32),
        dlog_val=z((n, dlog_records, 3, bv256.NLIMBS), jnp.uint32),
        dlog_count=z((n,), jnp.int32),
        flog_parent=z((n,), jnp.int32),
        flog_child=z((n,), jnp.int32),
        flog_step=z((n,), jnp.int32),
        flog_pc=z((n,), jnp.int32),
        flog_sid=z((n,), jnp.int32),
        flog_gmin=z((n,), jnp.uint32),
        flog_gmax=z((n,), jnp.uint32),
        flog_fentry=z((n,), jnp.int32),
        flog_dest=z((n,), jnp.int32),
        flog_count=jnp.zeros((), jnp.int32),
        free_slots=jnp.arange(n - 1, -1, -1, dtype=jnp.int32),
        free_count=jnp.full((), n, jnp.int32),
        step_no=jnp.zeros((), jnp.int32),
    )


def init_sym_lanes(
    n_lanes: int,
    stack_depth: int = 64,
    memory_bytes: int = 4096,
    mem_records: int = 64,
    storage_slots: int = 64,
    calldata_bytes: int = 512,
    dlog_records: int = 64,
    pc_records: int = 64,
    gas_limit: int = 8_000_000,
) -> SymLaneState:
    return _init_sym_lanes_dev(
        n_lanes, stack_depth, memory_bytes, mem_records, storage_slots,
        calldata_bytes, dlog_records, pc_records, gas_limit,
    )


def _gather_flat(arr, idx):
    """arr[lane, idx[lane]] for a (N, S) plane via dense one-hot."""
    size = arr.shape[1]
    onehot = jnp.arange(size)[None, :] == idx[:, None]
    return jnp.sum(jnp.where(onehot, arr, 0), axis=1)


def _scatter_flat(arr, lane_mask, idx, value):
    """arr[lane, idx[lane]] = value[lane] where lane_mask (dense)."""
    size = arr.shape[1]
    onehot = (jnp.arange(size)[None, :] == idx[:, None]) \
        & lane_mask[:, None]
    return jnp.where(onehot, value[:, None], arr)


def _peek_sid(ssid, sp, k):
    return _gather_flat(ssid, jnp.clip(sp - k, 0, ssid.shape[1] - 1))


def _overlay_exact_hit(st, woff, mem_recs):
    """(exact, sid) for the LAST overlay record overlapping the 32-byte
    window at woff: exact iff that record covers the window precisely
    (off == woff, len == 32). The single source of the exact-hit rule
    shared by MLOAD resolution and SHA3 word reads — callers must also
    require the window's kind bytes to be all-KIND_SYM_WORD."""
    rec_ids = jnp.arange(mem_recs)[None, :]
    live_rec = rec_ids < st.mlog_count[:, None]
    ov = (live_rec & (st.mlog_off < (woff + 32)[:, None])
          & ((st.mlog_off + st.mlog_len) > woff[:, None]))
    last = jnp.max(jnp.where(ov, rec_ids + 1, 0), axis=1) - 1
    lc = jnp.clip(last, 0, mem_recs - 1)
    exact = ((last >= 0)
             & (_gather_flat(st.mlog_off, lc) == woff)
             & (_gather_flat(st.mlog_len, lc) == 32))
    sid = jnp.where(exact, _gather_flat(st.mlog_sid, lc), 0)
    return exact, sid


def _mem_fee(old_bytes, new_bytes):
    """Yellow-paper memory fee delta, mirroring
    MachineState.calculate_memory_gas (laser/state/machine_state.py)."""
    ow = (old_bytes // 32).astype(jnp.uint32)
    nw = (new_bytes // 32).astype(jnp.uint32)
    old_fee = ow * GAS_MEMORY + (ow * ow) // GAS_MEMORY_QUAD_DENOM
    new_fee = nw * GAS_MEMORY + (nw * nw) // GAS_MEMORY_QUAD_DENOM
    return new_fee - old_fee


def _nbits(x):
    """(…, 8) u32 limbs -> number of significant bits (0 for zero)."""
    bl = 32 - lax.clz(x).astype(jnp.int32)
    pos = bl + 32 * jnp.arange(bv256.NLIMBS, dtype=jnp.int32)
    return jnp.max(jnp.where(x != 0, pos, 0), axis=-1)


def _build_mstore_pattern_masks():
    """The user-assertions module fires on concrete MSTOREs whose hex
    rendering starts with the 60-digit 0xcafe… scribble pattern
    (analysis/module/modules/user_assertions.py). A value of nd hex
    digits (no leading zeros) matches iff value >> 4*(nd-60) equals the
    240-bit pattern, nd in [60, 64] — precompute (mask, expect) pairs."""
    pat = int("cafe" * 15, 16)  # 240 bits
    masks, expects = [], []
    for s in range(0, 20, 4):
        mask = ((1 << 256) - 1) ^ ((1 << s) - 1)
        masks.append(bv256.int_to_limbs(mask))
        expects.append(bv256.int_to_limbs((pat << s) & ((1 << 256) - 1)))
    return np.stack(masks), np.stack(expects)


MSTORE_PAT_MASK, MSTORE_PAT_EXPECT = _build_mstore_pattern_masks()

# ArbitraryStorage probe slot: a concrete-key SSTORE to it must mint a
# sink record even though nothing is symbolic (the one concrete key the
# module's probe constraint can satisfy).
from ..support.eth_constants import ARB_PROBE_SLOT  # noqa: E402

_ARB_PROBE_LIMBS = np.array(
    [(ARB_PROBE_SLOT >> (32 * i)) & 0xFFFFFFFF for i in range(8)],
    np.uint32)


def sym_step(code: CompiledCode, st: SymLaneState,
             exec_table: jnp.ndarray = None,
             taint_table: jnp.ndarray = None) -> SymLaneState:
    """Advance every running lane by one instruction (symbolic mode).

    exec_table: optional (256,) bool — the set of opcodes the device may
    execute this run. The bridge passes SYM_EXECUTABLE minus every
    opcode with a registered detector pre/post hook, so hooked
    instructions always park and fire their hooks host-side.

    taint_table: optional (256,) bool — opcodes needing drain-side
    detector support (the lane adapters that LIFT a hook from the parked
    set, analysis/module/lane_adapters.py). Per-op meaning:
    ADD/SUB/MUL/EXP — emit a deferred record when all-concrete operands
    actually wrap (the integer module annotates concrete overflows too);
    SSTORE — emit a sink record when the stored value is symbolic (taint
    promotion parity); MSTORE — park when a concrete value matches the
    user-assertions 0xcafe… pattern."""
    if exec_table is None:
        exec_table = SYM_EXECUTABLE
    if taint_table is None:
        taint_table = np.zeros(256, bool)
    # numpy tables embed as free constants; traced args pass through
    exec_table = jnp.asarray(exec_table)
    taint_table = jnp.asarray(taint_table)
    n, depth_cap, _ = st.stack.shape
    mem_bytes = st.memory.shape[1]
    mem_recs = st.mlog_off.shape[1]
    s_slots = st.skeys.shape[1]
    d_recs = st.dlog_op.shape[1]
    lanes = jnp.arange(n)

    running = st.status == Status.RUNNING
    pc_c = jnp.clip(st.pc, 0, code.size)
    if code.seg_tab is not None:
        # cross-tenant packed arena (stepper.compile_packed_code):
        # lane pcs are ARENA coordinates, so the owning member segment
        # is a per-pc lookup; jump bounds, CODESIZE and the PC opcode
        # resolve against the member's own [base, size] row through
        # this one indirect load. Plain compiles take the other branch
        # at trace time — their jit variants (and cached XLA
        # executables) are untouched.
        _seg = code.seg_of[jnp.clip(pc_c, 0,
                                    code.seg_of.shape[0] - 1)]
        _srow = code.seg_tab[jnp.clip(_seg, 0,
                                      code.seg_tab.shape[0] - 1)]
        seg_base, seg_size = _srow[:, 0], _srow[:, 1]
    else:
        seg_base, seg_size = None, None
    op = code.opcode[pc_c]
    # idle lanes execute JUMPDEST (a supported no-op) to stay masked out
    op = jnp.where(running, op, _OP["JUMPDEST"]).astype(jnp.int32)

    npop = jnp.asarray(NPOP_TABLE)[op]
    npush = jnp.asarray(NPUSH_TABLE)[op]
    is_dup = (op >= 0x80) & (op <= 0x8F)
    is_swap = (op >= 0x90) & (op <= 0x9F)
    dup_n = jnp.where(is_dup, op - 0x7F, 1)
    swap_n = jnp.where(is_swap, op - 0x8F, 1)
    eff_pop = jnp.where(is_dup, dup_n, jnp.where(is_swap, swap_n + 1, npop))

    underflow = st.sp < eff_pop
    overflow = (st.sp - npop + npush) > depth_cap

    a = _peek(st.stack, st.sp, 1)
    b = _peek(st.stack, st.sp, 2)
    c = _peek(st.stack, st.sp, 3)
    sid_a = _peek_sid(st.ssid, st.sp, 1)
    sid_b = _peek_sid(st.ssid, st.sp, 2)
    sid_c = _peek_sid(st.ssid, st.sp, 3)
    sym_a = sid_a != 0
    sym_b = sid_b != 0
    sym_c = sid_c != 0
    any_sym = (
        ((npop >= 1) & sym_a)
        | ((npop >= 2) & sym_b)
        | ((npop >= 3) & sym_c)
    )

    zero_w = jnp.zeros_like(a)
    zero_b = jnp.zeros_like(running)
    zero_i = jnp.zeros_like(st.pc)

    # ---- opcode groups ----------------------------------------------------
    is_mload = op == _OP["MLOAD"]
    is_mstore = op == _OP["MSTORE"]
    is_mstore8 = op == _OP["MSTORE8"]
    is_sload = op == _OP["SLOAD"]
    is_sstore = op == _OP["SSTORE"]
    is_cdl = op == _OP["CALLDATALOAD"]
    is_jump = op == _OP["JUMP"]
    is_jumpi = op == _OP["JUMPI"]
    is_exp = op == _OP["EXP"]
    is_sha3 = op == _OP["SHA3"]
    is_balance = op == _OP["BALANCE"]

    # ---- memory offsets / fees (needed before park resolution) -----------
    # SHA3 with a concrete 32/64-byte length reads memory like MLOAD
    # does (and extends msize / pays the fee); anything else about it
    # parks (symbolic offset/length, odd lengths — the in-place resume
    # path owns those)
    sha3_len_u32, sha3_len_hi = _u32_of(b)
    sha3_lenok = (
        is_sha3 & ~sym_b & ~sha3_len_hi
        & ((sha3_len_u32 == 32) | (sha3_len_u32 == 64)))
    sha3_len = jnp.where(sha3_lenok, sha3_len_u32, 32).astype(jnp.int32)
    mem_off_u32, mem_off_hi = _u32_of(a)
    mem_big = mem_off_hi | (mem_off_u32 >= jnp.uint32(1 << 30))
    mem_off = jnp.where(mem_big, 0, mem_off_u32).astype(jnp.int32)
    mem_ops = is_mload | is_mstore | is_mstore8 | sha3_lenok
    acc_len = jnp.where(is_mstore8, 1,
                        jnp.where(is_sha3, sha3_len, 32))
    mem_end = mem_off + acc_len
    mem_oob = mem_ops & ~sym_a & (mem_big | (mem_end > mem_bytes))
    new_msize = jnp.where(
        mem_ops & ~sym_a & ~mem_oob,
        jnp.maximum(st.msize, ((mem_end + 31) // 32) * 32),
        st.msize,
    )
    mem_fee = _mem_fee(st.msize.astype(jnp.uint32),
                       new_msize.astype(jnp.uint32))

    # ---- jump destination decode ------------------------------------------
    # `dest` stays in MEMBER-LOCAL coordinates (it is what the program
    # pushed — recorded in fork logs and fentry tracking for host
    # parity); `dest_eff` is the arena pc control flow actually takes
    dest_u32, dest_hi = _u32_of(a)
    if seg_base is None:
        dest_small = ~dest_hi & (dest_u32 < jnp.uint32(code.size))
        dest = jnp.where(dest_small, dest_u32, 0).astype(jnp.int32)
        dest_eff = dest
    else:
        dest_small = ~dest_hi & (dest_u32
                                 < seg_size.astype(jnp.uint32))
        dest = jnp.where(dest_small, dest_u32, 0).astype(jnp.int32)
        dest_eff = jnp.where(dest_small, dest + seg_base, 0)
    dest_ok = dest_small & code.is_jumpdest[
        jnp.clip(dest_eff, 0, code.size)]
    jumpi_taken_conc = ~sym_b & ~bv256.is_zero(b)

    # ---- EXP purity: device defers only 0/1/2^m concrete bases ------------
    a_popcount = jnp.sum(
        lax.population_count(a.astype(jnp.uint32)), axis=-1
    )
    exp_pure = ~sym_a & (a_popcount <= 1)

    # ---- drain-side taint support (lane adapters) -------------------------
    # all-concrete arithmetic that actually wraps must still reach the
    # host: the integer module annotates concrete overflows too (its
    # constraint folds true). Such ops emit a deferred record like their
    # symbolic siblings; non-wrapping concrete ops stay record-free
    # (their constraint folds false and the host filters them anyway).
    is_add = op == _OP["ADD"]
    is_sub = op == _OP["SUB"]
    is_mul = op == _OP["MUL"]
    taint_op = taint_table[op]
    wrap_cand = (
        running & ~any_sym & taint_op
        & (is_add | is_sub | is_mul | (is_exp & exp_pure))
    )

    def _wrap_flags():
        w_add = is_add & bv256.ult(bv256.add(a, b), a)
        w_sub = is_sub & bv256.ult(a, b)
        nb_a = _nbits(a)
        nb_b = _nbits(b)
        w_mul_cand = is_mul & (nb_a + nb_b >= 257)

        def _mul_exact():
            _, hi = bv256.mul_full(a, b)
            return ~bv256.is_zero(hi)

        w_mul = w_mul_cand & lax.cond(
            jnp.any(wrap_cand & w_mul_cand), _mul_exact, lambda: zero_b
        )
        # pure EXP base 2^m (m>=1): wraps iff exp >= ceil(256/m), i.e.
        # m*exp >= 256 — the integer module's own concrete bound
        m_exp = nb_a - 1
        e_hi = jnp.any(b[..., 1:] != 0, axis=-1)
        e0 = jnp.minimum(b[..., 0], jnp.uint32(1 << 20)).astype(jnp.int32)
        w_exp = (
            is_exp & exp_pure & (a_popcount == 1) & (m_exp >= 1)
            & (e_hi | (m_exp * e0 >= 256))
        )
        return w_add | w_sub | w_mul | w_exp

    wrap_rec = wrap_cand & lax.cond(
        jnp.any(wrap_cand), _wrap_flags, lambda: zero_b
    )

    # SSTORE of a symbolic value leaves a sink record so taint promotion
    # (integer module JUMPI/SSTORE sinks) sees every store, not just the
    # final storage contents. An all-concrete SSTORE whose key IS the
    # ArbitraryStorage probe slot also records: it is the one concrete
    # key the module's probe constraint can satisfy, and without a
    # record the drain would never see the write (adversarial
    # sentinel-writer parity).
    key_is_probe = jnp.all(a == jnp.asarray(_ARB_PROBE_LIMBS), axis=-1)
    sink_want = is_sstore & taint_op & ((sid_b != 0) | key_is_probe)

    # concrete MSTORE matching the user-assertions 0xcafe… pattern parks
    # (the module fires its issue at the MSTORE site host-side)
    mstore_pat_cand = running & is_mstore & ~sym_b & taint_op

    def _mstore_pat():
        nd = (_nbits(b) + 3) // 4
        idx = jnp.clip(nd - 60, 0, 4)
        hit = jnp.all(
            (b & jnp.asarray(MSTORE_PAT_MASK)[idx])
            == jnp.asarray(MSTORE_PAT_EXPECT)[idx], axis=-1
        )
        return (nd >= 60) & hit

    mstore_pat_park = mstore_pat_cand & lax.cond(
        jnp.any(mstore_pat_cand), _mstore_pat, lambda: zero_b
    )

    # ---- memory overlay decisions (MLOAD) — gated: the kind-plane
    # gather and overlay scans read O(N*32 + N*MR) every evaluation ------
    byte_idx32 = mem_off[:, None] + jnp.arange(32)[None, :]
    byte_idx32_c = jnp.clip(byte_idx32, 0, mem_bytes - 1)
    sym_store_val = is_mstore & sym_b

    def _mem_decisions():
        # the kind plane decides concrete vs symbolic reads; the overlay
        # log (symbolic word stores only, in program order) supplies the
        # sid for an exact all-symbolic hit
        kinds32 = jnp.take_along_axis(st.mkind, byte_idx32_c, axis=1)
        any_sym_byte = jnp.any(kinds32 == KIND_SYM_WORD, axis=1)
        all_sym_byte = jnp.all(kinds32 == KIND_SYM_WORD, axis=1)
        hit, hit_sid = _overlay_exact_hit(st, mem_off, mem_recs)
        exact = all_sym_byte & hit
        sym_sid = jnp.where(exact, hit_sid, 0)
        park_ = is_mload & ~sym_a & ~mem_oob \
            & ~(exact | ~any_sym_byte)
        return exact, sym_sid, park_

    top_sym_exact, mload_sym_sid, mload_park = lax.cond(
        jnp.any(running & mem_ops),
        _mem_decisions,
        lambda: (zero_b, zero_i, zero_b),
    )
    # MSTORE of a symbolic word appends an overlay record
    mlog_full = sym_store_val & (st.mlog_count >= mem_recs)

    # ---- SHA3 word reads (gated) ------------------------------------------
    # A 32/64-byte SHA3 whose input words are each either fully
    # concrete or an exact symbolic-overlay hit DEFERS: the record
    # carries the word values/sids + the length, the host builds the
    # keccak term at drain, and the lane keeps running with a
    # provisional sid — no park. This is the mapping-slot hash pattern
    # (MSTORE key; MSTORE slot; SHA3(off, 64)) that otherwise forces a
    # park/resume round trip per hash.
    def _sha3_decisions():
        def word_read(woff):
            bidx = woff[:, None] + jnp.arange(32)[None, :]
            bidx_c = jnp.clip(bidx, 0, mem_bytes - 1)
            kinds = jnp.take_along_axis(st.mkind, bidx_c, axis=1)
            any_symb = jnp.any(kinds == KIND_SYM_WORD, axis=1)
            all_symb = jnp.all(kinds == KIND_SYM_WORD, axis=1)
            hit, hit_sid = _overlay_exact_hit(st, woff, mem_recs)
            exact = all_symb & hit
            sid = jnp.where(exact, hit_sid, 0)
            raw = jnp.take_along_axis(st.memory, bidx_c, axis=1)
            val = bytes_be_to_word(
                jnp.where(bidx < mem_bytes, raw, 0))
            # canonical record args: zero limbs when the sid carries
            # the word (dedup hashes sids AND vals)
            val = jnp.where(exact[:, None], 0, val)
            # per-byte KIND_* bits (2 each), packed: the host rebuilds
            # the hash input term byte-for-byte the way the
            # interpreter's Memory would (ints vs 8-bit const terms vs
            # Extract slices), so the keccak input tids match exactly.
            # A sid-carried word reads all-KIND_SYM_WORD (every 2-bit
            # field = 3) — unambiguous, since a value-carried word can
            # never contain a SYM byte
            k2 = jnp.where(exact[:, None], KIND_SYM_WORD,
                           kinds.astype(jnp.uint32))
            shifts = (2 * jnp.arange(16, dtype=jnp.uint32))[None, :]
            klo = jnp.sum(k2[:, :16] << shifts, axis=1,
                          dtype=jnp.uint32)
            khi = jnp.sum(k2[:, 16:] << shifts, axis=1,
                          dtype=jnp.uint32)
            return exact | ~any_symb, sid, val, klo, khi

        ok0, sid0, val0, k0lo, k0hi = word_read(mem_off)
        ok1, sid1, val1, k1lo, k1hi = word_read(mem_off + 32)
        return (ok0, sid0, val0, k0lo, k0hi,
                ok1, sid1, val1, k1lo, k1hi)

    sha3_cand = running & sha3_lenok & ~sym_a & ~mem_oob & ~mem_big
    zero_u = jnp.zeros((n,), jnp.uint32)
    (s3_ok0, s3_sid0, s3_val0, s3_k0lo, s3_k0hi,
     s3_ok1, s3_sid1, s3_val1, s3_k1lo, s3_k1hi) = lax.cond(
        jnp.any(sha3_cand),
        _sha3_decisions,
        lambda: (zero_b, zero_i, zero_w, zero_u, zero_u,
                 zero_b, zero_i, zero_w, zero_u, zero_u),
    )
    sha3_two = sha3_len == 64
    sha3_defer = sha3_cand & s3_ok0 & (~sha3_two | s3_ok1)
    if NO_SHA3_DEFER:
        sha3_defer = sha3_defer & False

    # ---- storage decisions (gated: the key compare reads the whole
    # (N,S,8) log every evaluation) -----------------------------------------
    def _storage_decisions():
        slot_ids = jnp.arange(s_slots)[None, :]
        live = slot_ids < st.scount[:, None]
        # syntactic key equality: concrete keys by limbs (placeholder
        # limbs of symbolic keys are excluded via skey_sid), symbolic
        # keys by sid identity
        conc_eq = (jnp.all(st.skeys == a[:, None, :], axis=-1)
                   & (st.skey_sid == 0) & ~sym_a[:, None])
        sym_eq = (st.skey_sid == sid_a[:, None]) & sym_a[:, None]
        key_match = (conc_eq | sym_eq) & live
        match_score = jnp.where(key_match, slot_ids + 1, 0)
        best = jnp.max(match_score, axis=1)
        found = best > 0
        idx = jnp.clip(best - 1, 0, s_slots - 1)
        any_written = jnp.any(live & (st.s_written > 0), axis=1)
        return (found, idx, _onehot_gather(st.svals, idx),
                _gather_flat(st.sval_sid, idx), any_written)

    any_storage_op = jnp.any(running & (is_sload | is_sstore))
    (s_found, s_idx, sload_hit_val, sload_hit_sid,
     s_any_written) = lax.cond(
        any_storage_op,
        _storage_decisions,
        lambda: (zero_b, zero_i, zero_w, zero_i, zero_b),
    )
    # symbolic-storage mode: turns on at the lane's first symbolic-key
    # access, but only while its write mirror is empty (mode records
    # capture every write from this step on, so the host's per-path
    # mirror is complete); with unrecorded prior writes the lane parks
    # once and its descendants re-enter through the host interpreter
    sym_key_op = (is_sload | is_sstore) & sym_a
    mode_on_now = sym_key_op & (st.s_mode == 0) & ~s_any_written
    mode_park = sym_key_op & (st.s_mode == 0) & s_any_written
    if NO_STORAGE_MODE:
        mode_on_now = mode_on_now & False
        mode_park = sym_key_op & (st.s_mode == 0)
    mode_eff = (st.s_mode != 0) | mode_on_now
    # in mode every SLOAD defers to a host-built read-over-write term
    # (the syntactic cache could be stale under maybe-aliasing writes;
    # the host's If-chain folds exact matches back to the cached value)
    sload_rw = is_sload & mode_eff
    sload_miss = is_sload & ~s_found
    # non-mode misses against a symbolic base defer to a select() term;
    # misses against the zero K-array are concrete 0 — both are cached
    # in the log (written=0) so materialization can replay keys_get
    sload_miss_sym = sload_miss & ~mode_eff & (st.sbase != 0)
    storage_insert = (is_sstore & ~s_found) | sload_miss
    storage_full = storage_insert & (st.scount >= s_slots)

    # ---- calldata ---------------------------------------------------------
    cd_bytes = st.calldata.shape[1]
    cd_symbolic = st.cd_sym != 0
    cdl_defer = is_cdl & cd_symbolic
    cd_off_u32, cd_off_hi = _u32_of(a)
    cd_big = cd_off_hi | (cd_off_u32 >= jnp.uint32(1 << 30))
    cd_off = jnp.where(cd_big, cd_bytes, cd_off_u32).astype(jnp.int32)
    cd_oob = is_cdl & ~cd_symbolic & ~sym_a & (
        (cd_off < st.cd_size) & (cd_off + 32 > cd_bytes)
    )

    # ---- deferral decision ------------------------------------------------
    defer = jnp.asarray(DEFERRABLE)[op] & any_sym
    defer = defer & ~(is_exp & ~exp_pure)  # impure EXP parks below
    defer = defer | cdl_defer | sload_miss_sym | wrap_rec \
        | sha3_defer | sload_rw
    # mode lanes record every SSTORE (key+value) so the host's
    # per-path write mirror stays complete; taint sinks as before
    sstore_rec_want = sink_want | (is_sstore & mode_eff)
    dlog_full = (defer | sstore_rec_want) & (st.dlog_count >= d_recs)

    # ---- gas --------------------------------------------------------------
    gmin = jnp.asarray(GAS_MIN_TABLE)[op] + mem_fee
    gmax = jnp.asarray(GAS_MAX_TABLE)[op] + mem_fee
    # deferred SHA3 has a concrete length: exact 30 + 6/word (the
    # static table's interval is for unknown lengths)
    sha3_fee = (jnp.uint32(30) + jnp.uint32(6)
                * (sha3_len // 32).astype(jnp.uint32)) + mem_fee
    gmin = jnp.where(sha3_defer, sha3_fee, gmin)
    gmax = jnp.where(sha3_defer, sha3_fee, gmax)
    min_gas_after = st.min_gas + gmin
    oog = min_gas_after > st.gas_limit

    # ---- park resolution (everything except fork capacity) ----------------
    park0 = (
        ~exec_table[op]
        | underflow
        | overflow
        | oog
        | dlog_full
        # impure EXP parks even with all-concrete operands: the host
        # path pins Power(base,exp) == const in the constraints, and a
        # device-executed EXP would silently drop that axiom
        | (is_exp & ~exp_pure)
        # memory
        | (mem_ops & sym_a)                  # symbolic offset
        | (is_mstore8 & sym_b)               # symbolic byte value
        | mem_oob
        | mload_park
        | mlog_full
        # SHA3 outside the defer envelope (symbolic offset/length, odd
        # length, non-word-readable input) parks — the in-place resume
        # path handles it host-side
        | (is_sha3 & ~sha3_defer)
        # BALANCE defers only for SYMBOLIC addresses (a pure select
        # over the world balances array); a concrete address must park
        # — the interpreter's handler may auto-create the account
        # (instructions.py balance_ / accounts_exist_or_load)
        | (is_balance & ~sym_a)
        # storage: symbolic keys run in mode; the one park left is a
        # first symbolic-key access over unrecorded prior writes
        | mode_park
        | storage_full
        # calldata
        | (is_cdl & ~cd_symbolic & sym_a)
        | cd_oob
        # user-assertions scribble pattern (hook fires host-side)
        | mstore_pat_park
        # control flow
        | (is_jump & (sym_a | ~dest_ok))
        # concrete-true condition: a symbolic dest must park (its
        # placeholder limbs would decode to a garbage-but-maybe-valid
        # JUMPDEST and silently take an unconstrained jump)
        | (is_jumpi & ~sym_b & jumpi_taken_conc & (sym_a | ~dest_ok))
        | (is_jumpi & sym_b & (sym_a | ~dest_ok))
        # verified loop-summary heads (loop_summary.device_park_pcs,
        # MTPU_LOOPSUM): park BEFORE executing the head JUMPDEST so
        # the host applies the closed-form summary instead of the
        # device unrolling the loop; all-zero plane when the layer is
        # off, so this term vanishes bit-for-bit
        | code.loopsum_park[pc_c]
    )

    # ---- fork request / slot allocation (after park0 so capacity gaps
    # never orphan a fork whose parent already committed to jumping) --------
    fork_req = running & is_jumpi & sym_b & ~sym_a & dest_ok & ~park0
    forder = jnp.cumsum(fork_req.astype(jnp.int32)) - 1
    navail = jnp.minimum(st.free_count, min(MAX_FORKS_PER_STEP, n))
    flog_room = st.flog_parent.shape[0] - st.flog_count
    navail = jnp.minimum(navail, flog_room)
    fork_can = fork_req & (forder < navail)
    # over the per-step fork budget but within the free pool: STALL the
    # lane (retry the JUMPI next step) instead of parking it — parking
    # would push whole subtrees back to the host whenever one step
    # wants more than MAX_FORKS_PER_STEP forks
    fork_stall = fork_req & ~fork_can & (forder < st.free_count)
    fork_nocap = fork_req & ~fork_can & ~fork_stall

    park = park0 | fork_nocap
    ok = running & ~park & ~fork_stall
    defer = defer & ok
    sink_rec = sstore_rec_want & ok
    logrec = defer | sink_rec
    fork_can = fork_can & ok

    # ---- concrete ALU families (gated; only lanes with all-concrete
    # operands consume these results) ---------------------------------------
    live_alu = ok & ~defer

    add_r = bv256.add(a, b)
    sub_r = bv256.sub(a, b)
    and_r = a & b
    or_r = a | b
    xor_r = a ^ b
    not_r = ~a
    iszero_r = bv256.bool_to_word(bv256.is_zero(a))
    lt_r = bv256.bool_to_word(bv256.ult(a, b))
    gt_r = bv256.bool_to_word(bv256.ugt(a, b))
    slt_r = bv256.bool_to_word(bv256.slt(a, b))
    sgt_r = bv256.bool_to_word(bv256.sgt(a, b))
    eq_r = bv256.bool_to_word(bv256.eq(a, b))

    shift_ops = (
        (op == _OP["BYTE"]) | (op == _OP["SHL"]) | (op == _OP["SHR"])
        | (op == _OP["SAR"]) | (op == _OP["SIGNEXTEND"])
    )
    byte_r, shl_r, shr_r, sar_r, sext_r = lax.cond(
        jnp.any(live_alu & shift_ops),
        lambda: (
            bv256.byte_op(a, b),
            bv256.shl(b, a),
            bv256.shr(b, a),
            bv256.sar(b, a),
            bv256.signextend(a, b),
        ),
        lambda: (zero_w, zero_w, zero_w, zero_w, zero_w),
    )

    mul_r = lax.cond(
        jnp.any(live_alu & (op == _OP["MUL"])),
        lambda: bv256.mul(a, b),
        lambda: zero_w,
    )

    div_ops = (
        (op == _OP["DIV"]) | (op == _OP["SDIV"])
        | (op == _OP["MOD"]) | (op == _OP["SMOD"])
    )

    def _div_all():
        q, r = bv256.divmod_u(a, b)
        sa, sb = bv256.sign_bit(a), bv256.sign_bit(b)
        aa = jnp.where(sa[..., None], bv256.neg(a), a)
        ab = jnp.where(sb[..., None], bv256.neg(b), b)
        sq, sr = bv256.divmod_u(aa, ab)
        sdiv_r = jnp.where((sa ^ sb)[..., None], bv256.neg(sq), sq)
        smod_r = jnp.where(sa[..., None], bv256.neg(sr), sr)
        return q, r, sdiv_r.astype(jnp.uint32), smod_r.astype(jnp.uint32)

    div_r, mod_r, sdiv_r, smod_r = lax.cond(
        jnp.any(live_alu & div_ops),
        _div_all,
        lambda: (zero_w, zero_w, zero_w, zero_w),
    )

    mod2_ops = (op == _OP["ADDMOD"]) | (op == _OP["MULMOD"])
    addmod_r, mulmod_r = lax.cond(
        jnp.any(live_alu & mod2_ops),
        lambda: (bv256.addmod(a, b, c), bv256.mulmod(a, b, c)),
        lambda: (zero_w, zero_w),
    )

    exp_r = lax.cond(
        jnp.any(live_alu & is_exp),
        lambda: bv256.exp(a, b),
        lambda: zero_w,
    )

    # ---- memory execution -------------------------------------------------
    def _memory_block():
        mem_read = jnp.take_along_axis(st.memory, byte_idx32_c, axis=1)
        mload = bytes_be_to_word(mem_read)

        store_bytes = word_to_bytes_be(b)
        do_mstore = ok & is_mstore & ~sym_b
        scatter_idx = jnp.where(do_mstore[:, None], byte_idx32,
                                mem_bytes)
        mem = st.memory.at[lanes[:, None], scatter_idx].set(
            store_bytes, mode="drop"
        )
        # writer-kind plane: concrete word = 2, symbolic word = 3,
        # concrete byte = 1
        do_store_any = ok & is_mstore
        kind_idx = jnp.where(do_store_any[:, None], byte_idx32,
                             mem_bytes)
        kind_val = jnp.where(
            sym_store_val, KIND_SYM_WORD, KIND_CONC_WORD
        ).astype(jnp.uint8)
        mkind = st.mkind.at[lanes[:, None], kind_idx].set(
            jnp.broadcast_to(kind_val[:, None], byte_idx32.shape),
            mode="drop",
        )
        do_mstore8 = ok & is_mstore8
        b8 = (b[..., 0] & 0xFF).astype(jnp.uint8)
        idx8 = jnp.where(do_mstore8, mem_off, mem_bytes)
        mem = mem.at[lanes, idx8].set(b8, mode="drop")
        mkind = mkind.at[lanes, idx8].set(
            jnp.uint8(KIND_BYTE_INT), mode="drop")

        # overlay record for symbolic word stores
        do_rec = ok & sym_store_val
        rec_pos = jnp.clip(st.mlog_count, 0, mem_recs - 1)
        mlog_off_n = _scatter_flat(st.mlog_off, do_rec, rec_pos, mem_off)
        mlog_len_n = _scatter_flat(st.mlog_len, do_rec, rec_pos, acc_len)
        mlog_sid_n = _scatter_flat(st.mlog_sid, do_rec, rec_pos, sid_b)
        mlog_count_n = jnp.where(do_rec, st.mlog_count + 1,
                                 st.mlog_count)
        return (mem, mkind, mload, mlog_off_n, mlog_len_n, mlog_sid_n,
                mlog_count_n)

    (memory, mkind2, mload_r, mlog_off2, mlog_len2, mlog_sid2,
     mlog_count2) = lax.cond(
        jnp.any(ok & mem_ops),
        _memory_block,
        lambda: (st.memory, st.mkind, zero_w, st.mlog_off, st.mlog_len,
                 st.mlog_sid, st.mlog_count),
    )
    msize2 = jnp.where(ok & mem_ops, new_msize, st.msize)
    msize_r = bv256.from_u32(msize2.astype(jnp.uint32))

    # ---- storage execution ------------------------------------------------
    def _storage_block():
        # value pushed by SLOAD: hit -> log value; miss+zero base -> 0;
        # miss+sym base -> provisional (sid handled in sid select)
        sload_v = jnp.where(s_found[:, None], sload_hit_val, 0) \
            .astype(jnp.uint32)

        ins_pos = jnp.where(s_found, s_idx, st.scount)
        pos_c = jnp.clip(ins_pos, 0, s_slots - 1)
        do_sstore = ok & is_sstore
        do_cache = ok & sload_miss
        do_write = do_sstore | do_cache
        new_key = a
        new_val = jnp.where(do_sstore[:, None], b, zero_w)
        new_sid = jnp.where(
            do_sstore, sid_b,
            jnp.where(sload_miss_sym | (sload_rw & sload_miss),
                      prov_id, 0))
        new_written = jnp.where(do_sstore, 1, 0)
        sk = _scatter_word(st.skeys, do_write, pos_c, new_key)
        skd = _scatter_flat(st.skey_sid, do_write, pos_c, sid_a)
        swst = _scatter_flat(
            st.s_wstep, do_sstore, pos_c,
            jnp.full((n,), st.step_no, jnp.int32))
        sv = _scatter_word(st.svals, do_write, pos_c, new_val)
        ssd = _scatter_flat(st.sval_sid, do_write, pos_c, new_sid)
        # an SSTORE over a read-cache slot must mark it written; a cache
        # insert never clears a written flag (cache only fires on miss)
        swr = _scatter_flat(
            st.s_written, do_write, pos_c,
            jnp.maximum(new_written, _gather_flat(st.s_written, pos_c)),
        )
        # the interpreter's Storage.__getitem__ records *every* read in
        # keys_get; track whether this slot was read before/after its
        # first write so materialize can replay the reads
        do_sread = ok & is_sload
        prior_written = _gather_flat(st.s_written, pos_c)
        rd_bit = jnp.where(prior_written > 0, 2, 1)
        sr = _scatter_flat(
            st.s_read, do_sread, pos_c,
            rd_bit | _gather_flat(st.s_read, pos_c),
        )
        sc = jnp.where(do_write & ~s_found, st.scount + 1, st.scount)
        return sk, skd, swst, sv, ssd, swr, sr, sc, sload_v

    # provisional id for this step's deferred record (used by storage
    # cache insertion and the result sid select)
    prov_id = -(lanes * d_recs + jnp.clip(st.dlog_count, 0, d_recs - 1)
                + 1)

    (skeys2, skey_sid2, s_wstep2, svals2, sval_sid2, s_written2,
     s_read2, scount2, sload_r) = lax.cond(
        jnp.any(ok & (is_sload | is_sstore)),
        _storage_block,
        lambda: (st.skeys, st.skey_sid, st.s_wstep, st.svals,
                 st.sval_sid, st.s_written, st.s_read, st.scount,
                 zero_w),
    )
    s_mode2 = jnp.where(ok & mode_on_now, 1, st.s_mode)

    # ---- calldata execution (concrete path) -------------------------------
    def _calldata_block():
        cd_idx = cd_off[:, None] + jnp.arange(32)[None, :]
        cd_valid = (cd_idx < st.cd_size[:, None]) & (cd_idx < cd_bytes)
        cd_read = jnp.take_along_axis(
            st.calldata, jnp.clip(cd_idx, 0, cd_bytes - 1), axis=1
        )
        return bytes_be_to_word(jnp.where(cd_valid, cd_read, 0))

    cdl_r = lax.cond(
        jnp.any(ok & is_cdl & ~cd_symbolic),
        _calldata_block,
        lambda: zero_w,
    )

    # ---- env / misc results ----------------------------------------------
    env_idx = jnp.asarray(ENV_TABLE)[op]
    env_r = _onehot_gather(st.env, jnp.clip(env_idx, 0, N_ENV - 1))
    env_sid_r = _gather_flat(st.env_sid, jnp.clip(env_idx, 0, N_ENV - 1))
    pc_r = bv256.from_u32(st.pc.astype(jnp.uint32)) \
        if seg_base is None \
        else bv256.from_u32((st.pc - seg_base).astype(jnp.uint32))
    # GAS pushes mstate.gas_limit (host parity: gas_ in
    # laser/instructions.py) — the same value the GASLIMIT env slot is
    # seeded with, NOT the device's oog budget (which is reduced by the
    # seed state's gas already used)
    gl_slot = ENV_SLOTS["GASLIMIT"]
    gas_r = st.env[:, gl_slot, :]
    cds_r = bv256.from_u32(st.cd_size.astype(jnp.uint32))
    codesize_r = bv256.from_u32(
        jnp.full((n,), code.size, jnp.uint32)) if seg_base is None \
        else bv256.from_u32(seg_size.astype(jnp.uint32))
    push_r = code.push_value[pc_c]
    dup_r = _peek(st.stack, st.sp, dup_n)
    dup_sid = _peek_sid(st.ssid, st.sp, dup_n)

    # ---- result select ----------------------------------------------------
    cases = (
        zero_w, add_r, mul_r, sub_r, div_r, sdiv_r, mod_r, smod_r,
        addmod_r, mulmod_r, exp_r, sext_r, lt_r, gt_r, slt_r, sgt_r,
        eq_r, iszero_r, and_r, or_r, xor_r, not_r, byte_r, shl_r,
        shr_r, sar_r, mload_r, sload_r, pc_r, msize_r, gas_r, cdl_r,
        cds_r, codesize_r, env_r, push_r, dup_r,
    )
    assert len(cases) == len(RESULT_CLASSES)
    which = jnp.broadcast_to(
        jnp.asarray(RESULT_CLASS_TABLE)[op][:, None], (n, bv256.NLIMBS)
    )
    result = lax.select_n(which, *cases)
    result = jnp.where(defer[:, None], 0, result)

    # result sid: deferred -> provisional; else op-specific symbolic
    # passthroughs; else 0 (concrete)
    result_sid = jnp.where(defer, prov_id, 0)
    result_sid = jnp.where(
        ~defer & (jnp.asarray(RESULT_CLASS_TABLE)[op] == RESULT_CLASS_ID["ENV"]),
        env_sid_r, result_sid)
    result_sid = jnp.where(
        ~defer & (op == _OP["CALLDATASIZE"]), st.cd_size_sid, result_sid)
    result_sid = jnp.where(
        ~defer & (op == _OP["GAS"]), st.env_sid[:, gl_slot], result_sid)
    result_sid = jnp.where(~defer & is_dup, dup_sid, result_sid)
    result_sid = jnp.where(
        ~defer & is_mload, mload_sym_sid, result_sid)
    result_sid = jnp.where(
        ~defer & is_sload & s_found, sload_hit_sid, result_sid)

    # ---- stack updates ----------------------------------------------------
    new_sp = st.sp - npop + npush
    do_push = ok & (npush == 1)
    push_idx = jnp.clip(new_sp - 1, 0, depth_cap - 1)
    stack = _scatter_word(st.stack, do_push, push_idx, result)
    ssid = _scatter_flat(st.ssid, do_push, push_idx, result_sid)

    do_swap = ok & is_swap
    top_idx = jnp.clip(st.sp - 1, 0, depth_cap - 1)
    swap_idx = jnp.clip(st.sp - 1 - swap_n, 0, depth_cap - 1)
    swap_val = _peek(st.stack, st.sp, swap_n + 1)
    swap_sid = _peek_sid(st.ssid, st.sp, swap_n + 1)
    stack = _scatter_word(stack, do_swap, top_idx, swap_val)
    stack = _scatter_word(stack, do_swap, swap_idx, a)
    ssid = _scatter_flat(ssid, do_swap, top_idx, swap_sid)
    ssid = _scatter_flat(ssid, do_swap, swap_idx, sid_a)

    # ---- deferred-record append (indexed row scatter: a dense one-hot
    # select would rewrite the whole (N,R,3,8) log plane every step) ------
    # record-arg overrides: SHA3 records carry the input WORDS (not the
    # popped offset/length) plus the length in slot 2; mode SLOADs are
    # re-tagged REC_SLOAD_RW (dedup-exempt: resolution depends on the
    # lane's write mirror)
    rec_op = jnp.where(sload_rw, jnp.int32(REC_SLOAD_RW), op)
    rec_sid0 = jnp.where(sha3_defer, s3_sid0, sid_a)
    rec_sid1 = jnp.where(sha3_defer,
                         jnp.where(sha3_two, s3_sid1, 0), sid_b)
    rec_sid2 = jnp.where(sha3_defer, 0, sid_c)
    rec_val0 = jnp.where(sha3_defer[:, None], s3_val0, a)
    rec_val1 = jnp.where(
        sha3_defer[:, None],
        jnp.where((sha3_two & (s3_sid1 == 0))[:, None], s3_val1, 0), b)
    # SHA3 meta word: [length, word0 kinds lo/hi, word1 kinds lo/hi]
    # in the first five u32 limbs (limbs are LSB-first)
    sha3_meta = jnp.stack(
        [sha3_len.astype(jnp.uint32), s3_k0lo, s3_k0hi,
         jnp.where(sha3_two, s3_k1lo, 0),
         jnp.where(sha3_two, s3_k1hi, 0),
         jnp.zeros((n,), jnp.uint32), jnp.zeros((n,), jnp.uint32),
         jnp.zeros((n,), jnp.uint32)], axis=-1)
    rec_val2 = jnp.where(sha3_defer[:, None], sha3_meta, c)

    def _dlog_append():
        pos = jnp.where(logrec, jnp.clip(st.dlog_count, 0, d_recs - 1),
                        d_recs)  # drop for non-logging lanes
        dop = st.dlog_op.at[lanes, pos].set(rec_op, mode="drop")
        dpc = st.dlog_pc.at[lanes, pos].set(st.pc, mode="drop")
        dstep = st.dlog_step.at[lanes, pos].set(
            jnp.full((n,), st.step_no, jnp.int32), mode="drop")
        dfen = st.dlog_fentry.at[lanes, pos].set(st.fentry, mode="drop")
        sids = jnp.stack([rec_sid0, rec_sid1, rec_sid2], axis=-1)
        vals = jnp.stack([rec_val0, rec_val1, rec_val2], axis=1)
        dsid = st.dlog_sid.at[lanes, pos].set(sids, mode="drop")
        dval = st.dlog_val.at[lanes, pos].set(vals, mode="drop")
        dcount = jnp.where(logrec, st.dlog_count + 1, st.dlog_count)
        return dop, dpc, dstep, dfen, dsid, dval, dcount

    (dlog_op2, dlog_pc2, dlog_step2, dlog_fentry2, dlog_sid2, dlog_val2,
     dlog_count2) = lax.cond(
        jnp.any(logrec),
        _dlog_append,
        lambda: (st.dlog_op, st.dlog_pc, st.dlog_step, st.dlog_fentry,
                 st.dlog_sid, st.dlog_val, st.dlog_count),
    )

    # ---- control flow -----------------------------------------------------
    next_pc = code.next_pc[pc_c]
    new_pc = next_pc
    new_pc = jnp.where(is_jump, dest_eff, new_pc)
    new_pc = jnp.where(is_jumpi & ~sym_b & jumpi_taken_conc, dest_eff,
                       new_pc)
    # symbolic JUMPI: parent takes the jump; the forked child (below)
    # takes the fall-through
    new_pc = jnp.where(fork_can, dest_eff, new_pc)

    new_depth = st.depth + (ok & is_jumpi).astype(jnp.int32)

    # function-entry tracking: jumps landing on a selector-dispatch
    # target update the lane's active function (the fall-through fork
    # child keeps the old value — restored in _do_forks)
    jumped = ok & (
        is_jump | (is_jumpi & ~sym_b & jumpi_taken_conc) | fork_can
    )
    dest_c2 = jnp.clip(dest_eff, 0, code.size)
    new_fentry = jnp.where(
        jumped & code.is_func_entry[dest_c2], dest, st.fentry
    )

    # ---- gas / status / bookkeeping ---------------------------------------
    min_gas = jnp.where(ok, st.min_gas + gmin, st.min_gas)
    max_gas = jnp.where(ok, st.max_gas + gmax, st.max_gas)
    status = jnp.where(running & park, Status.NEEDS_HOST, st.status)

    out = st._replace(
        pc=jnp.where(ok, new_pc, st.pc),
        sp=jnp.where(ok, new_sp, st.sp),
        depth=new_depth,
        fentry=new_fentry,
        last_jump=jnp.where(ok & is_jump, st.pc, st.last_jump),
        stack=stack,
        ssid=ssid,
        memory=memory,
        mkind=mkind2,
        msize=msize2,
        mlog_off=mlog_off2,
        mlog_len=mlog_len2,
        mlog_sid=mlog_sid2,
        mlog_count=mlog_count2,
        skeys=skeys2,
        skey_sid=skey_sid2,
        s_wstep=s_wstep2,
        s_mode=s_mode2,
        svals=svals2,
        sval_sid=sval_sid2,
        s_written=s_written2,
        s_read=s_read2,
        scount=scount2,
        calldata=st.calldata,
        min_gas=min_gas,
        max_gas=max_gas,
        status=status,
        steps=st.steps + ok.astype(jnp.int32),
        dlog_op=dlog_op2,
        dlog_pc=dlog_pc2,
        dlog_step=dlog_step2,
        dlog_fentry=dlog_fentry2,
        dlog_sid=dlog_sid2,
        dlog_val=dlog_val2,
        dlog_count=dlog_count2,
        step_no=st.step_no + 1,
    )

    # ---- forks ------------------------------------------------------------
    def _do_forks(s: SymLaneState) -> SymLaneState:
        maxf = min(MAX_FORKS_PER_STEP, n)
        fslot = jnp.arange(maxf)
        # rows of forking parents, scattered by fork order
        parent_rows = jnp.full((maxf,), n, jnp.int32)
        parent_rows = parent_rows.at[
            jnp.where(fork_can, forder, maxf)
        ].set(jnp.where(fork_can, lanes, n).astype(jnp.int32),
              mode="drop")
        nf = jnp.sum(fork_can.astype(jnp.int32))
        valid = fslot < nf
        # pop child slots from the free stack top
        child_idx = jnp.clip(s.free_count - 1 - fslot, 0, n - 1)
        child_rows = jnp.where(valid, s.free_slots[child_idx], n)
        parent_c = jnp.clip(parent_rows, 0, n - 1)

        # fields whose leading axis is NOT the lane axis (fork/free-slot
        # bookkeeping) must not be row-copied
        no_copy = {"flog_parent", "flog_child", "flog_step", "flog_pc",
                   "flog_sid", "flog_gmin", "flog_gmax", "flog_fentry",
                   "flog_dest", "flog_count", "free_slots",
                   "free_count", "step_no"}

        def copy_rows(name, x):
            if name in no_copy or x.ndim == 0 or x.shape[0] != n:
                return x
            return x.at[child_rows].set(x[parent_c], mode="drop")

        s2 = SymLaneState(
            **{f: copy_rows(f, getattr(s, f)) for f in s._fields}
        )
        # child diverges: fall-through pc (negated condition side); it
        # did not take the jump, so it keeps the pre-step function entry
        fall_pc = next_pc[parent_c]
        frow = jnp.where(valid, s.flog_count + fslot, n)
        s2 = s2._replace(
            pc=s2.pc.at[child_rows].set(fall_pc, mode="drop"),
            fentry=s2.fentry.at[child_rows].set(
                st.fentry[parent_c], mode="drop"),
            # the child minted no deferred records of its own
            dlog_count=s2.dlog_count.at[child_rows].set(0, mode="drop"),
            flog_parent=s2.flog_parent.at[frow].set(
                parent_rows, mode="drop"),
            flog_child=s2.flog_child.at[frow].set(
                child_rows, mode="drop"),
            flog_step=s2.flog_step.at[frow].set(
                jnp.full((maxf,), st.step_no, jnp.int32), mode="drop"),
            flog_pc=s2.flog_pc.at[frow].set(
                st.pc[parent_c], mode="drop"),
            flog_sid=s2.flog_sid.at[frow].set(
                sid_b[parent_c], mode="drop"),
            flog_gmin=s2.flog_gmin.at[frow].set(
                st.min_gas[parent_c], mode="drop"),
            flog_gmax=s2.flog_gmax.at[frow].set(
                st.max_gas[parent_c], mode="drop"),
            flog_fentry=s2.flog_fentry.at[frow].set(
                st.fentry[parent_c], mode="drop"),
            flog_dest=s2.flog_dest.at[frow].set(
                dest[parent_c], mode="drop"),
            flog_count=s.flog_count + nf,
            free_count=s.free_count - nf,
        )
        return s2

    out = lax.cond(jnp.any(fork_can), _do_forks, lambda s: s, out)
    return out


def sym_run(code: CompiledCode, st: SymLaneState, max_steps: int,
            exec_table: jnp.ndarray = None,
            taint_table: jnp.ndarray = None,
            visited: jnp.ndarray = None):
    """Run up to max_steps (one sync window; exits early once no lane
    is RUNNING). max_steps MAY exceed the deferred-log capacity: a lane
    that would mint a record with its log full parks (dlog_full ->
    NEEDS_HOST) before appending — degraded to a host round trip, never
    wrong. Records are only minted for symbolic/deferred work, so the
    default window (lane_engine.DEFAULT_WINDOW) rarely hits the cap.

    `visited` is an optional per-byte-address coverage bitmap (device
    resident, accumulated across windows): each step marks the pc of
    every RUNNING lane before it executes — the device twin of the
    interpreter's execute_state coverage hook.  Returns (state,
    visited); visited is None when not requested."""
    if exec_table is None:
        exec_table = SYM_EXECUTABLE
    if taint_table is None:
        taint_table = np.zeros(256, bool)

    if visited is None:

        def cond(carry):
            s, i = carry
            return (i < max_steps) & jnp.any(s.status == Status.RUNNING)

        def body(carry):
            s, i = carry
            return sym_step(code, s, exec_table, taint_table), i + 1

        final, _ = lax.while_loop(cond, body, (st, jnp.int32(0)))
        return final, None

    def cond_v(carry):
        s, i, _ = carry
        return (i < max_steps) & jnp.any(s.status == Status.RUNNING)

    def body_v(carry):
        s, i, vis = carry
        mark = jnp.where(s.status == Status.RUNNING, s.pc,
                         vis.shape[0])
        vis = vis.at[mark].set(True, mode="drop")
        return sym_step(code, s, exec_table, taint_table), i + 1, vis

    final, _, visited = lax.while_loop(
        cond_v, body_v, (st, jnp.int32(0), visited))
    return final, visited


sym_run_jit = jax.jit(sym_run, static_argnums=(2,), donate_argnums=(1,))
