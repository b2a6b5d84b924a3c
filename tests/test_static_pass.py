"""Static bytecode pre-analysis (analysis/static_pass/,
docs/static_pass.md).

Covers:

* jump-table resolution units: direct push-jump, the cross-block
  return-address pattern, value-set joins, and unresolved (data-
  dependent) dests;
* a randomized structured-CFG property: generated codes with known
  ground-truth edges must resolve their jump table exactly, and the
  per-PC reach mask must equal the mask computed independently over
  the known graph (soundness AND precision on fully-resolvable code);
* loop-head / cycle detection on the bounded-loops loop shape;
* code-hash memo hit + sidecar roundtrip;
* end-to-end retire soundness: the rigged detector-dead-tail contract
  analyzed with MTPU_STATIC on vs off yields identical issues while
  `statically_retired` lanes are provably nonzero (lane seam), i.e.
  no issue ever came from any retired lane's subtree.
"""

import pickle
import random

import numpy as np
import pytest

from mythril_tpu.analysis import static_pass
from mythril_tpu.analysis.static_pass import memo as static_memo
from mythril_tpu.analysis.static_pass.reach import (
    ALL_BITS,
    OP_BITS,
    TERMINATOR_BIT,
)
from mythril_tpu.support.opcodes import ADDRESS, OPCODES

OP = {name: data[ADDRESS] for name, data in OPCODES.items()}


def push(v, n=1):
    return bytes([0x5F + n]) + v.to_bytes(n, "big")


def _bit(op):
    return np.uint32(1 << OP_BITS[op])


# -- jump-table resolution units --------------------------------------------


class TestJumpResolution:
    def test_direct_push_jump(self):
        code = bytes([*push(4), OP["JUMP"], OP["INVALID"],
                      OP["JUMPDEST"], OP["STOP"]])
        info = static_pass.analyze(code)
        assert info.jump_table == {2: (4,)}
        assert info.jumps_resolved == 1 and info.complete

    def test_cross_block_return_address(self):
        # caller pushes ret + func, func jumps back through the stack
        code = bytes([*push(8), *push(6), OP["JUMP"], OP["STOP"],
                      OP["JUMPDEST"], OP["JUMP"],
                      OP["JUMPDEST"], OP["STOP"]])
        info = static_pass.analyze(code)
        assert info.jump_table[4] == (6,)
        assert info.jump_table[7] == (8,)  # through the VSA stack
        assert info.complete

    def test_value_set_join_two_callers(self):
        # two call sites push different return addresses; the callee's
        # JUMP resolves to BOTH
        c = bytearray()
        c += push(0, 1) + bytes([OP["CALLDATALOAD"]])
        j = len(c)
        c += push(0, 2) + bytes([OP["JUMPI"]])
        # caller A: push retA, jump func
        c += push(0, 2)  # retA placeholder
        ra_patch = len(c) - 2
        c += push(0, 2) + bytes([OP["JUMP"]])
        fa_patch = len(c) - 3
        # caller B (JUMPI target)
        b = len(c)
        c[j + 1:j + 3] = b.to_bytes(2, "big")
        c += bytes([OP["JUMPDEST"]])
        c += push(0, 2)  # retB placeholder
        rb_patch = len(c) - 2
        c += push(0, 2) + bytes([OP["JUMP"]])
        fb_patch = len(c) - 3
        # func
        func = len(c)
        c += bytes([OP["JUMPDEST"], OP["JUMP"]])
        func_jump = func + 1
        # returns
        ra = len(c)
        c += bytes([OP["JUMPDEST"], OP["STOP"]])
        rb = len(c)
        c += bytes([OP["JUMPDEST"], OP["STOP"]])
        c[ra_patch:ra_patch + 2] = ra.to_bytes(2, "big")
        c[rb_patch:rb_patch + 2] = rb.to_bytes(2, "big")
        c[fa_patch:fa_patch + 2] = func.to_bytes(2, "big")
        c[fb_patch:fb_patch + 2] = func.to_bytes(2, "big")
        info = static_pass.analyze(bytes(c))
        assert info.jump_table[func_jump] == (ra, rb)
        assert info.complete

    def test_data_dependent_dest_unresolved(self):
        code = bytes([*push(0), OP["CALLDATALOAD"], OP["JUMP"],
                      OP["JUMPDEST"], OP["STOP"]])
        info = static_pass.analyze(code)
        assert info.jump_table == {3: None}
        assert info.jumps_resolved == 0 and not info.complete

    def test_push_data_jumpdest_rejected(self):
        code = bytes([0x61, 0x5B, 0x00, *push(1), OP["JUMP"]])
        info = static_pass.analyze(code)
        assert info.jump_table == {5: ()}  # resolved, but illegal dest


# -- randomized structured-CFG property -------------------------------------


_ANCHOR_POOL = (
    ("TIMESTAMP", bytes([OP["TIMESTAMP"], OP["POP"]])),
    ("ORIGIN", bytes([OP["ORIGIN"], OP["POP"]])),
    ("SSTORE", push(1) + push(0) + bytes([OP["SSTORE"]])),
    ("ADD", push(1) + push(2) + bytes([OP["ADD"], OP["POP"]])),
    (None, push(7) + bytes([OP["POP"]])),  # anchor-free filler
)


def _build_random_cfg(rng, n_segments=6):
    """Segments of JUMPDEST + straight-line body + terminator with
    KNOWN edges; returns (code, seg_starts, edges, seg_ops,
    terminators)."""
    bodies = [[rng.choice(_ANCHOR_POOL)
               for _ in range(rng.randrange(0, 3))]
              for _ in range(n_segments)]
    kinds = [rng.choice(("jump", "jumpi", "stop", "revert"))
             for _ in range(n_segments)]
    targets = [(rng.randrange(n_segments),
                rng.randrange(n_segments))
               for _ in range(n_segments)]
    # two passes: layout with placeholders, then patch (segment
    # addresses depend on body sizes only, so one relayout suffices)
    starts, code = [], bytearray()
    for i in range(n_segments):
        starts.append(len(code))
        code += bytes([OP["JUMPDEST"]])
        for _, chunk in bodies[i]:
            code += chunk
        if kinds[i] == "jump":
            code += push(0, 2) + bytes([OP["JUMP"]])
        elif kinds[i] == "jumpi":
            code += push(0, 1) + bytes([OP["CALLDATALOAD"]])
            code += push(0, 2) + bytes([OP["JUMPI"]])
            code += bytes([OP["STOP"]]) if i == n_segments - 1 \
                else b""
        elif kinds[i] == "stop":
            code += bytes([OP["STOP"]])
        else:
            code += push(0) + push(0) + bytes([OP["REVERT"]])
    code += bytes([OP["STOP"]])
    # patch jump targets + record ground-truth edges
    edges = {i: set() for i in range(n_segments)}
    pos = 0
    for i in range(n_segments):
        pos = starts[i] + 1
        for _, chunk in bodies[i]:
            pos += len(chunk)
        if kinds[i] == "jump":
            t = starts[targets[i][0]]
            code[pos + 1:pos + 3] = t.to_bytes(2, "big")
            edges[i].add(targets[i][0])
        elif kinds[i] == "jumpi":
            t = starts[targets[i][0]]
            patch = pos + len(push(0, 1)) + 1
            code[patch + 1:patch + 3] = t.to_bytes(2, "big")
            edges[i].add(targets[i][0])
            if i + 1 < n_segments:
                edges[i].add(i + 1)  # fallthrough into next segment
    return bytes(code), starts, edges, bodies, kinds


def _ground_truth_masks(starts, edges, bodies, kinds, n):
    gen = []
    for i in range(n):
        g = np.uint32(0)
        for name, _ in bodies[i]:
            if name:
                g |= _bit(name)
        if kinds[i] == "jump":
            g |= _bit("JUMP")
        elif kinds[i] == "jumpi":
            g |= _bit("JUMPI")
            if i == n - 1:
                g |= _bit("STOP") | TERMINATOR_BIT
        elif kinds[i] == "stop":
            g |= _bit("STOP") | TERMINATOR_BIT
        else:
            g |= _bit("REVERT")
        gen.append(g)
    masks = list(gen)
    changed = True
    while changed:
        changed = False
        for i in range(n):
            m = gen[i]
            for s in edges[i]:
                m |= masks[s]
            if m != masks[i]:
                masks[i] = m
                changed = True
    return masks


@pytest.mark.parametrize("seed", [3, 17, 99, 1234])
def test_randomized_cfg_mask_matches_ground_truth(seed):
    rng = random.Random(seed)
    code, starts, edges, bodies, kinds = _build_random_cfg(rng)
    info = static_pass.analyze(code)
    assert info.complete, "fully push-jump code must fully resolve"
    gt = _ground_truth_masks(starts, edges, bodies, kinds, len(starts))
    for i, start in enumerate(starts):
        got = np.uint32(info.reach_mask[start])
        assert got == gt[i], (
            f"seed {seed} segment {i}@{start}: mask {got:#x} != "
            f"ground truth {gt[i]:#x}")


def test_non_instruction_offsets_are_all_bits():
    code = bytes([0x61, 0x5B, 0x00, OP["STOP"]])  # PUSH2 data at 1, 2
    info = static_pass.analyze(code)
    assert info.reach_mask[1] == ALL_BITS
    assert info.reach_mask[2] == ALL_BITS


# -- loop heads / cycle pcs --------------------------------------------------


def _loop_program(iterations=10):
    code = bytearray()
    code += push(iterations, 2)
    loop = len(code)
    code += bytes([OP["JUMPDEST"], OP["DUP1"], OP["ISZERO"]])
    code += push(0, 2) + bytes([OP["JUMPI"]])
    patch = len(code) - 3
    code += push(1) + bytes([OP["SWAP1"], OP["SUB"]])
    code += push(loop, 2) + bytes([OP["JUMP"]])
    done = len(code)
    code += bytes([OP["JUMPDEST"], OP["POP"]])
    code += push(1) + push(0) + bytes([OP["SSTORE"], OP["STOP"]])
    code[patch:patch + 2] = done.to_bytes(2, "big")
    return bytes(code), loop, done


class TestLoops:
    def test_loop_head_and_cycle_pcs(self):
        code, loop, done = _loop_program()
        info = static_pass.analyze(code)
        assert loop in info.loop_heads
        assert loop in info.cycle_pcs
        assert done not in info.cycle_pcs  # exit block: no cycle
        assert info.complete

    def test_straight_line_has_no_cycles(self):
        code = push(1) + push(2) + bytes([OP["ADD"], OP["POP"],
                                          OP["STOP"]])
        info = static_pass.analyze(code)
        assert info.cycle_pcs == frozenset()
        assert info.loop_heads == frozenset()

    def test_bounded_loops_strategy_unaffected(self):
        """The cycle-pcs filter must leave the bound's cut intact on
        the loop fixture shape (the loop head IS a cycle pc)."""
        from mythril_tpu.disassembler.disassembly import Disassembly

        code, loop, done = _loop_program(50)
        dis = Disassembly(code.hex())
        pcs = static_pass.cycle_pcs_for(dis)
        assert pcs is not None and loop in pcs


# -- memo + sidecar roundtrip ------------------------------------------------


class TestMemo:
    def test_memo_hit_returns_same_object(self):
        code, *_ = _loop_program(7)
        a = static_pass.info_for(code)
        b = static_pass.info_for(code)
        assert a is not None and a is b

    def test_counters_bump_once_per_fresh_analysis(self):
        from mythril_tpu.smt.solver.solver_statistics import (
            SolverStatistics,
        )

        code, *_ = _loop_program(11)
        static_memo.clear()
        ss = SolverStatistics()
        b0 = ss.static_blocks
        static_pass.info_for(code)
        static_pass.info_for(code)
        assert ss.static_blocks - b0 == static_pass.analyze(
            code).n_blocks  # bumped once, not twice

    def test_export_import_roundtrip(self, tmp_path):
        from mythril_tpu.support.checkpoint import (
            load_static_sidecar,
            save_static_sidecar,
        )

        code, loop, _ = _loop_program(9)
        info = static_pass.info_for(code)
        assert info is not None
        entries = static_memo.export_entries([info.code_hash])
        assert entries and entries[0] is info
        side = tmp_path / "offer_1.static"
        assert save_static_sidecar(side, entries)
        loaded = load_static_sidecar(side)
        assert len(loaded) == 1
        static_memo.clear()
        assert static_memo.import_entries(loaded) == 1
        again = static_pass.info_for(code)
        assert again.code_hash == info.code_hash
        assert np.array_equal(again.reach_mask, info.reach_mask)
        assert again.jump_table == info.jump_table
        assert again.cycle_pcs == info.cycle_pcs

    def test_entries_pickle_without_terms(self):
        code, *_ = _loop_program(5)
        info = static_pass.analyze(code)
        blob = pickle.dumps(info)  # plain pickle: no term tables
        back = pickle.loads(blob)
        assert back.code_hash == info.code_hash

    def test_off_switch(self):
        code, *_ = _loop_program(6)
        static_pass.FORCE = False
        try:
            assert static_pass.info_for(code) is None
            assert static_pass.cycle_pcs_for(
                type("C", (), {"bytecode": code.hex()})()) is None
        finally:
            static_pass.FORCE = None


# -- active-mask derivation --------------------------------------------------


def test_active_mask_for_modules():
    from mythril_tpu.analysis.module.loader import ModuleLoader

    mods = {type(m).__name__: m
            for m in ModuleLoader().get_detection_modules()}
    mask = static_pass.active_mask_for_modules(
        [mods["AccidentallyKillable"], mods["ArbitraryStorage"]])
    assert mask == _bit("SELFDESTRUCT") | _bit("SSTORE")
    # a module with an unknown hook universe pins ALL_BITS
    class Weird:
        pre_hooks = ["NOT_AN_OPCODE"]
        post_hooks = []
    assert static_pass.active_mask_for_modules([Weird()]) == ALL_BITS


# -- end-to-end retire soundness (lane seam) ---------------------------------


def build_static_dead_contract(k=5, tail=160):
    """k symbolic forks, one SELFDESTRUCT branch (the reachable issue),
    a final concrete SSTORE, then a long pure-arithmetic tail to STOP —
    every lane past the SSTORE is statically dead for a
    {AccidentallyKillable, ArbitraryStorage} run."""
    c = bytearray()
    for i in range(k):
        c += push(i) + bytes([OP["CALLDATALOAD"]])
        c += push(1) + bytes([OP["AND"]])
        j = len(c)
        c += push(0, 2) + bytes([OP["JUMPI"]])
        c += bytes([OP["JUMPDEST"]])
        jf = len(c)
        c += push(0, 2) + bytes([OP["JUMP"]])
        t = len(c)
        c[j + 1:j + 3] = t.to_bytes(2, "big")
        c += bytes([OP["JUMPDEST"]])
        jt = len(c)
        c += push(0, 2) + bytes([OP["JUMP"]])
        r = len(c)
        c[jf + 1:jf + 3] = r.to_bytes(2, "big")
        c[jt + 1:jt + 3] = r.to_bytes(2, "big")
        c += bytes([OP["JUMPDEST"]])
    # SELFDESTRUCT branch: calldata word 31 == 0xdead
    c += push(31) + bytes([OP["CALLDATALOAD"]])
    c += push(0xDEAD, 2) + bytes([OP["EQ"]])
    j = len(c)
    c += push(0, 2) + bytes([OP["JUMPI"]])
    # fallthrough: last detector site, then the detector-dead tail
    c += push(1) + push(0) + bytes([OP["SSTORE"]])
    c += push(5)
    for _ in range(tail):
        c += push(3) + bytes([OP["MUL"]]) + push(7) + bytes([OP["ADD"]])
    c += bytes([OP["POP"], OP["STOP"]])
    d = len(c)
    c[j + 1:j + 3] = d.to_bytes(2, "big")
    c += bytes([OP["JUMPDEST"], OP["CALLER"], OP["SELFDESTRUCT"]])
    return bytes(c)


MODULES = ["AccidentallyKillable", "ArbitraryStorage"]


def _analyze(code, static_on, tpu_lanes, tx_count):
    from mythril_tpu.orchestration.mythril_analyzer import (
        MythrilAnalyzer, reset_analysis_state,
    )
    from mythril_tpu.orchestration.mythril_disassembler import (
        MythrilDisassembler,
    )
    from mythril_tpu.smt.solver.solver_statistics import SolverStatistics
    from mythril_tpu.support.analysis_args import make_cmd_args

    static_pass.FORCE = static_on
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
        report = analyzer.fire_lasers(modules=list(MODULES),
                                      transaction_count=tx_count)
        c1 = ss.batch_counters()
        return (sorted((i.swc_id, i.address, i.title)
                       for i in report.issues.values()),
                {k: c1[k] - c0.get(k, 0)
                 for k in ("static_retired_lanes",
                           "static_jumps_resolved", "static_blocks",
                           "batch_queries")})
    finally:
        static_pass.FORCE = None


class TestEndToEndRetireSoundness:
    def test_lane_window_boundary_retire(self):
        """The tentpole gate: identical issues with the pass on vs
        MTPU_STATIC=0 while lanes provably retired statically — so no
        issue can ever have come from a retired lane's subtree."""
        pytest.importorskip("jax")
        from mythril_tpu.laser import lane_engine

        code = build_static_dead_contract(k=5, tail=160)
        static_memo.clear()
        lane_engine.PATH_HISTORY[code] = 64
        lane_engine.FORCE_WIDTH = 64
        old_window = lane_engine.DEFAULT_WINDOW
        lane_engine.DEFAULT_WINDOW = 32
        try:
            lane_engine.warm_variant(64, len(code), {}, 32, 8192,
                                     seed_bucket=16)
            issues_off, d_off = _analyze(code, False, 64, 1)
            issues_on, d_on = _analyze(code, True, 64, 1)
        finally:
            lane_engine.FORCE_WIDTH = None
            lane_engine.DEFAULT_WINDOW = old_window
        assert issues_on == issues_off
        assert issues_on, "rig must produce a reachable issue"
        assert d_on["static_retired_lanes"] > 0
        assert d_on["static_jumps_resolved"] > 0
        assert d_off["static_retired_lanes"] == 0  # off really off
        assert d_off["static_blocks"] == 0

    def test_randomized_host_identity(self):
        """Host-path identity over random fork/tail shapes (exercises
        the bounded-loops filter and the pruner fast path; the host
        seam retires only via the sweep, absent here, so this is a
        pure no-behavior-change gate)."""
        rng = random.Random(11)
        for _ in range(2):
            code = build_static_dead_contract(
                k=rng.randrange(1, 3), tail=rng.randrange(4, 12))
            issues_off, _ = _analyze(code, False, 0, 2)
            issues_on, _ = _analyze(code, True, 0, 2)
            assert issues_on == issues_off
