"""LASER engine plugin behavior (this build's analog of plugin-level
coverage the reference exercises implicitly): mutation pruner drops
clean end states, coverage plugin tracks executed instructions, call
depth limiter cuts deep call chains."""

from datetime import datetime

from mythril_tpu.disassembler.disassembly import Disassembly
from mythril_tpu.laser.plugin.plugins.call_depth_limiter import (
    CallDepthLimit,
)
from mythril_tpu.laser.plugin.plugins.coverage.coverage_plugin import (
    InstructionCoveragePlugin,
)
from mythril_tpu.laser.plugin.plugins.mutation_pruner import MutationPruner
from mythril_tpu.laser.state.world_state import WorldState
from mythril_tpu.laser.svm import LaserEVM
from mythril_tpu.laser.time_handler import time_handler
from mythril_tpu.laser.transaction.symbolic import execute_message_call
from mythril_tpu.smt import symbol_factory
from tests.harness import ADDR, asm, push


def _run_symbolic(code: bytes, plugins=()):
    laser = LaserEVM(requires_statespace=False, execution_timeout=60,
                     transaction_count=1)
    for plugin in plugins:
        plugin.initialize(laser)
    world_state = WorldState()
    account = world_state.create_account(
        address=ADDR, concrete_storage=True)
    account.code = Disassembly(code.hex())
    laser.open_states = [world_state]
    laser.time = datetime.now()
    time_handler.start_execution(60)
    execute_message_call(
        laser, callee_address=symbol_factory.BitVecVal(ADDR, 256))
    return laser


def test_mutation_pruner_drops_clean_end_states():
    """A non-payable-style path (callvalue constrained to 0) with no
    mutation yields no open state when the mutation pruner is loaded.
    (A bare STOP is kept: its unconstrained symbolic callvalue may be
    positive, which counts as a balance mutation — reference
    semantics.)"""
    # callvalue != 0 -> revert at 5; else STOP (clean, value-free path)
    code = bytes(
        asm("CALLVALUE") + push(5, 1) + asm("JUMPI", "STOP", "JUMPDEST")
        + push(0, 1) + push(0, 1) + asm("REVERT")
    )
    laser = _run_symbolic(code, plugins=[MutationPruner()])
    assert len(laser.open_states) == 0

    laser2 = _run_symbolic(code)  # without the pruner the state survives
    assert len(laser2.open_states) == 1

    # bare STOP: symbolic callvalue may be > 0 -> kept even with pruner
    laser3 = _run_symbolic(bytes(asm("STOP")),
                           plugins=[MutationPruner()])
    assert len(laser3.open_states) == 1


def test_mutation_pruner_keeps_sstore_states():
    code = bytes(push(1, 1) + push(0, 1) + asm("SSTORE", "STOP"))
    laser = _run_symbolic(code, plugins=[MutationPruner()])
    assert len(laser.open_states) == 1


def test_coverage_plugin_counts_instructions():
    code = bytes(push(1, 1) + push(0, 1) + asm("SSTORE", "STOP"))
    plugin = InstructionCoveragePlugin()
    _run_symbolic(code, plugins=[plugin])
    assert plugin.coverage, "no coverage recorded"
    total, covered = next(iter(plugin.coverage.values()))
    assert total > 0
    n_covered = (
        sum(covered) if isinstance(covered, (list, tuple)) else covered
    )
    assert n_covered > 0


def test_call_depth_limiter_cuts_recursion():
    """A self-recursive CALL chain is cut at the configured depth:
    a tighter limit must explore strictly fewer states."""
    program = (
        push(0, 1) + push(0, 1) + push(0, 1) + push(0, 1)
        + push(0, 1) + push(ADDR) + push(100000, 3)
        + asm("CALL", "STOP")
    )
    shallow = _run_symbolic(
        bytes(program), plugins=[CallDepthLimit(call_depth_limit=1)]
    )
    deep = _run_symbolic(
        bytes(program), plugins=[CallDepthLimit(call_depth_limit=3)]
    )
    assert shallow.total_states > 0
    assert shallow.total_states < deep.total_states


def _run_symbolic_lane(code: bytes, stop_hook=None, lanes=64):
    """_run_symbolic with the lane sweep engaged (CPU backend:
    break-even 1, so the wave dispatches). The execution budget also
    pays the first sweep's XLA:CPU compile, which a loaded machine
    stretches past a minute: a spent budget ends the analysis before
    any path reaches STOP."""
    from mythril_tpu.laser import lane_engine
    from mythril_tpu.support.support_args import args

    laser = LaserEVM(requires_statespace=False, execution_timeout=600,
                     transaction_count=1)
    if stop_hook is not None:
        laser.pre_hook("STOP")(stop_hook)
    world_state = WorldState()
    account = world_state.create_account(
        address=ADDR, concrete_storage=True)
    account.code = Disassembly(code.hex())
    laser.open_states = [world_state]
    laser.time = datetime.now()
    time_handler.start_execution(600)
    old_lanes = args.tpu_lanes
    args.tpu_lanes = lanes
    stats0 = dict(lane_engine.RUN_STATS_TOTAL)
    try:
        execute_message_call(
            laser, callee_address=symbol_factory.BitVecVal(ADDR, 256))
    finally:
        args.tpu_lanes = old_lanes
    seeded = lane_engine.RUN_STATS_TOTAL.get("seeded", 0) \
        - stats0.get("seeded", 0)
    return laser, seeded


def _fork_stop_code():
    """calldata-bit fork; both arms SSTORE then STOP (2 end states)."""
    return bytes(
        push(0, 1) + asm("CALLDATALOAD") + push(1, 1) + asm("AND")
        + push(15, 1) + asm("JUMPI")
        + push(1, 1) + push(0, 1) + asm("SSTORE", "STOP")
        + asm("JUMPDEST") + push(2, 1) + push(0, 1)
        + asm("SSTORE", "STOP")
    )


def test_fast_terminal_respects_detector_stop_hooks():
    """A detector-channel STOP pre-hook (essential) must fire once per
    terminal path even with the lane engine engaged: slim_stop must
    disable the transaction-end shortcut (regression: the shortcut
    once consulted only the instruction hook channel)."""
    fired = []

    def stop_hook(global_state):
        fired.append(global_state)
        # the hooks' view must include the rebuilt machine state (the
        # slim materialization would have emptied it)
        assert global_state.mstate.stack is not None

    laser, seeded = _run_symbolic_lane(_fork_stop_code(),
                                       stop_hook=stop_hook)
    assert seeded > 0, "lane sweep did not engage; test is vacuous"
    assert len(fired) == 2
    assert len(laser.open_states) == 2


def test_fast_terminal_open_state_parity():
    """Without STOP hooks the shortcut engages; open states must match
    the host run (count and storage writes)."""
    code = _fork_stop_code()
    lane, seeded = _run_symbolic_lane(code)
    assert seeded > 0, "lane sweep did not engage; test is vacuous"
    host = _run_symbolic(code)

    def canon(laser):
        out = []
        for ws in laser.open_states:
            acct = ws.accounts[ADDR]
            out.append(sorted(
                (k.value, v.value)
                for k, v in acct.storage.printable_storage.items()
            ))
        return sorted(out)

    assert canon(lane) == canon(host)
    assert len(lane.open_states) == len(host.open_states) == 2
