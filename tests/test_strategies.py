"""Search strategies: worklist order, beam pruning, bounded loops (this
build's analog of the reference's tests/laser/strategy/ suite:
test_beam.py, test_loop_bound.py)."""

from tests.harness import asm, push, run_concrete

from mythril_tpu.laser.strategy.basic import (
    BreadthFirstSearchStrategy,
    DepthFirstSearchStrategy,
)
from mythril_tpu.laser.strategy.beam import BeamSearch


class _FakeState:
    def __init__(self, depth, importance=None):
        class _M:
            pass

        self.mstate = _M()
        self.mstate.depth = depth
        self._importance = importance

    @property
    def annotations(self):
        return []

    def get_annotations(self, cls):
        return []


def test_dfs_pops_newest():
    wl = [_FakeState(1), _FakeState(2), _FakeState(3)]
    strat = DepthFirstSearchStrategy(wl, max_depth=10)
    assert next(strat).mstate.depth == 3


def test_bfs_pops_oldest():
    wl = [_FakeState(1), _FakeState(2), _FakeState(3)]
    strat = BreadthFirstSearchStrategy(wl, max_depth=10)
    assert next(strat).mstate.depth == 1


def test_max_depth_skips_deep_states():
    wl = [_FakeState(100), _FakeState(5)]
    strat = BreadthFirstSearchStrategy(wl, max_depth=10)
    # depth-100 state is skipped, depth-5 returned
    assert next(strat).mstate.depth == 5


def test_beam_width_prunes_low_importance():
    """Beam search keeps only the beam_width most important states per
    layer (importance = sum of SearchImportance annotations)."""

    class ImportanceAnnotation:
        def __init__(self, importance):
            self.search_importance = importance
            self.persist_to_world_state = False
            self.persist_over_calls = False

    class _State(_FakeState):
        def __init__(self, depth, importance):
            super().__init__(depth)
            self._ann = ImportanceAnnotation(importance)
            self._annotations = [self._ann]

        def get_annotations(self, cls):
            return []

    states = [_State(1, i) for i in (5, 1, 9, 3)]
    strat = BeamSearch(list(states), max_depth=10, beam_width=2)
    got = []
    try:
        while True:
            got.append(next(strat)._ann.search_importance)
    except StopIteration:
        pass
    # only the two most important states survive the beam
    assert sorted(got, reverse=True) == [9, 5]


def _loop_program(iterations: int) -> bytes:
    """for (i = iterations; i != 0; --i) {}; sstore(0, 1)"""
    code = bytearray()
    code += push(iterations, 2)                     # [i]
    loop = len(code)
    code += asm("JUMPDEST", "DUP1", "ISZERO")
    code += push(0, 2) + asm("JUMPI")
    patch = len(code) - 4  # the PUSH2 opcode; +1..+3 are its operands
    code += push(1, 1) + asm("SWAP1", "SUB")
    code += push(loop, 2) + asm("JUMP")
    done = len(code)
    code += asm("JUMPDEST", "POP")
    code += push(1, 1) + push(0, 1) + asm("SSTORE", "STOP")
    code[patch + 1 : patch + 3] = done.to_bytes(2, "big")
    return bytes(code)


def test_bounded_loops_cuts_concrete_loop():
    """With BoundedLoopsStrategy at bound N, a loop body at JUMPDEST is
    not re-entered more than ~N times (reference
    strategy/extensions/bounded_loops.py)."""
    from mythril_tpu.laser.strategy.extensions.bounded_loops import (
        BoundedLoopsStrategy,
    )
    from mythril_tpu.laser.svm import LaserEVM
    from mythril_tpu.laser.state.world_state import WorldState
    from mythril_tpu.laser.transaction.concolic import execute_message_call
    from mythril_tpu.disassembler.disassembly import Disassembly
    from mythril_tpu.smt import symbol_factory
    from tests.harness import ADDR

    code = _loop_program(100)

    executed = []

    def run(with_bound):
        laser = LaserEVM(requires_statespace=False, execution_timeout=60)
        if with_bound:
            laser.extend_strategy(BoundedLoopsStrategy, loop_bound=3)
        counter = {"n": 0}

        @laser.laser_hook("execute_state")
        def count(global_state):
            counter["n"] += 1

        world_state = WorldState()
        account = world_state.create_account(
            address=ADDR, concrete_storage=True)
        account.code = Disassembly(code.hex())
        laser.open_states = [world_state]
        execute_message_call(
            laser,
            callee_address=symbol_factory.BitVecVal(ADDR, 256),
            caller_address=symbol_factory.BitVecVal(0xACE, 256),
            origin_address=symbol_factory.BitVecVal(0xACE, 256),
            code=code.hex(),
            data=[],
            gas_limit=8000000,
            gas_price=1,
            value=0,
            track_gas=False,
        )
        return counter["n"]

    from mythril_tpu.support.support_args import args

    old_lanes = args.tpu_lanes
    args.tpu_lanes = 0  # host-only: the lane engine must not engage
    try:
        bounded = run(True)
        unbounded = run(False)
    finally:
        args.tpu_lanes = old_lanes
    assert unbounded > 500  # the full 100-iteration loop runs
    assert bounded < unbounded / 5  # the bound cuts it off early
