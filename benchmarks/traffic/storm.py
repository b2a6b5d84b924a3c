"""Storm traffic: seeded fork+SSTORE+SHA3 contracts explored by the
LASER engine in a closed loop, with no detectors. Every exploration is
of a contract the process has not seen, as every contract a user
submits is new to the program: its width policy (PATH_HISTORY, keyed
on the code) has learned nothing of it.

A contract (a seeded copy of bench.build_symbolic_contract) has k
levels. Level i reads one calldata bit (CALLDATALOAD at a seeded
offset, AND 1) and on the taken arm adds a seeded constant to an
accumulator and stores it at a seeded slot; the tail stores
keccak256(accumulator). So it has 2^k feasible paths. Every PUSH has a
fixed width, so the code length, the fork tree, and with them every
compiled shape, are the same for every contract.

Mix parameters (traffic/<mix>.json, "generator": "storm"):
  warmup_max     explorations set-up may make to reach one that
                 compiles nothing
  trace_seconds  how long a traced run profiles (run.py)
"""

import random
import time

from ..reference.path_storm import path_set
from .counters import device_errors

#: opcodes, from the Ethereum yellow paper
_PUSH1, _JUMPI, _JUMPDEST = 0x60, 0x57, 0x5B
_CALLDATALOAD, _AND, _ISZERO, _ADD = 0x35, 0x16, 0x15, 0x01
_DUP1, _SSTORE, _MSTORE, _SHA3, _STOP = 0x80, 0x55, 0x52, 0x20, 0x00
_ADDRESS = 0xDEADBEEF


def _push(value: int, width: int) -> bytes:
    return bytes([_PUSH1 + width - 1]) + value.to_bytes(width, "big")


def draw_contract(seed: int, shape: dict) -> dict:
    """The seeded constants of a storm contract of the given shape:
    distinct calldata offsets (independent bits, so every path is
    feasible), distinct storage slots other than the SHA3 slot (so the
    writes name the path), and nonzero ADD constants."""
    k = shape["k"]
    rng = random.Random(seed)
    widths = shape["push_bytes"]
    sha3_slot = shape["sha3_slot"]
    offsets = rng.sample(range(1 << (8 * widths["offset"])), k)
    slots = rng.sample([s for s in range(1 << (8 * widths["slot"]))
                        if s != sha3_slot], k)
    adds = [rng.randrange(1, 1 << (8 * widths["add"])) for _ in range(k)]
    return {"offsets": offsets, "slots": slots, "adds": adds,
            "sha3_slot": sha3_slot, "widths": widths}


def build_code(c: dict) -> bytes:
    w = c["widths"]
    code = bytearray(_push(0, 1))                            # [acc]
    for off, slot, add in zip(c["offsets"], c["slots"], c["adds"]):
        code += _push(off, w["offset"]) + bytes([_CALLDATALOAD])
        code += _push(1, 1) + bytes([_AND, _ISZERO])
        j = len(code)
        code += _push(0, 2) + bytes([_JUMPI])
        code += _push(add, w["add"]) + bytes([_ADD, _DUP1])
        code += _push(slot, w["slot"]) + bytes([_SSTORE])
        code[j + 1:j + 3] = len(code).to_bytes(2, "big")
        code += bytes([_JUMPDEST])
    code += _push(0, 1) + bytes([_MSTORE])
    code += _push(32, 1) + _push(0, 1) + bytes([_SHA3])
    code += _push(c["sha3_slot"], 1) + bytes([_SSTORE, _STOP])
    return bytes(code)


def explored_paths(open_states) -> list:
    """The paths the engine found, as frozensets of the concrete
    (slot, value) writes each end state holds."""
    out = []
    for ws in open_states:
        storage = ws.accounts[_ADDRESS].storage.printable_storage
        out.append(frozenset((k.value, v.value) for k, v in storage.items()))
    return out


class Driver:
    """A new contract from the seed per unit of work; a unit explores
    it and completes 2^k paths."""

    annotation = "storm.explore"
    #: every exploration is the whole mix
    period = 1

    def __init__(self, config: dict, mix: dict, seed: int, root):
        self.config = config
        self.mix = mix
        self.shape = config["contract"]
        self.n_paths = 1 << self.shape["k"]
        # set-up and window draw from streams of their own
        self._rng = random.Random(f"window-{seed}")
        self._warm_rng = random.Random(f"set-up-{seed}")
        self._check_rng = random.Random(f"check-{seed}")
        self.attempted = 0
        self.failed = 0
        #: per completed exploration: (contract, paths found)
        self._found = []
        self.paths_done = 0
        #: per warm-up exploration: wall and compile seconds
        self.warmup = []
        #: wall seconds of each exploration of the window
        self.walls = []

    def _draw(self, rng: random.Random) -> dict:
        return draw_contract(rng.getrandbits(64), self.shape)

    # -- program side ------------------------------------------------------

    def _explore(self, contract: dict):
        from mythril_tpu.analysis.symbolic import SymExecWrapper
        from mythril_tpu.ethereum.evmcontract import EVMContract
        from mythril_tpu.orchestration.mythril_analyzer import (
            reset_analysis_state,
        )
        from mythril_tpu.support.support_args import args

        ex = self.config["explore"]
        args.tpu_lanes = ex["tpu_lanes"]
        args.tpu_mesh = ex["tpu_mesh"]
        reset_analysis_state()
        return SymExecWrapper(
            EVMContract(code=build_code(contract).hex(), name="storm"),
            address=_ADDRESS, strategy=ex["strategy"],
            max_depth=ex["max_depth"],
            execution_timeout=ex["execution_timeout"],
            create_timeout=ex["create_timeout"],
            transaction_count=ex["transaction_count"],
            compulsory_statespace=False, run_analysis_modules=False)

    def warm_up(self, clock) -> None:
        """Explore new contracts until one compiles nothing: every
        width the engine widens through on a new contract is then
        compiled."""
        for _ in range(self.mix["warmup_max"]):
            before, t0 = clock.seconds, time.perf_counter()
            self._explore(self._draw(self._warm_rng))
            self.warmup.append({"wall_s": time.perf_counter() - t0,
                                "compile_s": clock.seconds - before})
            if clock.seconds == before:
                return

    def run_one(self) -> None:
        errors0 = device_errors()
        self.attempted += 1
        contract = self._draw(self._rng)
        t0 = time.perf_counter()
        sym = self._explore(contract)
        self._found.append((contract, explored_paths(sym.laser.open_states)))
        self.walls.append(time.perf_counter() - t0)
        self.paths_done += self.n_paths
        if device_errors() != errors0:
            self.failed += 1

    def end_to_end(self, window_s: float) -> dict:
        return {"paths_per_s": self.paths_done / window_s}

    def record(self) -> dict:
        return {"completed": len(self._found), "paths": self.paths_done}

    # -- comparison with the reference -----------------------------------

    def checks(self) -> list:
        """(name, value, limit) per number compared, summed over a
        sample of the window's explorations drawn from the seed (the
        configuration's "reference_sample" of them, or all where there
        are fewer): the contract's reference paths not found, and paths
        found that it lacks or that came more than once."""
        n = min(len(self._found), self.config["reference_sample"])
        missing = extra = 0
        for c, paths in self._check_rng.sample(self._found, n):
            want = path_set(c["slots"], c["adds"], c["sha3_slot"])
            found = set(paths)
            missing += len(want - found)
            extra += len(found - want) + (len(paths) - len(found))
        return [("paths_missing", missing, 0),
                ("paths_unexpected", extra, 0)]
