"""In-place SHA3 resume: lanes parked at SHA3 are patched on device
(host-built keccak term) instead of retired + re-seeded, with identical
exploration results."""

import numpy as np
import pytest

import bench
from mythril_tpu.laser import lane_engine


@pytest.fixture(autouse=True)
def _fresh_stats():
    lane_engine.RUN_STATS_TOTAL = {}
    yield


def _warm(n_lanes, code):
    for bucket in (16, n_lanes):
        lane_engine.warm_variant(n_lanes, len(code), {}, lane_engine.DEFAULT_WINDOW, 8192,
                                 seed_bucket=bucket)


def test_sha3_word_hashes_defer_without_parking():
    # the bench workload's SHA3 tail is a word-aligned 32-byte hash:
    # since the device defers those as keccak records, NO lane should
    # park or resume at SHA3 anymore — the whole tree runs device-side
    code, n_paths = bench.build_symbolic_contract(k=6)
    _warm(16, code)
    lane_s, lane_paths = bench._explore(code, 16)
    host_s, host_paths = bench._explore(code, 0)
    assert lane_paths == host_paths == n_paths
    stats = lane_engine.RUN_STATS_TOTAL
    assert stats.get("resumed", 0) == 0


def test_sha3_odd_length_parks_and_resumes_in_place():
    # a 33-byte hash is outside the defer envelope (not 32/64): the
    # lane parks at SHA3 and the in-place resume path must patch it on
    # device (host-built keccak term), with host-identical results
    from mythril_tpu.support.opcodes import ADDRESS, OPCODES

    op = {name: data[ADDRESS] for name, data in OPCODES.items()}

    def push(v, n=1):
        return bytes([0x5F + n]) + v.to_bytes(n, "big")

    c = bytearray()
    c += push(0) + bytes([op["CALLDATALOAD"]])
    c += push(0) + bytes([op["MSTORE"]])
    c += push(7) + push(32) + bytes([op["MSTORE8"]])
    c += push(33) + push(0) + bytes([op["SHA3"]])
    c += push(99) + bytes([op["SSTORE"], op["STOP"]])
    code = bytes(c)
    _warm(16, code)
    lane_s, lane_paths = bench._explore(code, 16)
    host_s, host_paths = bench._explore(code, 0)
    assert lane_paths == host_paths
    stats = lane_engine.RUN_STATS_TOTAL
    assert stats.get("resumed", 0) >= 1


def test_resume_declines_when_sha3_hooked():
    eng = lane_engine.LaneEngine(n_lanes=8, blocked_ops=("SHA3",))
    assert eng.resume_on is False
    eng2 = lane_engine.LaneEngine(n_lanes=8)
    assert eng2.resume_on is True


def test_try_resume_concrete_memory_hash():
    """The patched hash must equal the interpreter's keccak of the
    same concrete bytes."""
    from mythril_tpu.laser.function_managers import (
        keccak_function_manager,
    )
    from mythril_tpu.native import keccak256

    eng = lane_engine.LaneEngine(n_lanes=8)
    payload = bytes(range(32))
    rows = {
        "sid_sub": np.zeros(1, np.int32),
        "sid_top": np.zeros(1, np.int32),
        "sub": np.asarray(
            [lane_engine.bv256.int_to_limbs(32)], np.uint32),
        "top": np.asarray(
            [lane_engine.bv256.int_to_limbs(0)], np.uint32),
        "msize": np.asarray([32], np.int32),
        "min_gas": np.asarray([100], np.int32),
        "max_gas": np.asarray([100], np.int32),
        "gas_limit": np.asarray([10**6], np.int32),
        "mlog_count": np.asarray([0], np.int32),
        "mlog_off": np.zeros((1, 8), np.int32),
        "mlog_len": np.zeros((1, 8), np.int32),
        "mlog_sid": np.zeros((1, 8), np.int32),
        "memory": np.frombuffer(payload, np.uint8)[None, :].repeat(
            1, axis=0).copy(),
        "mkind": np.full((1, 32), 1, np.uint8),
    }
    # pad memory planes to RESUME_MEM
    pad = lane_engine.RESUME_MEM - 32
    rows["memory"] = np.concatenate(
        [rows["memory"], np.zeros((1, pad), np.uint8)], axis=1)
    rows["mkind"] = np.concatenate(
        [rows["mkind"], np.zeros((1, pad), np.uint8)], axis=1)

    patch = eng._try_resume(rows, 0, byte_pc=7, sp=4)
    assert patch is not None
    pc, sp, msize, ming, maxg, sid, limbs = patch
    assert pc == 8 and sp == 3
    assert sid == 0  # concrete hash ships as limbs
    expected = int.from_bytes(keccak256(payload), "big")
    assert lane_engine.bv256.limbs_to_int(np.asarray(limbs)) == expected
    # sha3 gas for 32 bytes = 30 + 6, on top of the row's 100
    assert ming == maxg == 136


def test_try_resume_declines_symbolic_length():
    eng = lane_engine.LaneEngine(n_lanes=8)
    rows = {"sid_sub": np.asarray([7], np.int32)}
    assert eng._try_resume(rows, 0, byte_pc=1, sp=2) is None
