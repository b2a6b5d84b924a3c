"""The plain EVM that replays an issue's transaction sequence: it
confirms a condition the sequence reaches, refutes one it misses, and
holds back where a value the sequence left open decided the path."""

import pytest

from benchmarks.reference import evm
from benchmarks.reference.keccak import keccak256

ME, ATTACKER = "0x0", "0xdeadbeefdeadbeefdeadbeefdeadbeefdeadbeef"


def _code(*parts) -> str:
    return "0x" + "".join(parts)


#: if calldata[0:32] == 42: SELFDESTRUCT(caller) at 0x0c, else STOP
GUARDED_KILL = _code("600035", "602a", "14", "600a", "57", "00",
                     "5b", "33", "ff")
KILL_PC = 0x0C
#: the same guard on storage slot 0 instead of calldata
STORAGE_KILL = _code("600054", "602a", "14", "600a", "57", "00",
                     "5b", "33", "ff")
#: calldata[0:32] + 1 at 0x05, stored at slot 0
ADD_ONE = _code("600035", "6001", "01", "600055", "00")
ADD_PC = 0x05


def _issue(code, swc, address, calldata, storage="{}"):
    return {"swc-id": swc, "address": address, "tx_sequence": {
        "initialState": {"accounts": {
            ME: {"balance": "0x1", "code": code, "nonce": 0,
                 "storage": storage},
            ATTACKER: {"balance": "0x0", "code": "0x", "nonce": 0,
                       "storage": "{}"}}},
        "steps": [{"address": ME, "input": calldata, "origin": ATTACKER,
                   "value": "0x0"}]}}


def _word(v: int) -> str:
    return v.to_bytes(32, "big").hex()


@pytest.mark.parametrize("code, swc, pc, calldata, want", [
    (GUARDED_KILL, "106", KILL_PC, "0x" + _word(42), "confirmed"),
    (GUARDED_KILL, "106", KILL_PC, "0x" + _word(41), "refuted"),
    (GUARDED_KILL, "106", KILL_PC, "0x", "refuted"),
    (STORAGE_KILL, "106", KILL_PC, "0x", "indeterminate"),
    (ADD_ONE, "101", ADD_PC, "0x" + _word(2 ** 256 - 1), "confirmed"),
    (ADD_ONE, "101", ADD_PC, "0x" + _word(7), "refuted"),
], ids=["kill_reached", "kill_guard_fails", "kill_no_calldata",
        "kill_on_free_storage", "add_overflows", "add_does_not"])
def test_replay_outcome(code, swc, pc, calldata, want):
    assert evm.replay_issue(_issue(code, swc, pc, calldata)) == want


def test_no_sequence_is_refuted():
    assert evm.replay_issue({"swc-id": "106", "address": 0}) == "refuted"


def test_sha3_storage_and_return():
    """keccak256 of memory, a storage write read back, and RETURN."""
    code = bytes.fromhex(
        "602a600052"      # mstore(0, 42)
        "60206000" "20"   # sha3(0, 32)
        "80" "600155"     # sstore(1, h)
        "600154" "600052" "60206000f3")  # return sload(1)
    w = evm.World({1: evm.Account(code=code)})
    ok, out, taint = w.run(evm.Frame(1, code, 2, 0, b""))
    want = keccak256((42).to_bytes(32, "big"))
    assert ok and out == want and not taint
    assert w.accounts[1].storage[1] == int.from_bytes(want, "big")


def test_create_address_matches_the_yellow_paper():
    """The address of the first contract an account creates."""
    sender = 0x6AC7EA33F8831EA9DCC53393AAA88B25A785DBF0
    assert (evm._rlp_create_address(sender, 0)
            == 0xCD234A471B72BA2F1CCF0A70FCABA648A5EECD8D)
