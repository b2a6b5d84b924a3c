"""The four calldata implementations (this build's analog of the
reference's tests/laser/state/calldata_test.py): word reads, slicing,
OOB-read-is-zero for symbolic calldata, and model concretization."""

import pytest

from mythril_tpu.laser.state.calldata import (
    BasicConcreteCalldata,
    BasicSymbolicCalldata,
    ConcreteCalldata,
    SymbolicCalldata,
)
from mythril_tpu.smt import Solver, sat, symbol_factory, unsat

DATA = list(b"\x01\x02\x03\x04" + b"\x00" * 28 + b"\xff")


def _as_int(v):
    """BasicConcreteCalldata returns raw ints for concrete indices
    (reference parity); the array-backed variants return BitVecs."""
    return v if isinstance(v, int) else v.value


@pytest.mark.parametrize("cls", [ConcreteCalldata, BasicConcreteCalldata])
def test_concrete_indexing(cls):
    cd = cls(0, DATA)
    assert cd.size == len(DATA)
    for i, b in enumerate(DATA):
        assert _as_int(cd[i]) == b, f"byte {i}"


@pytest.mark.parametrize("cls", [ConcreteCalldata, BasicConcreteCalldata])
def test_concrete_word_and_slice(cls):
    cd = cls(0, DATA)
    word = cd.get_word_at(0)
    assert word.value == int.from_bytes(bytes(DATA[:32]), "big")
    sliced = cd[1:4]
    assert [_as_int(s) for s in sliced] == DATA[1:4]


@pytest.mark.parametrize("cls", [ConcreteCalldata, BasicConcreteCalldata])
def test_concrete_oob_read_is_zero(cls):
    cd = cls(0, DATA)
    assert _as_int(cd[1000]) == 0


@pytest.mark.parametrize("cls", [SymbolicCalldata, BasicSymbolicCalldata])
def test_symbolic_read_constrained_by_size(cls):
    """A read below calldatasize can be any byte; a read at an index
    >= calldatasize must be 0 (If(i < size, data[i], 0))."""
    cd = cls(1)
    idx = 5
    v = cd[idx]
    s = Solver()
    s.set_timeout(10000)
    # force size <= 5 -> byte 5 must be zero
    s.add(cd.calldatasize == symbol_factory.BitVecVal(3, 256))
    s.add(v != symbol_factory.BitVecVal(0, 8))
    assert s.check() == unsat

    s2 = Solver()
    s2.set_timeout(10000)
    s2.add(cd.calldatasize == symbol_factory.BitVecVal(32, 256))
    s2.add(v == symbol_factory.BitVecVal(0x7F, 8))
    assert s2.check() == sat


def test_concrete_concretization():
    cd = ConcreteCalldata(0, DATA)
    s = Solver()
    assert s.check() == sat
    assert cd.concrete(s.model()) == DATA


def test_symbolic_concretization():
    cd = SymbolicCalldata(2)
    s = Solver()
    s.set_timeout(10000)
    s.add(cd.calldatasize == symbol_factory.BitVecVal(4, 256))
    s.add(cd[0] == symbol_factory.BitVecVal(0xAB, 8))
    assert s.check() == sat
    got = cd.concrete(s.model())
    assert len(got) == 4
    assert got[0] == 0xAB


@pytest.mark.parametrize("cls", [ConcreteCalldata, BasicConcreteCalldata])
def test_word_at_symbolic_offset(cls):
    """A symbolic CALLDATALOAD offset (a dynamic-array argument) reads
    the 32 bytes from that offset: a 256-bit word, never an empty
    concat."""
    cd = cls(0, DATA)
    off = symbol_factory.BitVecSym("cdl_off", 256)
    word = cd.get_word_at(off)
    assert word.size() == 256
    s = Solver()
    s.add(off == 1)
    s.add(word != int.from_bytes(bytes(DATA[1:33]), "big"))
    assert s.check() == unsat
