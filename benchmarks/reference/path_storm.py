"""Plain reference of the path storm: the set of paths that the seeded
fork+SSTORE+SHA3 contract (traffic/storm.py) implies.

Level i of the contract branches on one calldata bit. On the taken arm
it adds the constant ``adds[i]`` to an accumulator and stores the
accumulator at ``slots[i]``; after the last level it stores
keccak256(accumulator) at ``sha3_slot``. Every calldata bit is free and
independent, so each of the 2^k arm choices is a feasible path, and
the storage it writes names it: a path is the frozenset of its
(slot, value) writes."""

from .keccak import keccak256

_WORD = 1 << 256


def path_set(slots, adds, sha3_slot) -> set:
    """Every path of the storm, as frozensets of (slot, value)."""
    k = len(slots)
    hashes = {}
    paths = set()
    for mask in range(1 << k):
        acc = 0
        writes = {}
        for i in range(k):
            if mask >> i & 1:
                acc = (acc + adds[i]) % _WORD
                writes[slots[i]] = acc
        if acc not in hashes:
            hashes[acc] = int.from_bytes(
                keccak256(acc.to_bytes(32, "big")), "big")
        writes[sha3_slot] = hashes[acc]
        paths.add(frozenset(writes.items()))
    return paths
