"""The path_storm control: paths that end with the same accumulator
(the same value in the SHA3 slot) are merged into one, as a state merge
that ignored storage would do, so the storage the others wrote is lost.
It breaks the configuration's guarantee that no path is merged away.
The program's own path-dropping options (--max-depth, --beam-search)
are not honoured on the lanes, so none of them can serve."""

from benchmarks.traffic import storm


def merge_equal_accumulators(paths: list, sha3_slot: int) -> list:
    """One path per final accumulator, which the SHA3 slot names."""
    kept = {}
    for p in paths:
        kept.setdefault(dict(p).get(sha3_slot), p)
    return list(kept.values())


def install(config: dict) -> None:
    """Merge what every later exploration reports."""
    real = storm.explored_paths
    sha3_slot = config["contract"]["sha3_slot"]
    storm.explored_paths = lambda states: merge_equal_accumulators(
        real(states), sha3_slot)
