"""Share of the window inside the lane engine: the union of the program's
lane.explore spans over the window."""

from benchmarks.trace_reduce import covered


def read(record):
    intervals = record["spans"].get("lane.explore", [])
    if not intervals:
        return None
    return 100.0 * covered(intervals) / record["window_s"]
