"""Share of the window spent draining device windows on the host: the
union of the program's lane.drain spans (decoding a window's record and
fork tables, then resolving them into terms), over the window."""

from benchmarks.trace_reduce import covered


def read(record):
    intervals = record["spans"].get("lane.drain", [])
    if not intervals:
        return None
    return 100.0 * covered(intervals) / record["window_s"]
