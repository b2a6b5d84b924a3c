"""Share of the window spent turning retired lanes back into host states:
the union of the program's retire.materialize spans over the window."""

from benchmarks.trace_reduce import covered


def read(record):
    intervals = record["spans"].get("retire.materialize", [])
    if not intervals:
        return None
    return 100.0 * covered(intervals) / record["window_s"]
