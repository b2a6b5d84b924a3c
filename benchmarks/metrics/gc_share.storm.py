"""Share of the window in full garbage collections: the union of the
program's gc.collect spans over the window. Tracing always hooks the
collector, so a window without such a span reads 0.0."""

from benchmarks.trace_reduce import covered


def read(record):
    intervals = record["spans"].get("gc.collect", [])
    return 100.0 * covered(intervals) / record["window_s"]
