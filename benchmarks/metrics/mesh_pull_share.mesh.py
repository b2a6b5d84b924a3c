"""Share of the window spent pulling arrays that live on the lane mesh
to the host (window logs, retired rows, the coverage bitmap), each
pull gathering from every chip: the union of the program's mesh.pull
spans over the window. A meshed exploration always pulls, so a window
without such a span reads nothing: the program does not record them."""

from benchmarks.trace_reduce import covered


def read(record):
    intervals = record["spans"].get("mesh.pull", [])
    if not intervals:
        return None
    return 100.0 * covered(intervals) / record["window_s"]
