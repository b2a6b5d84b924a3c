"""Share of the window in which some thread was in the solver: the union, over
all threads, of the program's solver.discharge and solver.check spans,
over the window. Never the summed solver_time, which counts each pool
thread apart and can exceed the wall."""

from benchmarks.trace_reduce import covered


def read(record):
    intervals = [iv for name in ('solver.discharge', 'solver.check')
                 for iv in record["spans"].get(name, [])]
    if not intervals:
        return None
    return 100.0 * covered(intervals) / record["window_s"]
