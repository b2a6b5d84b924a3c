"""Share of the window in the host interpreter's own work: the union of
the program's svm.host_exec spans (the stretches of LaserEVM.exec's
loop between lane sweeps) less what the solver's solver.check and
solver.discharge spans cover inside them, over the window."""

from benchmarks.self_time import self_time


def read(record):
    spans = record["spans"]
    host = spans.get("svm.host_exec", [])
    if not host:
        return None
    solver = spans.get("solver.check", []) + spans.get("solver.discharge", [])
    return 100.0 * self_time(host, solver) / record["window_s"]
