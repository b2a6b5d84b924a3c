"""The program's own counters that the benchmark reads: process-wide
and cumulative, so a window reads their change."""


def read() -> dict:
    """Device windows the lane engine ran, and fresh solver queries."""
    from mythril_tpu.laser import lane_engine
    from mythril_tpu.smt.solver.solver_statistics import SolverStatistics

    return {"windows": lane_engine.RUN_STATS_TOTAL.get("windows", 0),
            "solver_queries": SolverStatistics().query_count}


def device_errors() -> int:
    """Device errors the program recovered from on the host so far
    (SolverStatistics.device_*_errors)."""
    from mythril_tpu.smt.solver.solver_statistics import SolverStatistics

    c = SolverStatistics().batch_counters()
    return sum(v for k, v in c.items()
               if k.startswith("device_") and k.endswith("_errors"))
