"""Fresh solver queries per analysis: SolverStatistics.query_count over
the window, per analysis completed in it."""


def read(record):
    if not record["completed"]:
        return None
    return record["counters"]["solver_queries"] / record["completed"]
