"""Device windows (lane_engine.RUN_STATS_TOTAL["windows"]) per 1000
paths explored in the window: each is a host sync."""


def read(record):
    if not record["paths"]:
        return None
    return record["counters"]["windows"] * 1000.0 / record["paths"]
