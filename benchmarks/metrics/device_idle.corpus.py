"""Share of the traced window in which no operation ran on the device:
1 - (union of the device's op intervals / window), averaged over the
cell's chips, from the profiler trace."""


def read(record):
    t = record["trace"]
    if not t or t["busy_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
