"""Share of the traced window in which the chips ran the collectives
that GSPMD put into the sharded lane programs (all-reduce, all-gather,
reduce-scatter, collective-permute, all-to-all: trace_reduce's
COLLECTIVE): their device time over the window, averaged over the
chips. A window in which none ran reads 0.0."""


def read(record):
    t = record["trace"]
    if not t:
        return None
    return 100.0 * t["collective_s"] / t["window_s"]
