"""Share of the traced window in which the device ran the lane window
program (_window_exec, which runs ops/symstep.sym_run): the device time
of that XLA module's events over the window, from the profiler trace."""


def read(record):
    t = record["trace"]
    if not t:
        return None
    busy = sum(s for name, s in t["module_s"].items()
               if "_window_exec" in name)
    if busy <= 0:
        return None
    return 100.0 * busy / t["window_s"]
