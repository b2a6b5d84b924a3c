"""Reduction of a JAX profiler trace (.xplane.pb) to the numbers the
benchmark reports: the device's busy time and idle share per device,
device time per XLA module and per op, collective time, and the
longest idle gaps, each named by what the host was doing in it.

All times are on the profiler's clock, in nanoseconds, until the
result, which is in seconds."""

import re
from pathlib import Path

#: op names of the collectives XLA inserts across chips
COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all")


def load(profile_dir):
    """The newest trace the profiler wrote under profile_dir."""
    from jax.profiler import ProfileData

    found = sorted(Path(profile_dir).glob("plugins/profile/*/*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {profile_dir}")
    return ProfileData.from_file(str(found[-1]))


def _events(line):
    return [(e.start_ns, e.start_ns + e.duration_ns, e.name)
            for e in line.events if e.duration_ns > 0]


def short_name(name: str) -> str:
    """An XLA op or module event's name without its HLO text: the TPU
    trace names an op by its whole instruction, "%while.70 = (...)
    while(...)", and a module as "jit_f(1234)"."""
    return name.split(" = ", 1)[0].lstrip("%").split("(", 1)[0]


def qualified(ops, modules) -> list:
    """Ops renamed "<module>/<op>" after the module execution that
    holds them: op names repeat from one XLA module to the next."""
    mods = sorted((s, e, short_name(n)) for s, e, n in modules)
    out, j = [], 0
    for s, e, name in sorted(ops):
        while j < len(mods) and mods[j][1] <= s:
            j += 1
        mod = mods[j][2] if j < len(mods) and mods[j][0] <= s else "?"
        out.append((s, e, f"{mod}/{short_name(name)}"))
    return out


def tpu_devices(pd) -> dict:
    """{device plane: {"ops": [...], "modules": [...]}} for every TPU
    plane, events as (start, end, name), ops named by module."""
    out = {}
    for plane in pd.planes:
        if not re.fullmatch(r"/device:TPU:\d+", plane.name):
            continue
        lines = {line.name: line for line in plane.lines}
        modules = (_events(lines["XLA Modules"])
                   if "XLA Modules" in lines else [])
        ops = _events(lines["XLA Ops"]) if "XLA Ops" in lines else []
        out[plane.name] = {"ops": qualified(ops, modules),
                           "modules": [(s, e, short_name(n))
                                       for s, e, n in modules]}
    return out


def cpu_devices(pd) -> dict:
    """XLA:CPU's op events on the host's threads, as one device: for
    checking the reduction on a trace recorded without a chip. No
    device metric comes from it."""
    ops = []
    for plane in pd.planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            if line.name.startswith("tf_XLA"):
                ops += [ev for ev in _events(line) if "::" not in ev[2]]
    return {"/host:CPU": {"ops": ops, "modules": []}}


def host_events(pd, names) -> list:
    """(start, end, name) of every host-thread event whose name is in
    names: the harness's TraceAnnotations."""
    out = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            out += [(e.start_ns, e.start_ns + e.duration_ns, e.name)
                    for e in line.events if e.name in names]
    return out


def union(intervals) -> list:
    """Sorted disjoint (start, end) covering the given intervals."""
    merged = []
    for s, e in sorted((s, e) for s, e, *_ in intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1][1] = e
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def clip(intervals, lo, hi) -> list:
    return [(max(s, lo), min(e, hi), *rest) for s, e, *rest in intervals
            if e > lo and s < hi]


def covered(intervals) -> float:
    return sum(e - s for s, e in union(intervals))


def _label(gap, spans) -> str:
    """The shortest host span that covers the gap's midpoint: the
    innermost thing the host was doing."""
    mid = (gap[0] + gap[1]) / 2
    best = None
    for s, e, name in spans:
        if s <= mid < e and (best is None or e - s < best[1] - best[0]):
            best = (s, e, name)
    return best[2] if best else "no host span"


def reduce(devices: dict, window, spans=(), top: int = 10) -> dict:
    """Seconds of device work in the window (lo, hi).

    devices  {device: {"ops": [...], "modules": [...]}}, as tpu_devices
    spans    (start, end, name) host spans that name the idle gaps
    """
    lo, hi = window
    n = len(devices)
    if not n:
        raise ValueError("the trace holds no device plane")
    busy, op_s, module_s, all_busy = 0.0, {}, {}, []
    collective = 0.0
    for dev in devices.values():
        ops = clip(dev["ops"] or dev["modules"], lo, hi)
        busy += covered(ops)
        all_busy += ops
        for s, e, name in ops:
            op_s[name] = op_s.get(name, 0.0) + (e - s)
            if COLLECTIVE.search(name):
                collective += e - s
        by_module = {}
        for s, e, name in clip(dev["modules"], lo, hi):
            by_module.setdefault(name, []).append((s, e))
        for name, ivs in by_module.items():
            module_s[name] = module_s.get(name, 0.0) + covered(ivs)
    gaps, cursor = [], lo
    for s, e in union(all_busy):
        if s > cursor:
            gaps.append((cursor, s))
        cursor = max(cursor, e)
    if cursor < hi:
        gaps.append((cursor, hi))
    gaps.sort(key=lambda g: g[0] - g[1])
    ns = 1e-9
    return {
        "window_s": (hi - lo) * ns,
        "busy_s": busy / n * ns,
        "devices": n,
        "collective_s": collective / n * ns,
        "op_s": {k: v / n * ns for k, v in op_s.items()},
        "module_s": {k: v / n * ns for k, v in module_s.items()},
        "idle_gaps": [[_label(g, spans), (g[1] - g[0]) * ns]
                      for g in gaps[:top]],
    }


def top_ops(reduced: dict, top: int = 10) -> list:
    """[[op name, seconds], ...] of the ops that took most device time."""
    ranked = sorted(reduced["op_s"].items(), key=lambda kv: -kv[1])
    return [[name, s] for name, s in ranked[:top]]
