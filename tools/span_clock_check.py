#!/usr/bin/env python3
"""Run one benchmark cell traced, and compare each program span as the
harness maps it from the telemetry ring onto the profiler's clock with
the profiler's own event of that span.

    python3 tools/span_clock_check.py --workload <cell> --seed <n> \\
        --seconds <s>

The harness (benchmarks/run.py) moves the whole ring onto the
profiler's clock with one offset, taken at one TraceAnnotation. The
program's spans also open a profiler TraceMe of their own
(mythril_tpu/support/telemetry/spans.py), so each ring span has a
native twin. This prints the cell's result line as run.py does, then
one JSON line: {"span_clock": {...}} with the spans compared, those
without a twin, and the difference between a ring span and its twin
(the larger of the start and the end difference) in microseconds: the
largest, the 99th percentile and the median, the span that gave the
largest, and the seconds the comparison added to the run; and, under
"gaps", every device idle gap of 0.1 s or more of the traced window
counted and summed by the span that names it (the result line names
only the ten longest).
"""

import argparse
import bisect
import json
import statistics
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))


def compare(ring, native, offset_ns: float) -> dict:
    """ring: (start, end, name) in monotonic seconds; native: (start,
    end, name) in profiler nanoseconds. Each native event is paired with
    the ring span of its name, among the nearest by start, whose start
    and end are closest to its own."""
    by_name = {}
    for s, e, n in ring:
        by_name.setdefault(n, []).append((s * 1e9 + offset_ns,
                                          e * 1e9 + offset_ns))
    starts = {}
    for n, ivs in by_name.items():
        ivs.sort()
        starts[n] = [s for s, _ in ivs]
    diffs, unmatched, worst = [], 0, (0.0, None)
    for s, e, n in native:
        ivs = by_name.get(n)
        if not ivs:
            unmatched += 1
            continue
        i = bisect.bisect_left(starts[n], s)
        d = min(max(abs(ivs[j][0] - s), abs(ivs[j][1] - e))
                for j in range(max(i - 3, 0), min(i + 3, len(ivs))))
        diffs.append(d / 1e3)
        if d / 1e3 > worst[0]:
            worst = (d / 1e3, n)
    out = {"ring_spans": len(ring), "native_events": len(native),
           "compared": len(diffs), "native_without_ring": unmatched}
    if diffs:
        diffs.sort()
        out.update(max_us=diffs[-1],
                   p99_us=diffs[int(0.99 * (len(diffs) - 1))],
                   median_us=statistics.median(diffs), worst=worst[1],
                   over_1ms=sum(1 for d in diffs if d >= 1000.0))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    a = ap.parse_args(argv)
    from benchmarks import run, spans, trace_reduce

    traced_record = run._traced_record
    found = {}

    def checked(driver, profile_dir, window, clock_mark, *rest):
        t0 = time.monotonic()
        pd = trace_reduce.load(profile_dir)
        marks = trace_reduce.host_events(pd, {run.WINDOW, run.CLOCK})
        trace_window = next((s, e) for s, e, n in marks if n == run.WINDOW)
        offset = next(s for s, _e, n in marks if n == run.CLOCK) \
            - clock_mark * 1e9
        ring = spans.recorded()
        native = trace_reduce.host_events(pd, {n for _, _, n in ring})
        found.update(compare(ring, native, offset))
        on_trace = [(s * 1e9 + offset, e * 1e9 + offset, n)
                    for s, e, n in ring]
        on_trace += trace_reduce.host_events(pd, {driver.annotation})
        reduced = trace_reduce.reduce(trace_reduce.tpu_devices(pd),
                                      trace_window, on_trace, top=10 ** 6)
        del pd
        gaps = {}
        for name, seconds in reduced["idle_gaps"]:
            if seconds >= 0.1:
                n, total = gaps.get(name, (0, 0.0))
                gaps[name] = (n + 1, total + seconds)
        found.update(gaps=gaps, check_s=time.monotonic() - t0)
        return traced_record(driver, profile_dir, window, clock_mark,
                             *rest)

    run._traced_record = checked
    bench = run.load_json(run.ROOT / "BENCHMARK.json")
    run.emit(run.run_cell(bench, a.workload, a.seed, a.seconds, True))
    print(json.dumps({"span_clock": found}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
