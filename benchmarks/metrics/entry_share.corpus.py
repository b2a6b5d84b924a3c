"""Share of the window in the entry layer's own work: the union of the
program's analysis.contract spans (one per contract fire_lasers
analyses) less what its svm.sym_exec spans cover, over the window:
wrapper set-up, detectors' post-analysis and issue collection."""

from benchmarks.self_time import self_time


def read(record):
    spans = record["spans"]
    entry = spans.get("analysis.contract", [])
    if not entry:
        return None
    return (100.0 * self_time(entry, spans.get("svm.sym_exec", []))
            / record["window_s"])
