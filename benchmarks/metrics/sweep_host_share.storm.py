"""Share of the window in the lane sweep's host work around the device
explore: the union of the program's svm.sweep_prep spans (hook scan,
seedability verdicts, grouping, width pick) and svm.sweep_retire spans
(static screen, loop summaries, the transaction-end shortcut, coverage
merge), over the window."""

from benchmarks.trace_reduce import covered


def read(record):
    spans = record["spans"]
    intervals = (spans.get("svm.sweep_prep", [])
                 + spans.get("svm.sweep_retire", []))
    if not intervals:
        return None
    return 100.0 * covered(intervals) / record["window_s"]
