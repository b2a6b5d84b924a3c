"""The reader of the collector's spans, `gc_share.storm`, on a synthetic
record: the union of the program's gc.collect spans over the window, and
0.0 where the window holds none, since tracing always hooks the
collector."""

import pytest

from benchmarks import run


def _read(name, record):
    return run.metric_reader(name, run.ROOT)(record)


def _record(spans, completed=4, window_s=10.0):
    return {"spans": spans, "window_s": window_s, "completed": completed,
            "counters": {"windows": 0, "solver_queries": 0}}


@pytest.mark.parametrize("spans,share", [
    ({"gc.collect": [(1.0, 2.5), (2.0, 3.0), (7.0, 7.5)],
      "lane.drain": [(0.0, 9.0)]}, 100.0 * 2.5 / 10.0),
    ({"lane.drain": [(0.0, 9.0)]}, 0.0)], ids=["collections", "none"])
def test_gc_share_is_the_union_of_collections(spans, share):
    assert _read("gc_share.storm", _record(spans)) == pytest.approx(share)
