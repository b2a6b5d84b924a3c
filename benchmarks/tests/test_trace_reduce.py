"""The trace reduction on synthetic events and on a small recorded trace.

data/cpu_window.xplane.pb was recorded on XLA:CPU: a jitted sin and
matmul called three times under TraceAnnotations "bench.clock",
"bench.window" and "unit.work", 20 ms apart. Its op events sit on the
host's XLA threads, which trace_reduce.cpu_devices reads as one device;
that checks the logic, and gives no device number."""

from pathlib import Path

import pytest

from benchmarks import trace_reduce as tr

DATA = Path(__file__).resolve().parent / "data" / "cpu_window.xplane.pb"


def test_union_and_clip():
    assert tr.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    assert tr.covered([(0, 2, "a"), (1, 3, "b"), (10, 11, "c")]) == 4
    assert tr.clip([(0, 10, "a"), (20, 30, "b")], 5, 25) == [
        (5, 10, "a"), (20, 25, "b")]


def test_reduce_synthetic_two_devices():
    devices = {
        "d0": {"ops": [(10, 20, "fusion.1"), (15, 30, "all-reduce.2"),
                       (60, 70, "fusion.1")],
               "modules": [(10, 30, "jit__window_exec"),
                           (60, 70, "jit__window_exec")]},
        "d1": {"ops": [(10, 50, "fusion.1")],
               "modules": [(10, 50, "jit__window_exec")]},
    }
    spans = [(0, 100, "corpus.analysis"), (50, 60, "solver.check")]
    r = tr.reduce(devices, (0, 100), spans)
    ns = 1e-9
    assert r["window_s"] == pytest.approx(100 * ns)
    # d0 busy 30, d1 busy 40: the mean over devices
    assert r["busy_s"] == pytest.approx(35 * ns)
    assert r["collective_s"] == pytest.approx(15 / 2 * ns)
    assert r["module_s"]["jit__window_exec"] == pytest.approx(35 * ns)
    # gaps of the union of both devices: [0,10), [50,60), [70,100)
    assert [g[1] for g in r["idle_gaps"]] == pytest.approx(
        [30 * ns, 10 * ns, 10 * ns])
    # each gap is named by the innermost host span over its middle
    assert [g[0] for g in r["idle_gaps"]] == [
        "corpus.analysis", "corpus.analysis", "solver.check"]
    assert tr.top_ops(r, top=1) == [["fusion.1", pytest.approx(30 * ns)]]


def test_reduce_without_devices_refuses():
    with pytest.raises(ValueError):
        tr.reduce({}, (0, 1))


def test_recorded_cpu_trace():
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(DATA))
    marks = tr.host_events(pd, {"bench.window", "bench.clock", "unit.work"})
    names = sorted(n for _, _, n in marks)
    assert names == ["bench.clock", "bench.window"] + ["unit.work"] * 3
    window = next((s, e) for s, e, n in marks if n == "bench.window")
    devices = tr.cpu_devices(pd)
    r = tr.reduce(devices, window,
                  [m for m in marks if m[2] == "unit.work"])
    assert 0.05 < r["window_s"] < 0.1
    assert 0 < r["busy_s"] < r["window_s"]
    assert {"wrapped_sine", "dot_general.1"} <= set(r["op_s"])
    # the two 20 ms sleeps between the calls are the longest gaps, and
    # no annotation covers them
    gaps = r["idle_gaps"]
    assert [g[0] for g in gaps[:2]] == ["no host span"] * 2
    assert all(0.015 < g[1] < 0.03 for g in gaps[:2])
    assert gaps == sorted(gaps, key=lambda g: -g[1])


def test_tpu_devices_ignores_host_planes():
    from jax.profiler import ProfileData

    assert tr.tpu_devices(ProfileData.from_file(str(DATA))) == {}
