"""CPU rehearsal of the storm.k13.mesh4 cell on four virtual devices at
k = 6 (64 paths) on 64 lanes: it runs correct on the mesh, a program
without the sharded plane initialiser leaves before any exploration, an
exploration off the mesh makes `correct` false, and the cell's four
per-layer readers read their spans and the trace's collectives, or
nothing where a record has none."""

import pytest

from benchmarks import control, run
from benchmarks.traffic import storm, storm_mesh

SEED = 2 ** 40 + 17
#: a seed whose first k = 6 contract of the window has two arm choices
#: with equal sums
COLLIDING = 2 ** 40 + 7
CELL = "storm.k13.mesh4"
#: k = 6 on 64 lanes: 16 lanes on each of the four devices
SMALL = {"contract": {"k": 6}, "explore": {"tpu_lanes": 64}}


@pytest.fixture(scope="module")
def bench():
    return run.load_json(run.ROOT / "BENCHMARK.json")


def _config(override=SMALL):
    return run.merged(run.load_json(
        run.ROOT / "benchmarks" / "configs" / "path_storm_v5e4.json"),
        override)


def _run(bench, override=SMALL):
    return run.run_cell(bench, CELL, SEED, 0.5, False, require_tpu=False,
                        config_override=override)


def test_mesh_cell_is_correct(bench):
    import jax

    assert len(jax.devices()) == 4
    r = _run(bench)
    assert r["correct"] is True
    assert r["checks"] == {
        "paths_missing": {"value": 0, "limit": 0},
        "paths_unexpected": {"value": 0, "limit": 0},
        "explorations_off_mesh": {"value": 0, "limit": 0}}
    assert r["device"]["count"] == 4 and r["failed"] == 0
    assert set(r["metrics"]) == {"paths_per_s", "setup_s"}
    assert len(r["harness"]["chip_peak_bytes"]) == 4
    # the mesh warms in one exploration
    assert r["harness"]["warmup"][-1]["compile_s"] == 0
    assert len(r["harness"]["warmup"]) <= 2
    assert r["harness"]["window_compile_s"] == 0


def test_program_without_sharded_planes_leaves_before_exploring(
        monkeypatch):
    from mythril_tpu.parallel import mesh

    monkeypatch.delattr(mesh, "init_planes")

    def explore(*_):
        raise AssertionError("explored before the mesh check")

    monkeypatch.setattr(storm.Driver, "_explore", explore)
    with pytest.raises(SystemExit, match="no sharded lane planes"):
        storm_mesh.Driver(_config(), {"warmup_max": 1}, SEED, run.ROOT)


def test_planes_built_on_one_chip_are_refused(monkeypatch):
    """An initialiser that leaves every plane on one device, as a program
    that builds the planes and then copies them out would have them."""
    import jax

    from mythril_tpu.ops import symstep
    from mythril_tpu.parallel import mesh

    monkeypatch.setattr(mesh, "init_planes",
                        lambda _m, n, **kw: jax.device_put(
                            symstep.init_sym_lanes(n, **kw),
                            jax.devices()[0]))
    with pytest.raises(SystemExit, match="not sharded over 4 chips"):
        storm_mesh.Driver(_config(), {"warmup_max": 1}, SEED, run.ROOT)


def test_control_is_not_correct(bench, monkeypatch):
    """The configuration's control is path_storm's: paths that end with
    the same accumulator are merged, which breaks the guarantee that no
    path is merged away."""
    monkeypatch.setattr(storm, "explored_paths", storm.explored_paths)
    control.install("path_storm_v5e4", _config())
    r = run.run_cell(bench, CELL, COLLIDING, 0.5, False, require_tpu=False,
                     config_override=run.merged(
                         SMALL, {"reference_sample": 1 << 20}))
    assert r["correct"] is False
    assert r["checks"]["paths_missing"]["value"] > 0
    assert r["checks"]["explorations_off_mesh"]["value"] == 0


def test_explorations_off_the_mesh_are_not_correct(bench):
    r = _run(bench, run.merged(SMALL, {"explore": {"tpu_mesh": 0}}))
    assert r["checks"]["explorations_off_mesh"]["value"] >= 1
    assert r["correct"] is False


def _read(name, record):
    return run.metric_reader(name, run.ROOT)(record)


def _trace(collective_s=0.0, busy_s=4.0, module_s=None):
    return {"window_s": 10.0, "busy_s": busy_s, "devices": 4,
            "collective_s": collective_s,
            "module_s": {"jit__window_exec": 3.0}
            if module_s is None else module_s}


def _record(spans=None, trace=None):
    return {"spans": spans or {}, "window_s": 10.0, "completed": 1,
            "paths": 64, "counters": {"windows": 1, "solver_queries": 0},
            "trace": trace}


@pytest.mark.parametrize("name,record,value", [
    ("collective_share.mesh", _record(trace=_trace(collective_s=0.5)),
     5.0),
    ("collective_share.mesh", _record(trace=_trace()), 0.0),
    ("collective_share.mesh", _record(), None),
    ("mesh_pull_share.mesh", _record(
        {"mesh.pull": [(0.0, 1.0), (0.5, 2.0), (5.0, 5.5)],
         "lane.window_pull": [(0.0, 9.0)]}), 25.0),
    ("mesh_pull_share.mesh", _record({"lane.window_pull": [(0.0, 9.0)]}),
     None),
    ("window_kernel_share.mesh", _record(trace=_trace()), 30.0),
    ("window_kernel_share.mesh", _record(trace=_trace(module_s={})),
     None),
    ("device_idle.mesh", _record(trace=_trace(busy_s=2.5)), 75.0),
    ("device_idle.mesh", _record(trace=_trace(busy_s=0.0)), None),
], ids=["collectives", "no_collectives", "collectives_untraced",
        "pulls", "no_pulls", "kernel", "no_kernel", "idle", "no_ops"])
def test_mesh_readers(name, record, value):
    got = _read(name, record)
    assert got == (None if value is None else pytest.approx(value))
