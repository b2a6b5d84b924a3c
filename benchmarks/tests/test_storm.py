"""CPU rehearsal of the storm cell at k = 4 (16 paths): the explored
path set equals the reference's, the code length does not depend on
the seed, the control and every fault the cell can have make `correct`
come out false, and on four virtual devices a `chips: 4` cell engages
the lane mesh."""

import random

import pytest

from benchmarks import control, run
from benchmarks.reference.path_storm import path_set
from benchmarks.traffic import storm

SEED = 2 ** 40 + 17
#: a seed whose first k = 4 contract of the window has two arm choices
#: with equal sums
COLLIDING = 2 ** 40 + 11
#: k = 4 on 64 lanes, one device
SMALL = {"contract": {"k": 4}, "explore": {"tpu_lanes": 64, "tpu_mesh": 0}}


@pytest.fixture(scope="module")
def bench():
    return run.load_json(run.ROOT / "BENCHMARK.json")


def _config(override=SMALL):
    return run.merged(run.load_json(
        run.ROOT / "benchmarks" / "configs" / "path_storm.json"), override)


def _run(bench, override=SMALL, cell="storm.k13", seed=SEED):
    return run.run_cell(bench, cell, seed, 0.5, False, require_tpu=False,
                        config_override=override)


def test_code_length_and_shapes_do_not_depend_on_the_seed():
    shape = _config()["contract"]
    codes = [storm.build_code(storm.draw_contract(s, shape))
             for s in (1, 2, SEED)]
    assert len({len(c) for c in codes}) == 1
    assert len(set(codes)) == 3


def test_reference_has_every_path_once():
    c = storm.draw_contract(SEED, _config()["contract"])
    paths = path_set(c["slots"], c["adds"], c["sha3_slot"])
    assert len(paths) == 16
    assert all(c["sha3_slot"] in dict(p) for p in paths)


def test_explored_set_equals_reference(bench):
    r = _run(bench)
    assert r["correct"] is True
    assert r["checks"] == {"paths_missing": {"value": 0, "limit": 0},
                           "paths_unexpected": {"value": 0, "limit": 0}}
    assert r["attempted"] >= 1 and r["failed"] == 0
    assert set(r["metrics"]) == {"paths_per_s", "setup_s"}
    assert list(r)[-1] == "checks"


def test_every_exploration_is_of_a_new_contract(bench):
    d = storm.Driver(_config(), {"warmup_max": 1}, SEED, run.ROOT)
    drawn = [storm.build_code(d._draw(d._rng)) for _ in range(3)]
    drawn.append(storm.build_code(d._draw(d._warm_rng)))
    assert len(set(drawn)) == 4
    again = storm.Driver(_config(), {"warmup_max": 1}, SEED, run.ROOT)
    assert storm.build_code(again._draw(again._rng)) == drawn[0]


def test_control_is_not_correct(bench, monkeypatch):
    """The configuration's control merges paths that end with the same
    accumulator, which breaks the guarantee that no path is merged
    away."""
    monkeypatch.setattr(storm, "explored_paths", storm.explored_paths)
    control.install("path_storm", _config())
    # every exploration compared: at k = 4 only some collide (at
    # k = 13 every one does: 8192 paths, sums of at most 13 x 255)
    r = _run(bench, run.merged(SMALL, {"reference_sample": 1 << 20}),
             seed=COLLIDING)
    assert r["correct"] is False
    assert r["checks"]["paths_missing"]["value"] > 0


def _patched_paths(monkeypatch, fault):
    real = storm.explored_paths
    monkeypatch.setattr(storm, "explored_paths",
                        lambda states: fault(real(states)))


def _alter_one_value(paths):
    writes = dict(paths[0])
    slot = next(iter(writes))
    writes[slot] += 1
    return [frozenset(writes.items())] + paths[1:]


@pytest.mark.parametrize("fault", [
    # a step that returns its state unchanged: no path wrote anything
    lambda paths: [frozenset()] * len(paths),
    # half of the batch left out
    lambda paths: paths[:len(paths) // 2],
    # an answer altered where it is produced
    _alter_one_value,
], ids=["state_unchanged", "half_left_out", "answer_altered"])
def test_faults_are_not_correct(bench, monkeypatch, fault):
    _patched_paths(monkeypatch, fault)
    assert _run(bench)["correct"] is False


def test_four_chip_cell_engages_the_lane_mesh(bench):
    """A chips: 4 cell is one new workloads entry: the same config and
    mix under the program's default mesh policy (tpu_mesh -1) shard
    the lane planes over all four devices."""
    import jax

    assert len(jax.devices()) == 4
    four = dict(bench, workloads=bench["workloads"] + [{
        "name": "storm.k13.mesh4", "config": "path_storm",
        "traffic": "storm_fresh", "chips": 4, "why": "rehearsal"}])
    mesh = {"contract": {"k": 6}, "explore": {"tpu_lanes": 64}}
    r = _run(four, mesh, cell="storm.k13.mesh4")
    assert r["correct"] is True and r["device"]["count"] == 4

    from mythril_tpu.parallel.mesh import LANES_AXIS

    d = storm.Driver(_config(mesh), {"warmup_max": 1}, SEED, run.ROOT)
    engines = list(d._explore(d._draw(random.Random(SEED)))
                   .laser._lane_engines.values())
    sharded = [e for e in engines if e.mesh is not None]
    assert sharded, "no engine ran on the lane mesh"
    st = sharded[0]._acquire_state()
    try:
        sh = st.pc.sharding
        assert LANES_AXIS in tuple(sh.spec)
        assert len(sh.device_set) == 4
    finally:
        sharded[0]._release_state(st)
