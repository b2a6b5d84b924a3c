"""The lane mesh as the engine runs it (parallel/mesh.py): lane planes
built sharded, a storm explored on the mesh that finds exactly the
reference's paths and the same end states as one device and the host
interpreter, a mesh that compiles nothing for a second new contract,
and the mesh's spans and counters. On the virtual CPU mesh of
conftest.py; each test skips where fewer devices exist."""

import random

import jax
import pytest

from benchmarks.reference.path_storm import path_set
from benchmarks.traffic import storm
from mythril_tpu.laser import lane_engine
from mythril_tpu.ops import symstep
from mythril_tpu.parallel import mesh as pmesh
from mythril_tpu.smt.solver.solver_statistics import SolverStatistics
from mythril_tpu.support.support_args import args as global_args
from mythril_tpu.support.telemetry import trace

#: a storm of 2^6 paths, as the benchmark's storm generator draws them
SHAPE = {"k": 6, "push_bytes": {"offset": 1, "slot": 2, "add": 1},
         "sha3_slot": 99}
SEED = 2 ** 35 + 3

_COMPILES = []
jax.monitoring.register_event_duration_secs_listener(
    lambda event, _s, **_kw: _COMPILES.append(event)
    if event == "/jax/core/compile/backend_compile_duration" else None)


@pytest.fixture
def four_devices():
    if len(jax.devices()) < 4:
        pytest.skip("needs four devices for the lane mesh")
    return pmesh.make_mesh(4)


@pytest.fixture(autouse=True)
def _restore_args():
    saved = dict(vars(global_args))
    yield
    vars(global_args).clear()
    vars(global_args).update(saved)


def _explore(contract: dict, tpu_lanes: int, tpu_mesh: int):
    """One LASER exploration of a storm contract, as the benchmark
    drives it: the program's per-analysis reset, then SymExecWrapper."""
    from mythril_tpu.analysis.symbolic import SymExecWrapper
    from mythril_tpu.ethereum.evmcontract import EVMContract
    from mythril_tpu.orchestration.mythril_analyzer import (
        reset_analysis_state,
    )

    global_args.tpu_lanes = tpu_lanes
    global_args.tpu_mesh = tpu_mesh
    reset_analysis_state()
    sym = SymExecWrapper(
        EVMContract(code=storm.build_code(contract).hex(), name="storm"),
        address=storm._ADDRESS, strategy="bfs", max_depth=8192,
        execution_timeout=86400, create_timeout=10, transaction_count=1,
        compulsory_statespace=False, run_analysis_modules=False)
    return storm.explored_paths(sym.laser.open_states)


def _contract(seed):
    return storm.draw_contract(seed, SHAPE)


@pytest.mark.parametrize("n_lanes", [32, 64])
def test_planes_are_built_sharded(four_devices, n_lanes):
    planes = pmesh.init_planes(four_devices, n_lanes)
    devices = set(four_devices.devices.flat)
    for name, leaf in zip(planes._fields, planes):
        shards = leaf.addressable_shards
        assert {s.device for s in shards} == devices, name
        rows = [s.data.shape[0] if s.data.ndim else None for s in shards]
        if name == "free_slots" or not leaf.ndim:
            # the free-slot stack and the scalars: replicated
            assert leaf.sharding.is_fully_replicated, name
            continue
        assert leaf.shape[0] == n_lanes, name
        assert rows == [n_lanes // 4] * 4, (name, rows)
    ref = jax.device_get(symstep.init_sym_lanes(n_lanes))
    for got, want in zip(jax.device_get(planes), ref):
        assert (got == want).all()


def test_storm_on_the_mesh_finds_the_reference_paths(four_devices):
    c = _contract(SEED)
    on_mesh = _explore(c, 64, -1)
    assert SolverStatistics().mesh_devices == len(jax.devices())
    assert sorted(map(sorted, on_mesh)) == sorted(
        map(sorted, path_set(c["slots"], c["adds"], c["sha3_slot"])))
    assert len(on_mesh) == 1 << SHAPE["k"]
    one_device = _explore(c, 64, 0)
    host = _explore(c, 0, 0)
    assert sorted(map(sorted, on_mesh)) == sorted(map(sorted, one_device))
    assert sorted(map(sorted, on_mesh)) == sorted(map(sorted, host))


def test_second_new_contract_compiles_nothing_on_the_mesh(four_devices):
    rng = random.Random(SEED)
    _explore(_contract(rng.getrandbits(64)), 64, 4)
    before = len(_COMPILES)
    paths = _explore(_contract(rng.getrandbits(64)), 64, 4)
    assert len(paths) == 1 << SHAPE["k"]
    assert len(_COMPILES) == before
    assert SolverStatistics().mesh_devices == 4


@pytest.fixture
def traced():
    was = trace.enabled()
    trace.clear()
    trace.set_enabled(True)
    yield
    trace.set_enabled(was)
    trace.clear()


def _mesh_counters():
    c = SolverStatistics().batch_counters()
    return c["mesh_sweeps"], c["mesh_pull_bytes"]


def _pull_spans():
    return [attrs for _p, name, _t, _d, _tid, attrs
            in trace.snapshot_events() if name == "mesh.pull"]


def test_mesh_spans_and_counters(four_devices, traced, monkeypatch):
    # no pooled planes: this exploration builds its own
    monkeypatch.setattr(lane_engine, "_STATE_POOL", {})
    sweeps, nbytes = _mesh_counters()
    _explore(_contract(SEED + 1), 64, 4)
    pulls = _pull_spans()
    assert pulls and all(a["bytes"] > 0 for a in pulls)
    sweeps1, nbytes1 = _mesh_counters()
    assert sweeps1 > sweeps
    assert nbytes1 - nbytes == sum(a["bytes"] for a in pulls)
    inits = [attrs for _p, name, _t, _d, _tid, attrs
             in trace.snapshot_events() if name == "lane.init"]
    assert inits and all(a["shards"] == 4 for a in inits)

    trace.clear()
    _explore(_contract(SEED + 2), 64, 0)
    assert not _pull_spans()
    assert _mesh_counters() == (sweeps1, nbytes1)
