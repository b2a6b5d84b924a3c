"""Device-mesh lane sharding: SPMD path exploration across TPU chips.

The reference is single-process and parallelizes contract analysis by
launching many OS processes (tests/integration_tests/parallel_test.py:8-16
in /root/reference). This module is the TPU-native replacement promised by
SURVEY.md §2.10 (contract-level + distributed-backend rows): the lane batch
(ops/stepper.LaneState) is sharded over a 1-D `lanes` axis of a
jax.sharding.Mesh, the stepper loop runs per-device inside shard_map
(no cross-chip traffic in the data-parallel stepping itself — the
stepper's op-family gates reduce over the local shard only), and the few
global decisions (how many lanes are live, when to rebalance/compact)
ride ICI collectives (psum/all_gather) inside shard_map.

Multi-host corpus sharding (one contract set per host over DCN) composes on
top: each host builds its own mesh over local devices and runs an
independent corpus shard; nothing in this module assumes a single process.
"""

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..ops import stepper
from ..ops.stepper import CompiledCode, LaneState, Status

LANES_AXIS = "lanes"


def make_mesh(n_devices: Optional[int] = None) -> Mesh:
    """1-D mesh over the first n_devices (default: all)."""
    devs = jax.devices()
    if n_devices is not None:
        devs = devs[:n_devices]
    return Mesh(np.array(devs), (LANES_AXIS,))


def lane_sharding(mesh: Mesh) -> NamedSharding:
    """Shard the leading (lane) axis; replicate everything smaller."""
    return NamedSharding(mesh, P(LANES_AXIS))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def shard_lanes(state: LaneState, mesh: Mesh) -> LaneState:
    """Place every per-lane array with its leading axis split across the
    mesh. Lane count must be divisible by mesh size."""
    n = state.pc.shape[0]
    n_dev = mesh.devices.size
    assert n % n_dev == 0, f"{n} lanes not divisible by {n_dev} devices"
    sh = lane_sharding(mesh)
    return jax.tree_util.tree_map(
        lambda x: jax.device_put(x, sh), state
    )


def replicate_code(code: CompiledCode, mesh: Mesh) -> CompiledCode:
    rep = replicated(mesh)
    return jax.tree_util.tree_map(lambda x: jax.device_put(x, rep), code)


def sharded_run(
    code: CompiledCode, state: LaneState, max_steps: int, mesh: Mesh
) -> LaneState:
    """Run the stepper SPMD over the mesh via shard_map: each device
    executes its own while_loop over its lane shard with NO cross-chip
    traffic — the stepper's op-family `lax.cond` gates reduce over the
    LOCAL shard only, so a device whose lanes never touch memory this
    step skips the memory block even if another device's lanes need it
    (per-device divergence, strictly better than a global gate), and
    each device's loop exits as soon as its own lanes halt."""
    code_specs = jax.tree_util.tree_map(lambda _: P(), code)
    state_specs = jax.tree_util.tree_map(lambda _: P(LANES_AXIS), state)

    @partial(
        shard_map,
        mesh=mesh,
        in_specs=(code_specs, state_specs),
        out_specs=state_specs,
        # the stepper's while_loop has no replication rule
        check_vma=False,
    )
    def _run(code_local, state_local):
        return stepper.run(code_local, state_local, max_steps)

    return jax.jit(_run)(code, state)


def live_lane_counts(state: LaneState, mesh: Mesh):
    """(per-device running-lane counts, global total) via ICI psum inside
    shard_map — the lane-engine heartbeat used for rebalance decisions."""

    @partial(
        shard_map,
        mesh=mesh,
        in_specs=P(LANES_AXIS),
        out_specs=(P(LANES_AXIS), P()),
    )
    def _count(status):
        local = jnp.sum(status == Status.RUNNING).astype(jnp.int32)
        total = lax.psum(local, LANES_AXIS)
        return local[None], total

    per_dev, total = jax.jit(_count)(state.status)
    return np.asarray(per_dev), int(total)


def compact_lanes(state: LaneState, order=None) -> LaneState:
    """Pack live lanes to the front (device-wide gather). Dead lanes'
    slots become refill targets for the host worklist spill — the
    batched analog of the reference's worklist pop/push.

    A global argsort on status is a cheap all-to-all style reshuffle; on a
    mesh it routes over ICI automatically via XLA's gather partitioning."""
    if order is None:
        running = (state.status == Status.RUNNING).astype(jnp.int32)
        order = jnp.argsort(-running, stable=True)
    return jax.tree_util.tree_map(lambda x: x[order], state)


def steal_balance(state: LaneState, mesh: Mesh) -> LaneState:
    """Work-stealing rebalance: globally sort lanes by liveness and deal
    them round-robin across devices so every shard holds an equal share of
    running lanes. One all-to-all-ish resharding over ICI, amortized over
    many pure-SPMD steps."""
    n = state.pc.shape[0]
    n_dev = mesh.devices.size
    running = (state.status == Status.RUNNING).astype(jnp.int32)
    order = jnp.argsort(-running, stable=True)
    # deal sorted lanes round-robin: lane i of the sorted order goes to
    # device i % n_dev, slot i // n_dev — keeps live lanes evenly spread
    dealt = jnp.reshape(
        jnp.reshape(order, (n // n_dev, n_dev)).T, (n,)
    )
    compacted = jax.tree_util.tree_map(lambda x: x[dealt], state)
    return shard_lanes(compacted, mesh)
