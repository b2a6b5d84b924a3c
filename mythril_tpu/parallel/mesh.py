"""Device-mesh lane sharding: one exploration's lanes over several chips.

What the lane engine runs (laser/lane_engine.py): `pick_mesh` resolves
the `--tpu-mesh` policy to a 1-D `lanes` mesh (`make_mesh`), and a
meshed `LaneEngine` runs the SAME jitted programs as on one device
(`_window_exec`, the retire and table gathers) under GSPMD. The lane
planes are born sharded (`init_planes`: each chip materialises only its
own rows), the code tensors and the packed host buffers are replicated
(`replicated`), and the collectives are GSPMD's own: the ones XLA
inserts where a window's cumsum/scatter/sort phases read across lanes.
The host pulls (window logs, retired rows, the coverage bitmap) gather
from every chip; each is a `mesh.pull` span with its bytes.

The `shard_map` helpers below (`shard_lanes`, `replicate_code`,
`sharded_run`, `live_lane_counts`, `compact_lanes`, `steal_balance`)
drive the concrete stepper (ops/stepper) with per-device loops; no
engine path calls them, only tests/test_parallel.py does.

Multi-host corpus sharding (one contract set per host over DCN) composes
on top: each host builds its own mesh over local devices and runs an
independent corpus shard; nothing in this module assumes a single
process.
"""

from functools import lru_cache, partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..ops import stepper, symstep
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


#: SymLaneState fields of n_lanes rows that are not lane planes: the
#: free-slot stack holds slot numbers, and every window program returns
#: it replicated, so it is built replicated too
_NOT_PLANES = frozenset({"free_slots"})


def plane_shardings(mesh: Mesh, n_lanes: int, **lane_kwargs):
    """The placement of every SymLaneState leaf on the mesh: a lane
    plane (leading axis n_lanes) splits its rows over `lanes`; every
    other leaf is replicated. The window programs return the same
    placement, so a pooled state and a fresh one share one compile."""
    shapes = jax.eval_shape(
        partial(symstep.init_sym_lanes, n_lanes, **lane_kwargs))
    lanes, rep = lane_sharding(mesh), replicated(mesh)
    return type(shapes)(*(
        lanes if name not in _NOT_PLANES and s.ndim
        and s.shape[0] == n_lanes else rep
        for name, s in zip(shapes._fields, shapes)))


@lru_cache(maxsize=None)
def _plane_init(mesh: Mesh, n_lanes: int, lane_kwargs: tuple):
    kwargs = dict(lane_kwargs)

    def sharded_lane_planes():
        return symstep.init_sym_lanes(n_lanes, **kwargs)

    return jax.jit(sharded_lane_planes, out_shardings=plane_shardings(
        mesh, n_lanes, **kwargs))


def init_planes(mesh: Mesh, n_lanes: int, **lane_kwargs):
    """symstep.init_sym_lanes built sharded: one jitted program whose
    outputs are placed by `plane_shardings`, so each chip materialises
    only its own rows and no whole plane ever exists on one chip."""
    return _plane_init(mesh, n_lanes,
                          tuple(sorted(lane_kwargs.items())))()


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
