"""Storm traffic on the lane mesh: the storm generator's closed loop of
new seeded contracts, explored with the lane planes sharded over every
chip of the host by the program's default `--tpu-mesh -1` policy.

Before anything compiles, the generator asks the program for its sharded
plane initialiser (mythril_tpu.parallel.mesh.init_planes) and checks it at
the smallest meshed width: every lane plane must come back as one
shard of n/chips rows on each chip. A program that builds its planes
on one chip and copies them out, or has no initialiser, leaves at once
with a one-line message and no result line.

Besides the storm's checks, `explorations_off_mesh` counts the
window's explorations in which no lane sweep ran on the mesh, or the
last mesh did not span the cell's chips (the program's mesh_sweeps and
mesh_devices counters). A traced run's record carries the window's
change of the mesh counters; every run's notes give each chip's peak
device memory.

Mix parameters (traffic/<mix>.json, "generator": "storm_mesh"): those
of the storm generator.
"""

from . import storm

#: the width the plane initialiser is checked at: 8 lanes on each of four
#: chips, the narrowest shard the mesh policy takes
PROBE_ROWS_PER_CHIP = 8

#: SolverStatistics counters of the lane mesh
MESH_COUNTERS = ("mesh_sweeps", "mesh_pull_bytes")


def mesh_counters() -> dict:
    from mythril_tpu.smt.solver.solver_statistics import SolverStatistics

    c = SolverStatistics().batch_counters()
    return {k: c.get(k, 0) for k in MESH_COUNTERS + ("mesh_devices",)}


def check_sharded_planes(chips: int) -> None:
    """Raise SystemExit unless the program builds its lane planes
    sharded: every per-lane record plane (rank 2 or more, leading axis
    the lane count) in `chips` shards of equal rows, one per chip."""
    try:
        from mythril_tpu.parallel.mesh import init_planes, make_mesh
    except ImportError as e:
        raise SystemExit(
            "storm_mesh: the program has no sharded lane planes "
            f"(mythril_tpu.parallel.mesh.init_planes): {e}") from None
    n = PROBE_ROWS_PER_CHIP * chips
    planes = init_planes(make_mesh(chips), n)
    for name, leaf in zip(planes._fields, planes):
        if leaf.ndim < 2 or leaf.shape[0] != n:
            continue
        rows = sorted(s.data.shape[0] for s in leaf.addressable_shards)
        devices = {s.device for s in leaf.addressable_shards}
        if rows != [n // chips] * chips or len(devices) != chips:
            raise SystemExit(
                f"storm_mesh: lane plane {name} of {n} rows is not "
                f"sharded over {chips} chips: shards of {rows} rows on "
                f"{len(devices)} devices")


class Driver(storm.Driver):
    """storm.Driver, with the lane mesh checked before set-up and
    counted in the window."""

    def __init__(self, config: dict, mix: dict, seed: int, root):
        super().__init__(config, mix, seed, root)
        self.chips = config["chips"]
        check_sharded_planes(self.chips)
        self._mesh0 = None
        self.off_mesh = 0

    def warm_up(self, clock) -> None:
        super().warm_up(clock)
        self._mesh0 = mesh_counters()

    def run_one(self) -> None:
        before = mesh_counters()["mesh_sweeps"]
        super().run_one()
        after = mesh_counters()
        if after["mesh_sweeps"] == before \
                or after["mesh_devices"] != self.chips:
            self.off_mesh += 1

    def record(self) -> dict:
        now = mesh_counters()
        base = self._mesh0 or dict.fromkeys(now, 0)
        mesh = {k: now[k] - base[k] for k in MESH_COUNTERS}
        mesh["mesh_devices"] = now["mesh_devices"]
        return dict(super().record(), mesh=mesh)

    @property
    def notes(self) -> dict:
        """Each chip's peak device memory, where the backend reports it."""
        import jax

        return {"chip_peak_bytes": [
            (d.memory_stats() or {}).get("peak_bytes_in_use")
            for d in jax.devices()[:self.chips]]}

    def checks(self) -> list:
        return super().checks() + [
            ("explorations_off_mesh", self.off_mesh, 0)]
