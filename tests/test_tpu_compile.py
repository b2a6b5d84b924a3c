"""Compile-only rehearsals of the lane kernels for a described TPU v5e
(on-chip-measurement guide, section 2): the TPU compiler refuses here,
at no chip time, what it would refuse on the chip. Nothing runs.

The topology is described inside a fixture, never at import: only one
process may load libtpu, and every xdist worker imports this file."""

import jax
import pytest
from jax.sharding import SingleDeviceSharding

#: one v5e chip's HBM
HBM_BYTES = 16 * 1024 ** 3


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent
    # cache but cannot be read back without one: keep the cache off
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield SingleDeviceSharding(topo.devices[0])
    finally:
        jax.config.update("jax_enable_compilation_cache", was_on)
        compilation_cache.reset_cache()


def _shapes(tree, sharding):
    """Each leaf as a shape on `sharding`; Python scalars (traced the
    way jit traces them) as weak-typed scalars."""

    def one(x):
        if hasattr(x, "shape"):
            return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding)
        return jax.ShapeDtypeStruct((), jax.numpy.asarray(x).dtype,
                                    sharding=sharding, weak_type=True)

    return jax.tree_util.tree_map(one, tree)


def window_exec_args(n_lanes, code_len, seed_bucket, sharding):
    """Shapes and statics of one production `_window_exec` dispatch at
    the sweep's defaults (window 256, step budget 8192), as
    lane_engine._warm_one_inner builds them."""
    from mythril_tpu.laser import lane_engine as le
    from mythril_tpu.ops import symstep
    from mythril_tpu.ops.stepper import _code_bucket

    eng = le.LaneEngine(n_lanes=n_lanes)
    st = jax.eval_shape(lambda: symstep.init_sym_lanes(n_lanes))
    cc = le._compiled_code(b"\x00" * _code_bucket(code_len), ())
    big = seed_bucket > min(16, n_lanes)
    i32buf, u8buf, k, pv = eng._pack_window(
        [], [None] * n_lanes, list(range(n_lanes)), [],
        int(st.calldata.shape[1]), big=big)
    visited = jax.ShapeDtypeStruct((cc.packed.shape[0],), bool)
    arrays = _shapes((st, cc, i32buf, u8buf, eng.exec_table,
                      eng.taint_table), sharding)
    return (*arrays, le.DEFAULT_WINDOW, k, le.DEFAULT_STEP_BUDGET, pv,
            _shapes(visited, sharding), _shapes(eng._resume_flag, sharding))


def sym_run_args(n_lanes, code_len, sharding):
    from mythril_tpu.laser import lane_engine as le
    from mythril_tpu.ops import symstep
    from mythril_tpu.ops.stepper import _code_bucket

    st = jax.eval_shape(lambda: symstep.init_sym_lanes(n_lanes))
    cc = le._compiled_code(b"\x00" * _code_bucket(code_len), ())
    return _shapes(cc, sharding), _shapes(st, sharding)


def test_window_exec_compiles_at_cli_default(one_chip):
    """The fused window at the width `--tpu-lanes -1` resolves to on a
    chip (64 lanes), on a 1 KiB contract."""
    from mythril_tpu.laser import lane_engine as le

    args = window_exec_args(64, 1024, 16, one_chip)
    mem = le._window_exec.lower(*args).compile().memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes)
    assert 0 < total < HBM_BYTES, total


def test_sym_run_32768_lanes_fits_one_chip(one_chip):
    """The symbolic stepper at the state phase's width (32768 lanes)
    fits one v5e's HBM: arguments, outputs and temporaries together."""
    from mythril_tpu.ops import symstep

    code, st = sym_run_args(32768, 1024, one_chip)
    compiled = jax.jit(symstep.sym_run, static_argnums=(2,)).lower(
        code, st, 64).compile()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes)
    assert 0 < total < HBM_BYTES, total
