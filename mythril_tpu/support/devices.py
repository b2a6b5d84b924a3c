"""JAX device-platform setup shared by the analyzer, the test suite and
the driver entry points."""

import logging
import os
from pathlib import Path

from ..exceptions import CriticalError

log = logging.getLogger(__name__)

#: the compile cache's fixed home in this checkout, when
#: JAX_COMPILATION_CACHE_DIR does not place it
CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


class DeviceUnavailable(CriticalError):
    """The lane engine was asked for (``--tpu-lanes N``, N > 0) but the
    JAX device cannot execute: the run fails, it never finishes on the
    host interpreter instead."""


def default_tpu_lanes() -> int:
    """Lane width the auto (-1) tpu_lanes setting resolves to: batched
    lanes on an accelerator, host-only on a CPU platform."""
    import jax

    return 0 if jax.devices()[0].platform == "cpu" else 64


#: result of the one-time device-execution probe: None = not yet run,
#: "" = the device executes, otherwise why it does not
_DEVICE_ERROR = None


def device_error() -> str:
    """Why the default JAX device cannot execute ("" when it can).
    Probed with an actual executed op, ONCE per process: device
    *enumeration* can succeed while execution is broken (e.g. a libtpu
    client/terminal version mismatch fails only at the first executed
    primitive)."""
    global _DEVICE_ERROR
    if _DEVICE_ERROR is None:
        try:
            import jax
            import jax.numpy as jnp

            jax.block_until_ready(jnp.zeros((), jnp.int32) + 1)
            _DEVICE_ERROR = ""
        except Exception as e:  # any backend bring-up failure
            _DEVICE_ERROR = f"{type(e).__name__}: {e}"
    return _DEVICE_ERROR


def require_device(lanes: int) -> None:
    """Raise DeviceUnavailable when `lanes` > 0 asks for the lane engine
    and the device cannot execute: an explicit width never finishes on
    the host interpreter behind the user's back."""
    err = device_error() if lanes > 0 else ""
    if err:
        raise DeviceUnavailable(
            f"--tpu-lanes {lanes} needs a JAX device that executes, "
            f"and none does ({err}); pass --tpu-lanes 0 to analyze on "
            "the host interpreter")


def note_device_error(counter: str, what: str, exc: BaseException) -> None:
    """Count (SolverStatistics.<counter>) and report at warning level a
    device error the caller recovers from on the host, so that no such
    recovery goes unseen. Interrupts and out-of-memory propagate."""
    if isinstance(exc, (KeyboardInterrupt, MemoryError)):
        raise exc
    from ..smt.solver.solver_statistics import SolverStatistics

    SolverStatistics().bump(**{counter: 1})
    log.warning("%s failed on the device (%s: %s); continuing on the host",
                what, type(exc).__name__, exc)


def effective_tpu_lanes() -> int:
    """args.tpu_lanes with the auto sentinel (<0) resolved — and cached
    back onto the run context so every later reader sees the same
    resolution."""
    from .support_args import args

    lanes = args.tpu_lanes
    if lanes is None or lanes < 0:
        lanes = default_tpu_lanes()
        args.tpu_lanes = lanes
    return lanes


def enable_compile_cache() -> None:
    """Persistent XLA compilation cache: the lane-engine kernels take
    seconds to compile; caching them across processes makes CLI runs
    pay it once per kernel shape, not once per invocation.

    Where JAX_COMPILATION_CACHE_DIR is set, JAX already keeps its cache
    there and no other directory is set here. Otherwise the cache lives
    at <checkout>/.jax_cache: a fixed path, because the path is part of
    the cache key."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    # cache even sub-second kernels: the lane engine compiles dozens
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)


def force_virtual_cpu(n_devices: int) -> None:
    """Configure JAX as an n-device virtual CPU platform (the test
    suite's mesh). Must run before the first backend is created."""
    import jax

    # XLA_FLAGS reaches child processes the tests start; the config
    # update covers this one
    flag = f"--xla_force_host_platform_device_count={n_devices}"
    if flag not in os.environ.get("XLA_FLAGS", ""):
        os.environ["XLA_FLAGS"] = os.environ.get("XLA_FLAGS", "") + " " + flag
    os.environ["JAX_PLATFORMS"] = "cpu"
    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_num_cpu_devices", n_devices)
    devices = jax.devices()
    if devices[0].platform != "cpu" or len(devices) < n_devices:
        raise RuntimeError(
            f"virtual-CPU setup failed: {len(devices)} "
            f"{devices[0].platform} devices (backend created earlier?)")


def ensure_devices(n_devices: int) -> None:
    """Raise unless the backend provides n devices that execute."""
    import jax

    devices = jax.devices()
    if len(devices) < n_devices:
        raise RuntimeError(
            f"need {n_devices} devices, JAX has {len(devices)} "
            f"({devices[0].platform})")
    err = device_error()
    if err:
        raise RuntimeError(f"the {devices[0].platform} device does not "
                           f"execute: {err}")
