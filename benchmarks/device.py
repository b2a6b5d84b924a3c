"""The device a run measures, and the compile seconds JAX reports."""


class NoAccelerator(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def require_chips(chips: int) -> dict:
    """The device block of the result line; raises NoAccelerator unless
    JAX's default devices are TPUs, at least `chips` of them. It never
    falls back to the CPU."""
    import jax

    devices = jax.devices()
    d = devices[0]
    if d.platform != "tpu":
        raise NoAccelerator(
            f"no TPU: JAX's default device is {d.platform} "
            f"({d.device_kind}); the benchmark measures only a TPU")
    if len(devices) < chips:
        raise NoAccelerator(
            f"the cell needs {chips} chips and JAX finds {len(devices)}")
    return {"platform": d.platform, "kind": d.device_kind, "count": chips}


def memory_peak_bytes(chips: int) -> int:
    """Peak bytes in use on the fullest of the cell's chips."""
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.devices()[:chips]]
    return max(peaks)


class CompileClock:
    """Backend compile seconds (persistent-cache loads included) and
    persistent-cache hits and misses, from JAX's monitoring events (a
    copy of chip_smoke.CompileClock)."""

    def __init__(self):
        import jax

        self.seconds = 0.0
        self.cache_hits = 0
        self.cache_misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, event, duration_secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration_secs

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1
