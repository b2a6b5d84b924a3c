"""The process's garbage-collection schedule across analyses.

A warmed process tracks 0.8-0.95 M container objects that live as long
as it does: JAX's traced programs and the program's own caches. An
exploration adds about a hundred tracked objects per path, many of them
in reference cycles, so CPython's full (generation 2) collection runs
one to three times per large exploration, and each run walks the whole
warmed heap again although none of it can be garbage.

``before_analysis`` runs at the top of every analysis
(``LaserEVM.sym_exec``). When XLA compiled anything since the last
freeze, or at the process's first analysis, it collects what is not yet
frozen and freezes the rest (``gc.freeze``): the collector never walks
those objects again. It then scales the generation-2 threshold to the
frozen heap, so that a full collection runs about once per frozen
heap's worth of objects promoted since the last one. Young collections
are left alone and nothing is disabled. Once the process stops
compiling, no analysis freezes again.

Counters (``SolverStatistics``): ``gc_freezes`` (freezes run),
``gc_frozen`` (objects frozen) and ``gc_full`` (full collections).
"""

import gc
import sys
import threading

#: CPython's generation-2 threshold: the floor of the scaled one
DEFAULT_THRESHOLD2 = gc.get_threshold()[2]
#: jax.monitoring's event for one backend compile (or persistent-cache
#: load) of an XLA program
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

_lock = threading.Lock()
#: did the long-lived heap grow since the last freeze: a first analysis,
#: JAX's arrival, or a compile
_grown = True
_listening = False


def _on_duration(event: str, duration_secs: float, **_) -> None:
    global _grown
    if event == COMPILE_EVENT:
        _grown = True


def _listen() -> None:
    """Hear JAX's compile events once JAX is loaded. JAX arriving grows
    the long-lived heap as a compile does; it is never imported here."""
    global _grown, _listening
    if _listening or "jax" not in sys.modules:
        return
    import jax

    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    _listening = _grown = True


def scaled_threshold2(frozen: int) -> int:
    """The generation-2 threshold that spaces full collections about a
    frozen heap's worth of promoted objects apart."""
    t0, t1, _ = gc.get_threshold()
    return max(DEFAULT_THRESHOLD2, frozen // (t0 * t1))


def before_analysis() -> bool:
    """Freeze the heap if it grew since the last freeze, then scale the
    generation-2 threshold to it; True when it froze."""
    global _grown
    from ..smt.solver.solver_statistics import SolverStatistics

    with _lock:
        _listen()
        if not _grown:
            return False
        _grown = False
        gc.collect()
        gc.freeze()
        frozen = gc.get_freeze_count()
        t0, t1, _ = gc.get_threshold()
        gc.set_threshold(t0, t1, scaled_threshold2(frozen))
    stats = SolverStatistics()
    stats.bump(gc_freezes=1)
    stats.gc_frozen = frozen
    return True

