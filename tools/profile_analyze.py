#!/usr/bin/env python3
"""Run `myth` under a jax.profiler trace, with the program's spans on.

    python3 tools/profile_analyze.py OUT_DIR analyze -f contract.sol.o \\
        --bin-runtime --tpu-lanes 64

Everything after OUT_DIR is `myth`'s own command line. The profile is
written under OUT_DIR/plugins/profile/<time>/ (an .xplane.pb, read by
TensorBoard's profile plugin or jax.profiler.ProfileData, and a
perfetto_trace.json.gz for https://ui.perfetto.dev): the device's ops
and every program span (docs/observability.md) on the profiler's one
clock, each span on the thread that ran it. The Python tracer stays
off: it would record every call the interpreter makes.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    if len(argv) < 2:
        print(__doc__, file=sys.stderr)
        return 2
    out_dir, myth_args = argv[0], argv[1:]
    import jax

    from mythril_tpu.interfaces import cli
    from mythril_tpu.support.telemetry import trace

    trace.set_enabled(True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    sys.argv = ["myth"] + myth_args
    code = 0
    with jax.profiler.trace(out_dir, create_perfetto_trace=True,
                            profiler_options=options):
        try:
            cli.main()
        except SystemExit as e:
            code = e.code if isinstance(e.code, int) else 1
    return code


if __name__ == "__main__":
    sys.exit(main())
