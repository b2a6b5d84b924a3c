#!/usr/bin/env python3
"""Run a cell's control: the cell's timed path with the configuration's
"control" settings laid over it, or with benchmarks/controls/<config>.py
installed (its install(config) wraps a part of the timed path), which
break one guarantee that the configuration states. Its comparison with the reference has to come out
not correct on every seed. The benchmark's own runs never run it.

    python3 benchmarks/control.py --workload <cell> --seconds <s> \\
        --seeds <n> [<n> ...]

One process, one line per seed: the seed, `correct` and the checks.
It exits non-zero if any seed reads correct.
"""

import argparse
import importlib.util
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmarks import run  # noqa: E402


def install(name: str, config: dict) -> None:
    """Install benchmarks/controls/<name>.py, where there is one."""
    path = run.ROOT / "benchmarks" / "controls" / f"{name}.py"
    if path.exists():
        spec = importlib.util.spec_from_file_location(
            "benchmarks.controls." + name, path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        mod.install(config)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    a = ap.parse_args(argv)
    bench = run.load_json(run.ROOT / "BENCHMARK.json")
    w, config, _ = run.cell_inputs(bench, a.workload, run.ROOT)
    install(w["config"], config)
    caught = True
    for seed in a.seeds:
        r = run.run_cell(bench, a.workload, seed, a.seconds, False,
                         config_override=config.get("control"),
                         t_start=time.monotonic())
        caught &= r["correct"] is False
        print(json.dumps({"seed": seed, "correct": r["correct"],
                          "attempted": r["attempted"],
                          "checks": r["checks"]}), flush=True)
    return 0 if caught else 1


if __name__ == "__main__":
    sys.exit(main())
