#!/usr/bin/env python3
"""Record a corpus configuration's answer key: the canonical issue list
of each contract, analysed by the host interpreter with lanes off (the
program's declared reference path) under the configuration's settings,
with each contract's own metadata hash.

    JAX_PLATFORMS=cpu python3 benchmarks/tools/record_corpus_key.py \\
        benchmarks/configs/<config>.json

It writes the file the configuration's "reference" names, and refuses
to where the plain EVM (benchmarks/reference/evm.py) refutes one of the
issues' transaction sequences. The benchmark's runs only read the key.
"""

import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmarks.reference.evm import replay_issue  # noqa: E402
from benchmarks.run import load_json, merged  # noqa: E402
from benchmarks.traffic import corpus  # noqa: E402


def main(config_path: str) -> int:
    config = load_json(Path(config_path))
    host = merged(config, {"analyzer": {"tpu_lanes": 0}})
    issues = {}
    for name, code in corpus.corpus(config, ROOT):
        report = corpus.analyze_report(name, code, host)
        if report.exceptions:
            print(f"{name}: the analysis raised", file=sys.stderr)
            return 1
        found = json.loads(report.as_json())
        replays = [replay_issue(i) for i in found.get("issues") or []]
        if "refuted" in replays:
            print(f"{name}: the plain EVM refutes an issue", file=sys.stderr)
            return 1
        issues[name] = corpus.canon(found)
        print(name, len(issues[name]), replays, flush=True)
    key = {
        "what": "canonical issue list (benchmarks/traffic/corpus.canon) "
                "of each contract under the configuration's settings",
        "made_by": "the host interpreter with lanes off (tpu_lanes 0), "
                   "the program's declared reference path, on a CPU: "
                   "benchmarks/tools/record_corpus_key.py",
        "issues": issues,
    }
    with open(ROOT / config["reference"], "w") as f:
        json.dump(key, f, indent=1, sort_keys=True)
        f.write("\n")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
