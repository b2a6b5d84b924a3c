"""Corpus traffic: the configuration's bytecode corpus analysed one
contract at a time in a closed loop, through MythrilAnalyzer.fire_lasers
(the `myth analyze` path), in the fixed alphabetical order the corpus
runners use, cycling.

Each submission has its solc metadata hash redrawn from the seed: the
code runs unchanged, but every analysis is of a code hash the process
has not seen, as in a CI sweep. Set-up analyses every contract of the
corpus once, with hashes of their own, so that the window compiles
nothing.

Two comparisons decide `correct`: each report's canonical issue list
against the configuration's answer key, and each issue's transaction
sequence replayed on the plain EVM of benchmarks/reference/evm.py,
which has to show the issue's condition where it can decide.

Mix parameters (traffic/<mix>.json, "generator": "corpus"):
  redraw_metadata_hash   true: redraw every trailer's hash per submission
"""

import json
import random
import time
from pathlib import Path

from ..reference.evm import replay_issue
from .counters import device_errors

#: solc metadata trailers: CBOR map prefix up to the hash, hash bytes
TRAILERS = (
    ("a165627a7a72305820", 32),    # {"bzzr0": <32-byte swarm hash>}
    ("a2646970667358221220", 32),  # {"ipfs": <0x1220 + 32-byte digest>}
)


def redraw_metadata_hash(code: str, rng: random.Random) -> str:
    """The hex code with the hash of every solc metadata trailer
    replaced by bytes drawn from rng; raises ValueError if it has
    none."""
    out, found = code, 0
    for prefix, n in TRAILERS:
        start = 0
        while (i := out.find(prefix, start)) >= 0:
            j = i + len(prefix)
            out = out[:j] + rng.randbytes(n).hex() + out[j + 2 * n:]
            start, found = j + 2 * n, found + 1
    if not found:
        raise ValueError("no solc metadata trailer in the code")
    return out


def canon(report: dict) -> list:
    """Comparable issue list: identity fields only (a copy of the
    program's tests/compare_lane_host.canon). The values inside a
    tx_sequence (initial balances, which of several valid selectors
    reaches a shared site) are a solver's choice and may differ
    between engines whose query order differs; whether an issue
    carries a sequence is kept."""
    issues = []
    for i in report.get("issues") or []:
        i = dict(i)
        i.pop("discoveryTime", None)
        seq = i.pop("tx_sequence", None)
        i["has_tx_sequence"] = bool(seq and seq.get("steps"))
        issues.append(i)
    return sorted(issues, key=lambda i: json.dumps(i, sort_keys=True))


def corpus(config: dict, root: Path) -> list:
    """(name, hex code) of every contract the configuration analyses,
    in alphabetical order."""
    left_out = set(config["reduced"])
    paths = sorted((root / config["corpus_dir"]).glob("*.sol.o"))
    return [(p.name, p.read_text().strip()) for p in paths
            if p.name not in left_out]


def analyze_report(name: str, code: str, config: dict):
    """The report of one analysis (a copy of bench_corpus.analyze_report
    with the configuration's settings): every detector unless the
    configuration names some, creation or runtime code by fixture."""
    from mythril_tpu.orchestration.mythril_analyzer import MythrilAnalyzer
    from mythril_tpu.orchestration.mythril_disassembler import (
        MythrilDisassembler,
    )
    from mythril_tpu.support.analysis_args import make_cmd_args

    a = config["analyzer"]
    disassembler = MythrilDisassembler(eth=None)
    address, _ = disassembler.load_from_bytecode(
        code, bin_runtime=name not in config["creation_fixtures"])
    cmd_args = make_cmd_args(
        execution_timeout=a["execution_timeout"],
        solver_timeout=a["solver_timeout"], max_depth=a["max_depth"],
        loop_bound=a["loop_bound"], create_timeout=a["create_timeout"],
        call_depth_limit=a["call_depth_limit"], tpu_lanes=a["tpu_lanes"],
        tpu_mesh=a["tpu_mesh"], no_warm_store=not a["warm_store"])
    analyzer = MythrilAnalyzer(
        disassembler=disassembler, cmd_args=cmd_args,
        strategy=a["strategy"], address=address)
    return analyzer.fire_lasers(modules=a["modules"],
                                transaction_count=a["transaction_count"])


class Driver:
    """One unit of work is one analysis; the loop cycles the corpus."""

    annotation = "corpus.analysis"

    def __init__(self, config: dict, mix: dict, seed: int, root: Path):
        self.config = config
        self.mix = mix
        self.items = corpus(config, root)
        #: the window ends on a whole pass over the corpus
        self.period = len(self.items)
        # set-up and window draw from streams of their own
        self._rng = random.Random(f"window-{seed}")
        self._warm_rng = random.Random(f"set-up-{seed}")
        with open(root / config["reference"]) as f:
            self.key = json.load(f)
        self.attempted = 0
        self.failed = 0
        #: per completed analysis: (fixture, canonical issue list)
        self.done = []
        #: every issue the window's reports hold, as reported
        self.issues = []
        #: how the replays of the window's issues came out
        self.notes = {}
        #: (fixture, wall seconds) of each analysis of the window
        self.walls = []
        #: wall seconds of set-up's pass over the corpus
        self.warmup = []

    def _submit(self, name: str, code: str, rng: random.Random):
        if self.mix["redraw_metadata_hash"]:
            code = redraw_metadata_hash(code, rng)
        return analyze_report(name, code, self.config)

    def warm_up(self, clock) -> None:
        t0 = time.perf_counter()
        for name, code in self.items:
            self._submit(name, code, self._warm_rng)
        self.warmup.append(time.perf_counter() - t0)

    def run_one(self) -> None:
        name, code = self.items[self.attempted % len(self.items)]
        self.attempted += 1
        errors0 = device_errors()
        t0 = time.perf_counter()
        report = self._submit(name, code, self._rng)
        self.walls.append((name, time.perf_counter() - t0))
        if report.exceptions or device_errors() != errors0:
            self.failed += 1
        issues = json.loads(report.as_json())
        self.done.append((name, canon(issues)))
        self.issues += issues.get("issues") or []

    def end_to_end(self, window_s: float) -> dict:
        return {"contracts_per_hour": len(self.done) * 3600.0 / window_s}

    def record(self) -> dict:
        return {"completed": len(self.done)}

    def checks(self) -> list:
        """(name, value, limit): the analyses of the window whose issue
        list differs from the answer key's for that contract, and the
        issues whose transaction sequence, replayed, does not show the
        issue's condition although the replay could decide."""
        bad = sum(1 for name, issues in self.done
                  if issues != self.key["issues"][name])
        outcomes = [replay_issue(i) for i in self.issues]
        self.notes = {"replayed": {o: outcomes.count(o) for o in
                                   ("confirmed", "indeterminate", "refuted")}}
        return [("reports_differing", bad, 0),
                ("issues_refuted", outcomes.count("refuted"), 0)]
