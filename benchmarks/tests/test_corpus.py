"""CPU rehearsal of the corpus cell on three small contracts: the
metadata-hash redraw leaves the report unchanged, the answer key agrees
with the host interpreter, the host's issues replay on the plain EVM,
and the control and every fault the cell can have make `correct` come
out false."""

import json
import random

import pytest

from benchmarks import run
from benchmarks.reference.evm import replay_issue
from benchmarks.traffic import corpus

SEED = 2 ** 40 + 3
FEW = ("metacoin.sol.o", "origin.sol.o", "suicide.sol.o")


@pytest.fixture(scope="module")
def bench():
    return run.load_json(run.ROOT / "BENCHMARK.json")


@pytest.fixture(scope="module")
def config():
    return run.load_json(
        run.ROOT / "benchmarks" / "configs" / "mythril_testdata_t2.json")


def _only(config, names, **analyzer):
    """Override that keeps only `names` of the corpus, on 64 lanes of
    one device unless analyzer says otherwise."""
    every = sorted(p.name for p in
                   (run.ROOT / config["corpus_dir"]).glob("*.sol.o"))
    return {"reduced": [n for n in every if n not in names],
            "analyzer": dict({"tpu_lanes": 64, "tpu_mesh": 0}, **analyzer)}


def _run(bench, override):
    return run.run_cell(bench, "corpus.t2", SEED, 0.5, False,
                        require_tpu=False, config_override=override)


def test_every_contract_has_a_trailer_and_the_redraw_keeps_the_length(
        config):
    for name, code in corpus.corpus(config, run.ROOT):
        a = corpus.redraw_metadata_hash(code, random.Random(1))
        b = corpus.redraw_metadata_hash(code, random.Random(2))
        assert len(a) == len(b) == len(code), name
        assert len({a, b, code}) == 3, name
        assert a == corpus.redraw_metadata_hash(code, random.Random(1))


def test_redraw_refuses_code_without_a_trailer():
    with pytest.raises(ValueError):
        corpus.redraw_metadata_hash("6001600101", random.Random(0))


@pytest.mark.parametrize("name", ["suicide.sol.o", "origin.sol.o"])
def test_host_report_unchanged_by_the_redraw(config, name):
    host = run.merged(config, {"analyzer": {"tpu_lanes": 0}})
    code = dict(corpus.corpus(config, run.ROOT))[name]
    key = run.load_json(run.ROOT / config["reference"])["issues"][name]
    for c in (code, corpus.redraw_metadata_hash(code, random.Random(7))):
        report = corpus.analyze_report(name, c, host)
        assert corpus.canon(json.loads(report.as_json())) == key


def test_lanes_match_the_answer_key(bench, config):
    r = _run(bench, _only(config, FEW))
    assert r["correct"] is True
    assert r["checks"] == {"reports_differing": {"value": 0, "limit": 0},
                           "issues_refuted": {"value": 0, "limit": 0}}
    assert r["harness"]["replayed"]["confirmed"] >= 2
    assert r["failed"] == 0 and r["attempted"] >= 1
    assert set(r["metrics"]) == {"contracts_per_hour", "setup_s"}


def test_control_is_not_correct(bench, config):
    """The control leaves IntegerArithmetics out, which breaks the
    guarantee that every detector runs; metacoin's issue is an integer
    overflow."""
    override = run.merged(_only(config, FEW), config["control"])
    r = _run(bench, override)
    assert r["correct"] is False
    assert r["checks"]["reports_differing"]["value"] > 0


def _faulty(monkeypatch, fault):
    real = corpus.analyze_report

    def analyze(name, code, config):
        report = real(name, code, config)
        fault(report, name)
        return report

    monkeypatch.setattr(corpus, "analyze_report", analyze)


def _alter_first_issue(report, name):
    issue = next(iter(report.issues.values()))
    issue.swc_id = "000"


def _alter_calldata(report, name):
    """A wrong model: the last step's calldata loses its last byte."""
    for issue in report.issues.values():
        step = issue.transaction_sequence["steps"][-1]
        for key in ("input", "calldata"):
            step[key] = step[key][:-2]


def test_host_issues_replay(config):
    """suicide's and origin's host issues replay as confirmed; with the
    calldata cut, origin's transferOwnership(address) is not reached."""
    host = run.merged(config, {"analyzer": {"tpu_lanes": 0}})
    code = dict(corpus.corpus(config, run.ROOT))
    for name in ("suicide.sol.o", "origin.sol.o"):
        report = corpus.analyze_report(name, code[name], host)
        issues = json.loads(report.as_json())["issues"]
        assert [replay_issue(i) for i in issues] == ["confirmed"], name
    _alter_calldata(report, name)
    issues = json.loads(report.as_json())["issues"]
    assert [replay_issue(i) for i in issues] != ["confirmed"]


@pytest.mark.parametrize("fault", [
    # a step that returns its state unchanged: nothing found
    lambda report, name: report.issues.clear(),
    # half of the batch left out: the first contracts report nothing
    lambda report, name: (report.issues.clear()
                          if name in FEW[:len(FEW) // 2 + 1] else None),
    # an answer altered where it is produced
    _alter_first_issue,
    # a transaction sequence altered where it is produced
    _alter_calldata,
], ids=["state_unchanged", "half_left_out", "answer_altered",
        "sequence_altered"])
def test_faults_are_not_correct(bench, config, monkeypatch, fault):
    _faulty(monkeypatch, fault)
    assert _run(bench, _only(config, FEW))["correct"] is False
