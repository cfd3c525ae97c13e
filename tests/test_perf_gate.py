"""scripts/perf_gate.py: the smoke-to-smoke regression verdict
(warn-only default, --strict enforcement)."""

import json
import subprocess
import sys

import pytest

from scripts import perf_gate


def test_verdict_branches():
    history = [
        {"unix": 1, "values": {"bench_smoke": 2_000_000.0}},
        {"unix": 2, "values": {"bench_smoke": 3_000_000.0}},
    ]
    # ok: within tolerance of the best prior (3.0M)
    (res,) = perf_gate.verdict({"bench_smoke": 2_500_000.0}, history, 0.25)
    assert res[1] == "ok"
    # regression: below best * (1 - tol)
    (res,) = perf_gate.verdict({"bench_smoke": 2_000_000.0}, history, 0.25)
    assert res[1] == "regression"
    # baseline: no prior rounds for this config
    (res,) = perf_gate.verdict({"remote_smoke": 1.0}, history, 0.25)
    assert res[1] == "baseline"
    # failed smoke run: recorded as baseline-with-note, never a crash
    (res,) = perf_gate.verdict({"bench_smoke": None}, history, 0.25)
    assert res[1] == "baseline"


def test_history_roundtrip(tmp_path):
    path = str(tmp_path / "hist.jsonl")
    assert perf_gate.load_history(path) == []
    perf_gate.append_history({"unix": 1, "values": {"x": 2.0}}, path)
    perf_gate.append_history({"unix": 2, "values": {"x": 3.0}}, path)
    rows = perf_gate.load_history(path)
    assert [r["values"]["x"] for r in rows] == [2.0, 3.0]


def test_cli_with_both_smokes_skipped_runs_no_benches():
    proc = subprocess.run(
        [sys.executable, "scripts/perf_gate.py", "--skip-bench",
         "--skip-remote"],
        capture_output=True, text=True, timeout=60,
    )
    assert proc.returncode == 0, proc.stderr
    assert "nothing to judge" in proc.stdout


@pytest.mark.parametrize("strict,expected_rc", [(False, 0), (True, 1)])
def test_strict_flag_gates_a_regression(tmp_path, monkeypatch, capsys,
                                        strict, expected_rc):
    """Warn-only by default, --strict exits nonzero — with the smoke
    runners stubbed so the test costs milliseconds."""
    hist = str(tmp_path / "hist.jsonl")
    perf_gate.append_history(
        {"unix": 1, "values": {"remote_smoke": 10_000_000.0}}, hist
    )
    monkeypatch.setattr(perf_gate, "run_smoke_remote",
                        lambda timeout_s: {"value": 1_000_000.0})
    argv = ["perf_gate.py", "--skip-bench", "--history", hist,
            "--no-record"]
    if strict:
        argv.append("--strict")
    monkeypatch.setattr(sys, "argv", argv)
    assert perf_gate.main() == expected_rc
    out = capsys.readouterr().out
    assert "REGRESSION" in out


@pytest.mark.parametrize("listed,expected_rc", [(False, 0), (True, 1)])
def test_strict_configs_gate_only_named_configs(tmp_path, monkeypatch,
                                                capsys, listed,
                                                expected_rc):
    """--strict-configs enforces per config: a regression in a listed
    config fails, the same regression in an unlisted one stays a
    warning — the verify.sh shape (host bench gates, remote noise
    doesn't)."""
    hist = str(tmp_path / "hist.jsonl")
    perf_gate.append_history(
        {"unix": 1, "values": {"remote_smoke": 10_000_000.0}}, hist
    )
    monkeypatch.setattr(perf_gate, "run_smoke_remote",
                        lambda timeout_s: {"value": 1_000_000.0})
    configs = "remote_smoke" if listed else "bench_smoke"
    monkeypatch.setattr(sys, "argv", [
        "perf_gate.py", "--skip-bench", "--history", hist, "--no-record",
        "--strict-configs", configs,
    ])
    assert perf_gate.main() == expected_rc
    out = capsys.readouterr().out
    assert "REGRESSION" in out
    if not listed:
        assert "[warn-only config]" in out


def test_verdict_json_is_append_only(tmp_path, monkeypatch):
    """A run records its smoke values into the history for the next
    round's comparison (unless --no-record)."""
    hist = str(tmp_path / "hist.jsonl")
    monkeypatch.setattr(perf_gate, "run_smoke_remote",
                        lambda timeout_s: {"value": 5_000_000.0})
    monkeypatch.setattr(
        sys, "argv",
        ["perf_gate.py", "--skip-bench", "--history", hist],
    )
    assert perf_gate.main() == 0
    (row,) = perf_gate.load_history(hist)
    assert row["values"] == {"remote_smoke": 5_000_000.0}
    assert json.loads(open(hist).read().strip())