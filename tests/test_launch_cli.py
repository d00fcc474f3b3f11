"""Subprocess smoke tests for the launch CLIs (dryrun is covered in
test_dryrun.py; here: tune_cell's tuner-driven before-execution AT and the
train/serve entry points)."""
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# the entry points turn JAX's persistent compilation cache on; tests keep
# it off so no run leaves a cache behind in the checkout
ENV = dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src"),
           JAX_ENABLE_COMPILATION_CACHE="false")


def _run(args, timeout=560):
    return subprocess.run(
        [sys.executable, "-m"] + args, env=ENV, capture_output=True,
        text=True, timeout=timeout, cwd=ROOT,
    )


def test_tune_cell_selects_kvseq_for_decode(tmp_path):
    """The FIBER tuner must discover the KV-length sharding rule on a decode
    cell (EXPERIMENTS.md §Perf cell 5) — end-to-end through lower+compile."""
    db = str(tmp_path / "db.json")
    proc = _run(
        ["repro.launch.tune_cell", "--arch", "qwen3-0.6b",
         "--shape", "decode_32k", "--db", db]
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "best PP" in proc.stdout
    assert "'rule': 'tp_kvseq'" in proc.stdout
    data = json.load(open(db))
    assert data["schema_version"] == 2
    assert len(data["entries"]) == 1  # one BP entry persisted


def test_train_cli_runs():
    proc = _run(
        ["repro.launch.train", "--arch", "tinyllama-1.1b", "--steps", "3",
         "--batch", "2", "--seq", "32"]
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "final loss" in proc.stdout


def test_serve_cli_runs():
    proc = _run(
        ["repro.launch.serve", "--arch", "qwen3-0.6b", "--requests", "2",
         "--prompt-len", "8", "--new-tokens", "4"]
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "served 2 requests" in proc.stdout


def _failing_prefill(rid):
    """A StreamingEngine._prefill_exec whose groups holding ``rid`` raise."""
    from repro.runtime import StreamingEngine

    real = StreamingEngine._prefill_exec

    def prefill_exec(self, group, *a, **k):
        if any(w.req.rid == rid for w in group):
            raise RuntimeError("injected prefill fault")
        return real(self, group, *a, **k)

    return prefill_exec


STREAM_ARGS = ["--arch", "tinyllama-1.1b", "--stream", "--requests", "3"]


def test_serve_stream_exits_nonzero_on_error_retirement(monkeypatch, capsys):
    """Without --chaos-seed nothing injected the fault: an ``error``
    retirement is a bug, so the run fails even though every rid retired."""
    from repro.launch import serve
    from repro.runtime import StreamingEngine

    monkeypatch.setattr(StreamingEngine, "_prefill_exec", _failing_prefill(0))
    with pytest.raises(SystemExit) as exc:
        serve.main(STREAM_ARGS)
    assert exc.value.code == 1
    out = capsys.readouterr().out
    assert "ERROR: request 0 retired error" in out
    assert "drain incomplete" not in out


def test_serve_stream_clean_run_and_chaos_faults_pass(monkeypatch):
    """A clean run has no faults; under chaos an ``error`` retirement is
    an injected outcome, only a missed retirement is a fault."""
    from repro.launch import serve
    from repro.runtime import StreamingEngine

    args = serve.parse_args(STREAM_ARGS)
    cfg, params = serve.load_model(args)
    engine, requests, faults = serve.run_stream(cfg, params, args)
    assert faults == []
    assert {r.status for r in engine.results.values()} == {"ok"}

    monkeypatch.setattr(StreamingEngine, "_prefill_exec", _failing_prefill(0))
    engine, requests, faults = serve.run_stream(cfg, params, args)
    assert engine.results[0].status == "error"
    assert faults == ["request 0 retired error: " + engine.results[0].detail]
    engine.chaos = object()  # as if a ChaosInjector had run the trace
    assert serve.stream_faults(engine, requests) == []
