"""bench.py run-status contract: a correctness-
stage failure must poison the run — top-level flag + nonzero exit —
never hide in `failures` under rc 0."""
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    proc = subprocess.run(
        [sys.executable, "bench.py", "--smoke",
         "--stages", "probe,fuzz"],
        cwd=REPO, env=env, capture_output=True, text=True,
        timeout=300,
    )
    line = proc.stdout.strip().splitlines()[-1]
    return proc.returncode, json.loads(line)


@pytest.mark.slow
def test_green_fuzz_reports_clean_status():
    rc, out = _run()
    assert rc == 0
    assert out["correctness_failed"] is False
    assert out["detail"]["stages"]["fuzz"]["result"] == \
        "all-signatures-match"


@pytest.mark.slow
def test_seeded_fuzz_failure_flips_run_status():
    rc, out = _run({"FFTPU_FUZZ_SABOTAGE": "1"})
    assert rc != 0
    assert out["correctness_failed"] is True
    assert "fuzz" in out["correctness_failures"]


def test_chip_stage_failure_fails_the_run(monkeypatch, capsys):
    """Outside --smoke a stage runs once, on the TPU: when it fails,
    the run exits non-zero and reports no numbers for it — there is no
    retry on the CPU."""
    sys.path.insert(0, REPO)
    import bench

    calls = []

    def failing_spawn(stage, backend, scale, reps, cooldown, timeout):
        calls.append((stage, backend, scale))
        return None, "rc=1: RuntimeError: no TPU"

    monkeypatch.setattr(bench, "_spawn", failing_spawn)
    with pytest.raises(SystemExit) as exc:
        bench.main(["--stages", "config2,config7"])
    assert exc.value.code == 1
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert calls == [("config2", "tpu", "full"),
                     ("config7", "tpu", "full")]
    assert out["detail"]["stages"] == {}
    assert out["stage_failures"] == ["config2", "config7"]
    assert out["value"] == 0


def test_tpu_stage_refuses_the_cpu():
    """A stage child asked for the TPU fails when JAX finds the CPU."""
    proc = subprocess.run(
        [sys.executable, "bench.py", "--stage", "probe",
         "--backend", "tpu"],
        cwd=REPO, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode != 0
    assert "does not fall back" in proc.stderr
    assert proc.stdout == ""
