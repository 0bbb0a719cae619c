"""chip_smoke.py: the CPU rehearsal runs end to end, and the default
mode refuses to run without a TPU."""
import json
import os
import shutil
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(args, cwd=REPO, **env_extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **env_extra)
    env.pop("XLA_FLAGS", None)
    return subprocess.run(
        [sys.executable, "chip_smoke.py", *args], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=400,
    )


@pytest.mark.parametrize("chips,phases", [
    ("1", ["env", "compile", "serving", "requests", "done"]),
    ("4", ["env", "mesh4", "done"]),
])
def test_cpu_rehearsal_runs_end_to_end(tmp_path, chips, phases):
    cache = str(tmp_path / "cache")
    proc = _run(["--cpu-rehearsal", "--chips", chips],
                JAX_COMPILATION_CACHE_DIR=cache,
                JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0")
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = [json.loads(line) for line in proc.stdout.splitlines()]
    assert [r.get("phase") for r in lines[:-1]] == phases
    assert lines[-1] == {"ok": True, "device": {
        "platform": "cpu", "kind": "cpu", "count": int(chips)}}
    assert '"tpu"' not in proc.stdout
    env = lines[0]
    assert env["compile_cache_dir"] == cache
    assert os.listdir(cache)  # the cache went where the env said
    if chips == "1":
        serving = lines[2]
        assert serving["oracle_mismatches"] == 0
        assert serving["native_mismatches"] == 0
        assert serving["regrows"] >= 1
        assert serving["host_mode_docs"] == 0
        assert lines[3]["sidecar_agrees"] is True
    else:
        mesh = lines[1]
        assert mesh["migrations"] >= 1
        assert mesh["pool_table_device_counts"] == {"mesh": [4],
                                                    "seq": [4]}


@pytest.mark.parametrize("isolated", [False, True])
def test_default_mode_fails_without_a_tpu(tmp_path, isolated):
    """No accelerator: non-zero exit and no result, in the repo and in
    a directory holding chip_smoke.py alone."""
    cwd = REPO
    if isolated:
        shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
        cwd = str(tmp_path)
    proc = _run([], cwd=cwd)
    assert proc.returncode != 0
    assert proc.stdout == ""
    assert "no TPU" in proc.stderr


def test_compile_cache_dir_follows_the_environment(monkeypatch):
    from fluidframework_tpu.utils.compile_cache import compile_cache_dir

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
    assert compile_cache_dir() == "/somewhere/else"
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    assert compile_cache_dir() == os.path.join(REPO, ".jax_cache")
