"""The resume driver at smoke size on the CPU, its faults and its control;
and the command refusing to run without a TPU."""
import os
import subprocess
import sys

import pytest

import smoke
from harness import faults

CELL = "phi4mini-resume.warm"


def test_sound_run_is_correct(tmp_path, capsys):
    r = smoke.run(str(tmp_path), CELL)
    assert "compilations inside the measured window: 0" in \
        capsys.readouterr().err
    assert r["correct"], r["checks"]
    assert r["attempted"] >= 1 and r["failed"] == 0
    assert set(r["metrics"]) == {"resume_s", "setup_s"}


def test_traced_run_reads_both_span_metrics(tmp_path):
    r = smoke.run(str(tmp_path), CELL, trace=True)
    assert r["correct"], r["checks"]
    assert set(r["metrics"]) == {"resume_read_gb_per_s",
                                 "resume_stage_gb_per_s"}


@pytest.mark.parametrize("fault", ["leaf_altered", "leaf_left_out",
                                   "rounded"])
def test_broken_resume_is_incorrect(tmp_path, monkeypatch, fault):
    from repro.train import checkpoint

    monkeypatch.setattr(checkpoint, "restore_sharded",
                        getattr(faults, fault)(checkpoint.restore_sharded))
    r = smoke.run(str(tmp_path), CELL)
    assert not r["correct"], r["checks"]
    assert r["checks"]["leaf_mismatch_words"]["value"] > 0


@pytest.mark.parametrize("workload", ["phi4mini-train4k.steady", CELL])
def test_command_fails_without_a_tpu(workload):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(smoke.BENCH_DIR, "run.py"),
         "--workload", workload, "--seed", "3", "--seconds", "1",
         "--trace", "0"],
        cwd=smoke.CHECKOUT, env=env, capture_output=True, text=True,
        timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "needs a TPU" in p.stderr
