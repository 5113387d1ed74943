"""chip_smoke.py's phases at a smoke size on the CPU, checked against NumPy.

The chip runs the Pallas kernels compiled; here they run in interpret mode,
so every window still goes through the kernel code the chip runs.
"""
import functools
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

ROOT = os.path.join(os.path.dirname(__file__), "..")
sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402
from repro.configs.registry import get_config, smoke_config  # noqa: E402
from repro.data import CkIOPipeline  # noqa: E402

SMALL = dict(batch=2, seq=64, microbatches=1, steps=3, seed=5)
# Fewer pooled workers than the chip run: six test processes share the CPU.
WORKER_CAPS = {"streaming-service": ("--pool-workers", "2",
                                     "--max-workers", "2")}


@pytest.fixture
def interpret_kernels(monkeypatch):
    orig = CkIOPipeline.get_batch_device
    monkeypatch.setattr(CkIOPipeline, "get_batch_device",
                        functools.partialmethod(orig, use_pallas=True))


@pytest.fixture
def small_cfg():
    return smoke_config(get_config(chip_smoke.ARCH))


@pytest.mark.parametrize("name,flags", chip_smoke.PHASES,
                         ids=[p[0] for p in chip_smoke.PHASES])
def test_phase_matches_file(name, flags, small_cfg, tmp_path,
                            interpret_kernels):
    flags = (*flags, *WORKER_CAPS.get(name, ()))
    res = chip_smoke.run_phase(name, flags, small_cfg, str(tmp_path),
                               **SMALL)
    assert res["batches_identical"] == SMALL["steps"]
    assert len(res["losses"]) == SMALL["steps"]
    assert all(np.isfinite(res["losses"]))


def test_sharded_path_on_one_device(small_cfg, tmp_path):
    res = list(chip_smoke.run_sharded(small_cfg, str(tmp_path),
                                      jax.devices()[:1], **SMALL))
    assert [r["phase"] for r in res] == ["sharded-streaming",
                                         "sharded-window"]
    for r in res:
        assert r["rows_per_device"] == [SMALL["batch"]]
        assert all(np.isfinite(r["losses"]))


def test_kernel_check_on_cpu():
    res = chip_smoke.check_kernels(2, 64, seed=3, interpret=True)
    assert res["cases_identical"] == 9


def test_batch_check_rejects_a_changed_token(tmp_path):
    path = str(tmp_path / "t.tokens")
    chip_smoke.write_corpus(path, steps=2, batch=2, seq=8, vocab=50, seed=1)
    x, y = chip_smoke.reference_window(path, 1, 2, 8)
    check = chip_smoke.BatchCheck(path, 2, 8)
    check(1, {"tokens": x.view(np.int32), "labels": y.view(np.int32)})
    bad = x.copy()
    bad[1, 3] += 1
    with pytest.raises(chip_smoke.SmokeFailure):
        check(1, {"tokens": bad, "labels": y})
    with pytest.raises(chip_smoke.SmokeFailure):
        check(0, {"tokens": x, "labels": y})     # another step's window
    assert check.checked == 1


def test_entry_refuses_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, os.path.join(ROOT, "chip_smoke.py")],
                       env=env, capture_output=True, text=True, timeout=120)
    assert r.returncode != 0
    assert "'cpu'" in r.stderr
    assert '"ok"' not in r.stdout
