"""The train driver at smoke size on the CPU: a sound run is correct, and
each fault planted under the timed path makes it incorrect."""
import pytest

import smoke
from harness import faults

CELL = "phi4mini-train4k.steady"


def test_sound_run_is_correct(tmp_path, capsys):
    r = smoke.run(str(tmp_path), CELL)
    assert "compilations inside the measured window: 0" in \
        capsys.readouterr().err
    assert r["correct"], r["checks"]
    assert r["attempted"] >= 1 and r["failed"] == 0
    assert set(r["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert list(r)[-1] == "checks"


def test_traced_run_reads_the_span_metric(tmp_path):
    r = smoke.run(str(tmp_path), CELL, trace=True)
    assert r["correct"], r["checks"]
    # No TPU in the trace: only the host span's metric can be read.
    assert set(r["metrics"]) == {"input_wait_ms.train"}


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch"])
def test_broken_step_is_incorrect(tmp_path, monkeypatch, fault):
    import repro.train

    monkeypatch.setattr(repro.train, "make_train_step",
                        getattr(faults, fault)(repro.train.make_train_step))
    r = smoke.run(str(tmp_path), CELL)
    assert not r["correct"], r["checks"]


def test_altered_token_is_incorrect(tmp_path, monkeypatch):
    from repro.data import CkIOPipeline

    monkeypatch.setattr(CkIOPipeline, "get_batch_device",
                        faults.token_altered(CkIOPipeline.get_batch_device))
    r = smoke.run(str(tmp_path), CELL)
    assert not r["correct"]
    assert r["checks"]["batch_mismatch_tokens"]["value"] > 0
    assert r["failed"] == r["attempted"]
