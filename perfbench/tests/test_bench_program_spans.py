"""The per-layer metrics read from the program's own spans and counters
(``repro.core.tracing``): each reader against a snapshot made by hand, its
silence where the program has no such span, and the spans of a traced CPU
run of the train and resume cells at smoke size."""
import sys

import pytest

import smoke
from harness import main as harness_main
from harness.cells import metric_reader
from harness.spans import Spans
from harness.trace import Reduction
from repro.core import tracing
from repro.core.tracing import Snapshot, Span

RESUME = ["resume_open_s", "resume_reader_gb_per_s",
          "resume_deliver_gb_per_s", "resume_wait_s", "resume_unpack_s",
          "resume_host_copies"]
TRAIN = ["input_ready_wait_ms.train", "input_lookahead_ms.train",
         "h2d_transfers_per_step.train"]
NEW = RESUME + TRAIN + ["admit_wait_ms.train"]

# A device trace, as a chip run gives (the readers only ask that there is
# one).
ON_CHIP = Reduction(window=(0, 1), devices=1, busy_ns=[0])


def span(i, name, start, end, parent=None, **ids):
    return Span(name, start, end, thread=1, parent=parent, id=i, ids=ids)


def hand_made():
    """Two resumes (sessions 1 and 2) and two train steps, in ns."""
    spans = [
        span(1, "ckio.wait", 0, 1000),
        span(2, "ckio.session.start", 10, 210, parent=1, session=1),
        span(3, "ckio.deliver", 300, 400, parent=1, session=1, nbytes=600),
        span(4, "ckio.deliver", 500, 700, parent=1, session=1, nbytes=900),
        span(5, "ckio.reader.read", 100, 600, session=1, nbytes=1000),
        span(6, "ckio.reader.read", 200, 900, session=1, nbytes=1000),
        span(7, "ckio.wait", 2000, 2600),
        span(8, "ckio.session.start", 2000, 2100, parent=7, session=2),
        span(9, "ckio.reader.read", 2000, 2400, session=2, nbytes=2000),
        span(10, "checkpoint.unpack", 3000, 3500),
        span(11, "checkpoint.unpack", 4000, 4100),
        span(12, "pipeline.wait_ready", 0, 3_000_000, step=0),
        span(13, "pipeline.wait_ready", 0, 1_000_000, step=1),
        span(14, "pipeline.lookahead", 0, 500_000, step=0),
        span(15, "pipeline.lookahead", 0, 100_000, step=1),
        span(16, "service.admit", 0, 7_000_000, session=3),
    ]
    counters = {"ckio.bytes_copied": 2000, "checkpoint.unpack_bytes": 2000,
                "pipeline.h2d_transfers": 9}
    return Snapshot(spans, counters)


def readings(trace=ON_CHIP, **counters):
    return harness_main.Readings(spans=Spans(), counters=counters,
                                 trace=trace, peaks={}, chips=1)


@pytest.mark.parametrize("name, counters, want", [
    ("resume_open_s", {"resumes": 2}, 300e-9 / 2),
    # session 1 reads 100..900, session 2 2000..2400: 1200 ns for 2 x 1000 B
    ("resume_reader_gb_per_s", {"checkpoint_bytes": 1000}, 2000 / 1200),
    ("resume_deliver_gb_per_s", {}, 1500 / 300),
    # wait self time: 1000 - 200 - 100 - 200 and 600 - 100
    ("resume_wait_s", {"resumes": 2}, 1000e-9 / 2),
    ("resume_unpack_s", {"resumes": 2}, 600e-9 / 2),
    ("resume_host_copies", {"resumes": 2, "checkpoint_bytes": 1000}, 2.0),
    ("input_ready_wait_ms.train", {"steps": 2}, 2.0),
    ("input_lookahead_ms.train", {"steps": 2}, 0.3),
    ("h2d_transfers_per_step.train", {"steps": 2}, 4.5),
    ("admit_wait_ms.train", {"steps": 2}, 3.5),
])
def test_reader_reads_a_hand_made_snapshot(monkeypatch, name, counters, want):
    monkeypatch.setattr(tracing, "snapshot", hand_made)
    got = metric_reader(name).read(readings(**counters))
    assert got == pytest.approx(want)


@pytest.mark.parametrize("name", NEW)
def test_reader_is_silent_without_the_programs_spans(monkeypatch, name):
    import repro.core

    counters = {"resumes": 2, "steps": 2, "checkpoint_bytes": 1000}
    reader = metric_reader(name)
    monkeypatch.setattr(tracing, "snapshot", lambda: Snapshot([], {}))
    assert reader.read(readings(**counters)) is None
    monkeypatch.setattr(tracing, "snapshot", hand_made)
    assert reader.read(readings(trace=None, **counters)) is None
    # A program without the tracing module, as before it existed.
    monkeypatch.delattr(repro.core, "tracing")
    monkeypatch.setitem(sys.modules, "repro.core.tracing", None)
    assert reader.read(readings(**counters)) is None


@pytest.mark.parametrize("cell, names", [
    ("phi4mini-train4k.steady", TRAIN), ("phi4mini-resume.warm", RESUME)])
def test_traced_smoke_run_gives_every_new_metric(tmp_path, monkeypatch,
                                                 cell, names):
    seen, made = [], harness_main.Readings

    def keep(*a, **k):
        seen.append(made(*a, **k))
        return seen[-1]

    monkeypatch.setattr(harness_main, "Readings", keep)
    tracing.reset()                 # records of earlier traces in this process
    r = smoke.run(str(tmp_path), cell, trace=True)
    assert r["correct"], r["checks"]
    # On the CPU the trace holds no device operation: the result line
    # carries none of these metrics, and the readings do.
    assert not set(names) & set(r["metrics"])
    (got,) = seen
    got.trace = ON_CHIP
    values = {n: metric_reader(n).read(got) for n in names}
    assert all(v is not None and v > 0 for v in values.values()), values
    if cell == "phi4mini-resume.warm":
        # every byte copied into its leaf's buffer, then once more unpacked
        assert values["resume_host_copies"] == pytest.approx(2.0, rel=1e-3)
    else:
        assert values["h2d_transfers_per_step.train"] == 1.0
