"""The tracing surface (``core/tracing.py``): spans and counters record only
while a profiler trace is being taken, and under one the read path, the
pipeline and checkpoint restore produce the spans and counters that the
per-layer metrics read, nested as the work is, on the trace's host plane."""
import contextlib
import glob
import os

import jax
import numpy as np
import pytest
from jax._src.lib import _profiler

from repro.core import CkIO, FileOptions, tracing
from repro.data import CkIOPipeline, make_token_file
from repro.train import restore_tree, save_checkpoint


@contextlib.contextmanager
def profiler_trace(trace_dir):
    tracing.reset()
    jax.profiler.start_trace(str(trace_dir))
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def host_event_names(trace_dir):
    (path,) = glob.glob(os.path.join(str(trace_dir), "**", "*.xplane.pb"),
                        recursive=True)
    names = set()
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if plane.name.startswith("/host"):
            for line in plane.lines:
                names.update(ev.name for ev in line.events)
    return names


def by_id(snap):
    return {s.id: s for s in snap.spans}


def test_profiler_flag_is_on_exactly_while_tracing(tmp_path):
    assert not _profiler.TraceMe.is_enabled()
    jax.profiler.start_trace(str(tmp_path))
    try:
        assert _profiler.TraceMe.is_enabled()
    finally:
        jax.profiler.stop_trace()
    assert not _profiler.TraceMe.is_enabled()


def test_without_a_trace_nothing_is_recorded(monkeypatch):
    def no_annotation(*a, **k):
        raise AssertionError("a TraceAnnotation was entered")

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", no_annotation)
    tracing.reset()
    a = tracing.span("ckio.deliver", session=1, nbytes=8)
    assert a is tracing.span("pipeline.stage")     # one shared no-op
    with a as sp:
        sp.tag(session=2)
    tracing.record("ckio.reader.read", 0, 10, session=1)
    tracing.count("ckio.bytes_copied", 8)
    snap = tracing.snapshot()
    assert snap.spans == [] and snap.counters == {}


def test_span_nesting_ids_and_self_time(tmp_path):
    with profiler_trace(tmp_path):
        with tracing.span("outer", step=3) as o:
            with tracing.span("inner", nbytes=5):
                pass
            o.tag(session=7)
        tracing.count("c", 2)
        tracing.count("c", 3)
    snap = tracing.snapshot()
    (outer,) = snap.named("outer")
    (inner,) = snap.named("inner")
    assert inner.parent == outer.id and outer.parent is None
    assert outer.ids == {"step": 3, "session": 7}
    assert inner.ids == {"nbytes": 5}
    assert outer.start <= inner.start <= inner.end <= outer.end
    assert snap.self_s("outer") == pytest.approx(
        (outer.ns - inner.ns) / 1e9)
    assert snap.counters == {"c": 5}
    assert {"outer", "inner"} <= host_event_names(tmp_path)
    tracing.reset()
    assert tracing.snapshot().spans == []


def test_threads_record_every_span_and_count(tmp_path):
    import sys
    import threading

    n_threads, n = 16, 500

    def work(k):
        for i in range(n):
            with tracing.span("outer", thread=k):
                with tracing.span("inner"):
                    tracing.count("c")

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        with profiler_trace(tmp_path):
            threads = [threading.Thread(target=work, args=(k,))
                       for k in range(n_threads)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(60)
    finally:
        sys.setswitchinterval(old)
    assert not any(t.is_alive() for t in threads)
    snap = tracing.snapshot()
    assert snap.counters == {"c": n_threads * n}
    spans = by_id(snap)
    assert len(spans) == len(snap.spans) == 2 * n_threads * n
    for s in snap.named("inner"):
        parent = spans[s.parent]
        assert parent.name == "outer" and parent.thread == s.thread


B, S = 2, 16


@pytest.fixture
def token_path(tmp_path):
    path = str(tmp_path / "corpus.tokens")
    make_token_file(path, B * (S + 1) * 6, vocab_size=1000, seed=3)
    return path


def test_pipeline_steps_are_traced_where_the_work_happens(tmp_path,
                                                          token_path):
    trace_dir = tmp_path / "trace"
    with profiler_trace(trace_dir):
        pipe = CkIOPipeline(token_path, B, S, num_pes=2, num_consumers=4,
                            file_opts=FileOptions(num_readers=2),
                            prefetch_depth=2)
        try:
            for step in range(2):
                x, _ = pipe.get_batch_device(step)
                jax.block_until_ready(x)
        finally:
            pipe.close()
    snap = tracing.snapshot()
    spans = by_id(snap)
    outer = {s.ids["step"]: s for s in snap.named("pipeline.get_batch_device")}
    assert sorted(outer) == [0, 1]
    for step, o in outer.items():
        children = [s for s in snap.spans if s.parent == o.id]
        assert sorted(s.name for s in children) == [
            "pipeline.assemble", "pipeline.close_retired",
            "pipeline.lookahead", "pipeline.stage", "pipeline.wait_ready"]
        assert all(s.ids.get("step", step) == step for s in children)
    # the consumer pumps the scheduler inside the ready wait
    waits = [s for s in snap.named("ckio.wait")
             if spans.get(s.parent) is not None
             and spans[s.parent].name == "pipeline.wait_ready"]
    assert len(waits) == 2
    # every session's reads and deliveries carry its id
    sessions = {s.ids["session"] for s in snap.named("ckio.session.start")}
    assert len(sessions) >= 2
    reads = snap.named("ckio.reader.read")
    assert reads and {s.ids["session"] for s in reads} <= sessions
    delivered = snap.named("ckio.deliver")
    assert delivered and {s.ids["session"] for s in delivered} <= sessions
    assert all(spans[s.parent].name == "ckio.wait" for s in delivered
               if s.parent in spans)
    assert snap.counters["pipeline.h2d_transfers"] == \
        pipe.ingest.h2d_transfers == 2
    assert "ckio.bytes_copied" not in snap.counters    # views, no copies
    assert {"pipeline.get_batch_device", "pipeline.wait_ready",
            "pipeline.lookahead", "pipeline.stage", "pipeline.assemble",
            "ckio.wait", "ckio.session.start", "ckio.reader.read",
            "ckio.deliver"} <= host_event_names(trace_dir)


def test_streamed_steps_count_one_transfer_per_chunk(tmp_path):
    seq = 2047                      # a 16 KiB window: four 4 KiB splinters
    path = str(tmp_path / "corpus.tokens")
    make_token_file(path, B * (seq + 1) * 5, vocab_size=1000, seed=4)
    with profiler_trace(tmp_path / "trace"):
        pipe = CkIOPipeline(path, B, seq, num_pes=2, num_consumers=4,
                            file_opts=FileOptions(num_readers=2,
                                                  splinter_bytes=4096),
                            prefetch_depth=2, streaming=True)
        try:
            for step in range(2):
                x, _ = pipe.get_batch_device(step)
                jax.block_until_ready(x)
        finally:
            pipe.close()
    snap = tracing.snapshot()
    assert snap.counters["pipeline.h2d_transfers"] == \
        pipe.ingest.h2d_transfers >= 8
    assert len(snap.named("pipeline.assemble")) == 2


def small_state():
    k = jax.random.split(jax.random.PRNGKey(0), 3)
    return {"a": jax.random.normal(k[0], (64, 48)),
            "b": {"c": jax.random.normal(k[1], (1000,)),
                  "d": jax.random.randint(k[2], (7, 5), 0, 9)}}


def test_restore_is_traced_from_session_to_unpack(tmp_path):
    tree = small_state()
    path = str(tmp_path / "state.ckpt")
    manifest = save_checkpoint(path, tree, step=4)
    leaf_bytes = sum(e["nbytes"] for e in manifest["leaves"])
    with profiler_trace(tmp_path / "trace"):
        restored, step = restore_tree(path, tree, num_readers=2, num_pes=2)
    assert step == 4
    for a, b in zip(jax.tree.leaves(restored), jax.tree.leaves(tree)):
        np.testing.assert_array_equal(a, np.asarray(b))
    snap = tracing.snapshot()
    spans = by_id(snap)
    (outer,) = snap.named("checkpoint.restore_arrays")
    assert outer.parent is None

    def under_outer(s):
        while s.parent is not None and s.parent in spans:
            s = spans[s.parent]
            if s is outer:
                return True
        return False

    children = {s.name for s in snap.spans if s.parent == outer.id}
    assert children == {"ckio.wait", "ckio.session.close",
                        "checkpoint.unpack"}
    (start,) = snap.named("ckio.session.start")
    assert spans[start.parent].name == "ckio.wait" and under_outer(start)
    (close,) = snap.named("ckio.session.close")
    session = start.ids["session"]
    assert close.ids["session"] == session
    assert start.ids["nbytes"] == manifest["total_bytes"]
    reads = snap.named("ckio.reader.read")
    assert {s.ids["session"] for s in reads} == {session}
    delivered = snap.named("ckio.deliver")
    assert {s.ids["session"] for s in delivered} == {session}
    assert all(spans[s.parent].name == "ckio.wait" and under_outer(s)
               for s in delivered)
    assert sum(s.ids["nbytes"] for s in delivered) == leaf_bytes
    (unpack,) = snap.named("checkpoint.unpack")
    assert spans[unpack.parent] is outer
    # The leaves are views of the session arena: nothing is copied.
    assert snap.counters.get("ckio.bytes_copied", 0) == 0
    assert snap.counters["checkpoint.unpack_bytes"] == 0
    assert snap.counters["checkpoint.view_bytes"] == leaf_bytes


def test_restore_without_a_trace_records_nothing(tmp_path):
    tree = small_state()
    path = str(tmp_path / "state.ckpt")
    save_checkpoint(path, tree, step=1)
    tracing.reset()
    restore_tree(path, tree, num_readers=2, num_pes=2)
    snap = tracing.snapshot()
    assert snap.spans == [] and snap.counters == {}


def test_process_readers_give_one_read_span_per_splinter(tmp_path):
    data = np.random.default_rng(5).integers(
        0, 256, size=600_000, dtype=np.uint8).tobytes()
    path = str(tmp_path / "blob.bin")
    with open(path, "wb") as f:
        f.write(data)
    ck = CkIO(num_pes=2)
    fh = ck.open_sync(path, FileOptions(
        num_readers=2, splinter_bytes=64 * 1024, backend="process",
        max_workers=2))
    with profiler_trace(tmp_path / "trace"):
        sess = ck.start_read_session_sync(fh, len(data), 0)
        out = ck.read_sync(sess, len(data), 0)
        ck.close_read_session_sync(sess)
    ck.close_sync(fh)
    assert bytes(out) == data
    snap = tracing.snapshot()
    reads = snap.named("ckio.reader.read")
    assert len(reads) == len(sess.plan.splinters)
    assert {s.ids["session"] for s in reads} == {sess.id}
    assert sorted(s.ids["nbytes"] for s in reads) == sorted(
        sp.nbytes for sp in sess.plan.splinters)
    assert all(0 <= s.ns < 60e9 for s in reads)


def test_pooled_sessions_trace_their_admission(tmp_path, token_path):
    from repro.ipc.service import ReaderService, ServiceOptions

    service = ReaderService(ServiceOptions(pool_workers=2, backend="thread"))
    try:
        with profiler_trace(tmp_path / "trace"):
            pipe = CkIOPipeline(token_path, B, S, num_pes=2, num_consumers=4,
                                file_opts=FileOptions(num_readers=2,
                                                      backend="process",
                                                      max_workers=2),
                                service=service, prefetch_depth=2)
            try:
                for step in range(2):
                    x, _ = pipe.get_batch_device(step)
                    jax.block_until_ready(x)
            finally:
                pipe.close()
    finally:
        service.shutdown()
    snap = tracing.snapshot()
    sessions = {s.ids["session"] for s in snap.named("ckio.session.start")}
    # one admission per session that attached before the pipeline closed
    # (at least those of the two steps consumed)
    admits = snap.named("service.admit")
    admitted = {s.ids["session"] for s in admits}
    assert len(admitted) == len(admits) >= 2 and admitted <= sessions
    assert all(s.ns >= 0 and s.ids["workers"] >= 1 for s in admits)
    assert {s.ids["session"] for s in snap.named("ckio.reader.read")} <= \
        admitted
