"""Multi-process reader backend: shm arena, event rings, worker lifecycle.

Covers the ``src/repro/ipc`` subsystem and its ``backend="process"``
integration (``core/buffers.py`` ``ProcessReaderSet``):

* SharedArena create/attach/unlink semantics (zero-copy across mappings);
* EventRing protocol edges: ordering, wraparound under a slow consumer
  (producer throttled, nothing lost/overwritten), stop-vs-publish race;
* worker_main protocol run inline (attach → barrier → drain → DONE, and
  the ERROR reporting path);
* process-backend sessions end-to-end: correctness, consumer-side
  zero-copy (``bytes_copied == 0``), event stream replay, crash fail-fast
  (descriptive error within a bounded timeout — no hang), close racing
  in-flight publishes, and bit-identity with ``backend="thread"`` across
  the host, device and streamed pipeline paths;
* the NetworkModel borrowed-view accounting regression (a view delivery
  is never double-counted as a modeled transfer);
* the streamed per-call ``sharding`` explicit-fallback warning.
"""
from __future__ import annotations

import os
import threading
import time
import warnings

import numpy as np
import pytest

from repro.core import (
    CkIO,
    FileOptions,
    NetworkModel,
    ProcessReaderSet,
    WorkerCrashed,
)
from repro.data import CkIOPipeline, make_token_file
from repro.io.layout import plan_session
from repro.io.posix import write_file
from repro.ipc.ring import (
    ST_ATTACHED,
    ST_DONE,
    ST_ERROR,
    ST_INIT,
    EventRing,
    RingEvent,
    ring_bytes,
)
from repro.ipc.shm import SharedArena
from repro.ipc.worker import (
    ExitAfter,
    RaiseAfter,
    StallReader,
    WorkerSpec,
    worker_main,
)

SEED = 20260728


# A lambda that cannot cross spawn is reported as a pickling error or, when
# the pickler fails looking it up by name, as a "local object" it cannot
# get; either is the failure under test.
UNPICKLABLE = r"[Pp]ickl|Can't get local object"


def _shm_leftovers():
    # This process's segments only (every name carries its creator's pid):
    # test files running in parallel processes hold live segments too.
    d = "/dev/shm"
    if not os.path.isdir(d):
        return []
    mine = f"-{os.getpid()}-"
    return [n for n in os.listdir(d) if n.startswith("ckio-") and mine in n]


@pytest.fixture
def data_file(tmp_path):
    rng = np.random.default_rng(SEED)
    data = rng.integers(0, 256, 1_000_000, dtype=np.uint8).tobytes()
    path = str(tmp_path / "ipc_blob.bin")
    write_file(path, data)
    return path, data


@pytest.fixture(scope="module")
def token_file(tmp_path_factory):
    path = str(tmp_path_factory.mktemp("ipc_tokens") / "tokens.bin")
    make_token_file(path, 16 * 128 * 8 + 64, vocab_size=32000, seed=SEED)
    return path


# -- SharedArena --------------------------------------------------------------
def test_shared_arena_create_attach_zero_copy():
    a = SharedArena.create(8192, tag="t")
    try:
        arr = a.ndarray()
        arr[:] = np.arange(8192, dtype=np.uint8) % 251
        b = SharedArena.attach(a.path, 8192)     # second mapping, own fd
        assert bytes(b.buf) == arr.tobytes()
        b.ndarray()[100] = 77                    # writes are shared
        assert arr[100] == 77
        b.close()
    finally:
        a.close()
    assert a.closed
    a.close()                                    # idempotent


def test_shared_arena_unlink_keeps_mapping_alive():
    a = SharedArena.create(4096)
    path = a.path
    b = SharedArena.attach(path, 4096)
    a.unlink()
    assert not os.path.exists(path)
    b.ndarray()[0] = 9                           # mapping survives the name
    assert a.ndarray()[0] == 9
    b.close()
    a.close()


def test_shared_arena_close_tolerates_live_export():
    a = SharedArena.create(4096)
    arr = a.ndarray()
    arr[:4] = [1, 2, 3, 4]
    a.close()                                    # arr pins the mapping
    assert list(arr[:4]) == [1, 2, 3, 4]         # still readable (pinned)


# -- EventRing ----------------------------------------------------------------
def _ev(i: int, nbytes: int = 64) -> RingEvent:
    return RingEvent(index=i, reader=i % 3, offset=i * nbytes, nbytes=nbytes,
                     arena_off=i * nbytes, t_arrival=float(i), read_dt=0.25)


def test_ring_publish_consume_roundtrip():
    buf = memoryview(bytearray(ring_bytes(8)))
    prod = EventRing(buf, 8, create=True)
    cons = EventRing(buf, 8)                     # attach view of same bytes
    for i in range(5):
        assert prod.publish(_ev(i))
    assert cons.pending() == 5
    got = cons.consume()
    assert [e.index for e in got] == list(range(5))
    assert got[2].offset == 2 * 64 and got[2].read_dt == 0.25
    assert cons.pending() == 0
    # sequence continues across the consume
    assert prod.publish(_ev(5))
    assert [e.index for e in cons.consume()] == [5]


def test_ring_header_handshake_fields():
    buf = memoryview(bytearray(ring_bytes(4)))
    ring = EventRing(buf, 4, create=True)
    assert ring.state() == 0
    ring.set_pid(4242)
    ring.set_touch(123, 1)
    ring.set_state(ST_ATTACHED)
    assert ring.pid() == 4242
    assert ring.touch_report() == (123, 1)
    assert ring.state() == ST_ATTACHED
    ring.set_error("boom: " + "x" * 500)         # truncated, NUL-terminated
    assert ring.state() == ST_ERROR
    assert ring.error_message().startswith("boom: xxx")
    buf8 = memoryview(bytearray(ring_bytes(8)))
    EventRing(buf8, 8, create=True)
    with pytest.raises(ValueError, match="capacity mismatch"):
        EventRing(buf8, 6)                       # header disagrees with caller


def test_ring_wraparound_slow_consumer_loses_nothing():
    """A full ring throttles the producer (backoff) — a slow consumer can
    never be lapped; every record arrives exactly once, in order."""
    slots, total = 4, 64
    buf = memoryview(bytearray(ring_bytes(slots)))
    prod = EventRing(buf, slots, create=True)
    cons = EventRing(buf, slots)
    published = []

    def produce():
        for i in range(total):
            assert prod.publish(_ev(i), timeout=30.0)
            published.append(i)

    th = threading.Thread(target=produce)
    th.start()
    got = []
    while len(got) < total:
        time.sleep(0.002)                        # deliberately slow consumer
        batch = cons.consume(limit=2)
        assert cons.pending() <= slots           # never overfilled
        got.extend(e.index for e in batch)
    th.join(10)
    assert not th.is_alive()
    assert got == list(range(total))
    # the producer genuinely had to wait on the consumer at least once
    assert len(published) == total


def test_ring_torn_publication_never_consumed():
    """Weak-memory-ordering guard: a slot whose stamp is visible but whose
    payload bytes are not (simulated by corrupting one byte) fails the
    seq-keyed CRC and is left unconsumed until the payload is coherent."""
    from repro.ipc.ring import HDR_BYTES, MSG_BYTES

    buf = memoryview(bytearray(ring_bytes(4)))
    prod = EventRing(buf, 4, create=True)
    cons = EventRing(buf, 4)
    assert prod.publish(_ev(7))
    payload_off = HDR_BYTES + MSG_BYTES + 8      # slot 0, past the stamp
    original = buf[payload_off]
    buf[payload_off] = original ^ 0xFF           # payload "not visible yet"
    assert cons.consume() == []                  # stamp alone is not enough
    buf[payload_off] = original                  # stores land
    assert [e.index for e in cons.consume()] == [7]


def test_ring_publish_respects_stop_when_full():
    buf = memoryview(bytearray(ring_bytes(2)))
    prod = EventRing(buf, 2, create=True)
    cons = EventRing(buf, 2)
    assert prod.publish(_ev(0)) and prod.publish(_ev(1))
    cons.request_stop()
    assert prod.publish(_ev(2)) is False         # full + stop → drop, no hang
    assert prod.publish(_ev(3), timeout=0.01) is False
    assert [e.index for e in cons.consume()] == [0, 1]


def test_ring_wait_go_gate():
    buf = memoryview(bytearray(ring_bytes(2)))
    prod = EventRing(buf, 2, create=True)
    cons = EventRing(buf, 2)
    released = threading.Event()

    def waiter():
        assert prod.wait_go()
        released.set()

    th = threading.Thread(target=waiter)
    th.start()
    time.sleep(0.01)
    assert not released.is_set()
    cons.open_gate()
    assert released.wait(5)
    th.join(5)
    # stop beats go: a parked worker is released with False
    buf2 = memoryview(bytearray(ring_bytes(2)))
    ring2 = EventRing(buf2, 2, create=True)
    ring2.request_stop()
    assert ring2.wait_go() is False


# -- worker_main protocol (run inline for determinism + coverage) -------------
def _make_spec(path: str, nbytes: int, *, splinter=64 * 1024, fault=None,
               delay=None, prefault=True):
    plan = plan_session(0, nbytes, 2, splinter_bytes=splinter)
    arena = SharedArena.create(plan.nbytes, tag="t-arena")
    rings = SharedArena.create(ring_bytes(64), tag="t-ring")
    ring = EventRing(rings.buf[: ring_bytes(64)], 64, create=True)
    spec = WorkerSpec(
        worker_id=0, file_path=path,
        arena_path=arena.path, arena_bytes=plan.nbytes, base_offset=0,
        ring_path=rings.path, ring_region_bytes=ring_bytes(64),
        ring_offset=0, ring_slots=64,
        splinters=plan.splinters,
        stripe_bounds=plan.stripe_bounds,
        prefault=prefault, pin_cpus=None, delay_model=delay, fault=fault,
    )
    return spec, plan, arena, rings, ring


def test_worker_main_inline_protocol(data_file):
    path, data = data_file
    spec, plan, arena, rings, ring = _make_spec(path, len(data))
    ring.open_gate()                              # supervisor's role
    worker_main(spec)
    assert ring.state() == ST_DONE
    assert ring.pid() == os.getpid()
    pages, pin = ring.touch_report()
    assert pages > 0                              # prefault reported
    events = ring.consume()
    assert len(events) == len(plan.splinters)
    assert sorted(e.index for e in events) == list(range(len(plan.splinters)))
    assert all(e.read_dt >= 0 for e in events)
    assert bytes(arena.ndarray()) == data         # preadv'd into the mapping
    arena.close()
    rings.close()


def test_worker_main_inline_error_path(data_file):
    path, data = data_file
    spec, plan, arena, rings, ring = _make_spec(
        path, len(data), fault=RaiseAfter(1, "synthetic-fault"))
    ring.open_gate()
    with pytest.raises(SystemExit):
        worker_main(spec)
    assert ring.state() == ST_ERROR
    assert "synthetic-fault" in ring.error_message()
    assert len(ring.consume()) == 1               # one splinter made it
    arena.close()
    rings.close()


def test_worker_main_stop_before_go_exits_clean(data_file):
    path, data = data_file
    spec, plan, arena, rings, ring = _make_spec(path, len(data))
    ring.request_stop()                           # cancelled during spawn
    worker_main(spec)
    assert ring.state() == ST_DONE
    assert ring.consume() == []
    arena.close()
    rings.close()


# -- process backend end-to-end ----------------------------------------------
def test_process_backend_end_to_end(data_file):
    path, data = data_file
    ck = CkIO(num_pes=4, pes_per_node=2)
    fh = ck.open_sync(path, FileOptions(
        num_readers=2, splinter_bytes=128 * 1024, backend="process",
        max_workers=2))
    sess = ck.start_read_session_sync(fh, len(data), 0)
    assert isinstance(sess.readers, ProcessReaderSet)

    # event stream: replay sees everything workers published so far
    seen = []
    sess.readers.join(120)
    tok = sess.subscribe_splinters(seen.append)
    assert sorted(e.index for e in seen) == list(
        range(len(sess.plan.splinters)))
    sess.unsubscribe_splinters(tok)
    assert len(sess.arrival_order) == len(sess.plan.splinters)

    # zero-copy in the consumer process: the view aliases the mapped arena
    view = ck.read_view_sync(sess, 300_000, 4096)
    assert bytes(view) == data[4096: 304_096]
    assert sess.metrics.bytes_copied == 0
    # copy path still works cross-process
    out = ck.read_sync(sess, 100_000, 50_000)
    assert bytes(out) == data[50_000:150_000]
    assert sess.metrics.bytes_copied == 100_000
    ck.close_read_session_sync(sess)
    with pytest.raises(ValueError):
        view.tobytes()                            # borrow died with session
    ck.close_sync(fh)
    assert _shm_leftovers() == []


def test_process_backend_bad_backend_rejected():
    with pytest.raises(ValueError, match="unknown reader backend"):
        FileOptions(backend="fiber").reader_options()


def test_process_backend_delay_model_and_metrics(data_file):
    """Picklable delay hook reaches the worker; per-reader metrics flow
    back over the ring (read counts/bytes per planned owner)."""
    path, data = data_file
    ck = CkIO(num_pes=2)
    fh = ck.open_sync(path, FileOptions(
        num_readers=2, splinter_bytes=256 * 1024, backend="process",
        delay_model=StallReader(reader=0, seconds=0.01)))
    sess = ck.start_read_session_sync(fh, len(data), 0)
    assert sess.readers.join(120)
    m = sess.metrics
    assert m.bytes_read == len(data)
    assert set(m.bytes_per_reader) == {0, 1}
    assert m.read_calls == len(sess.plan.splinters)
    # the stall runs before each of reader 0's reads (2 splinters of its
    # stripe), so it shows up in session wall time, not read_dt — same
    # contract as the thread backend's delay_model
    assert m.ingest_seconds() >= 0.02
    ck.close_read_session_sync(sess)
    ck.close_sync(fh)


def test_worker_crash_fails_fast_no_hang(data_file):
    """Acceptance: a worker crash mid-session surfaces a descriptive error
    within a bounded timeout — blocked reads raise instead of hanging."""
    path, data = data_file
    ck = CkIO(num_pes=2)
    fh = ck.open_sync(path, FileOptions(
        num_readers=2, splinter_bytes=64 * 1024, backend="process",
        max_workers=2, worker_fault=ExitAfter(1, code=43)))
    sess = ck.start_read_session_sync(fh, len(data), 0)
    t0 = time.monotonic()
    with pytest.raises(WorkerCrashed, match="exited with code 43"):
        ck.read_sync(sess, len(data), 0, timeout=60)
    assert time.monotonic() - t0 < 60             # bounded, not a timeout
    with pytest.raises(WorkerCrashed):
        sess.readers.join(10)
    with pytest.raises(WorkerCrashed):
        sess.readers.when_available(0, 1024, lambda: None)
    ck.close_read_session_sync(sess)              # teardown still clean
    ck.close_sync(fh)
    assert _shm_leftovers() == []


def test_worker_crash_fails_every_blocked_future(data_file):
    """EVERY request blocked at crash time gets the error — not only the
    first pump to notice (each request's error channel is fed once)."""
    path, data = data_file
    ck = CkIO(num_pes=2, pes_per_node=1)      # 2 nodes → multi-piece reqs
    fh = ck.open_sync(path, FileOptions(
        num_readers=2, splinter_bytes=64 * 1024, backend="process",
        max_workers=2,
        delay_model=StallReader(reader=1, seconds=0.05),
        worker_fault=ExitAfter(2, code=44)))
    sess = ck.start_read_session_sync(fh, len(data), 0)
    futures = [ck.read_future(sess, len(data), 0),
               ck.read_future(sess, len(data) // 2, 0),
               ck.read_view_future(sess, 1024, len(data) - 2048)]
    for f in futures:
        with pytest.raises(WorkerCrashed, match="exited with code 44"):
            f.wait(ck.sched, timeout=30)
    ck.close_read_session_sync(sess)          # no stale raising tasks left
    ck.close_sync(fh)
    assert _shm_leftovers() == []


def test_worker_orphan_guard_inline(data_file):
    """A worker whose supervisor pid no longer matches exits cleanly
    before reading (the SIGKILLed-parent backstop), and the ring
    publish/wait_go loops honor their abort hooks."""
    path, data = data_file
    spec, plan, arena, rings, ring = _make_spec(path, len(data))
    spec.parent_pid = 2 ** 22 + 17            # nobody's parent
    worker_main(spec)                         # exits before attaching
    assert ring.state() == ST_INIT
    assert ring.consume() == []
    arena.close()
    rings.close()
    # abort hooks: a full ring / closed gate release the producer
    buf = memoryview(bytearray(ring_bytes(1)))
    prod = EventRing(buf, 1, create=True)
    assert prod.publish(_ev(0))
    assert prod.publish(_ev(1), should_abort=lambda: True) is False
    assert prod.wait_go(should_abort=lambda: True) is False


def test_pipeline_worker_crash_close_completes_teardown(token_file):
    """A crash inside a pipeline's (future-less read_notify) sessions:
    get_batch raises, and close() still runs teardown to completion —
    the file fd is really closed and no shm leaks — re-raising any
    prefetched session's error only after cleanup."""
    pipe = CkIOPipeline(
        token_file, 16, 127,
        ckio=CkIO(num_pes=4),
        file_opts=FileOptions(num_readers=2, splinter_bytes=32 * 1024,
                              backend="process", max_workers=2,
                              worker_fault=ExitAfter(0, code=45)))
    with pytest.raises(WorkerCrashed, match="exited with code 45"):
        pipe.get_batch(0)
    try:
        pipe.close()
    except WorkerCrashed:
        pass                        # a prefetched session's error, post-cleanup
    assert pipe.file.posix.closed   # teardown really finished
    assert _shm_leftovers() == []


def test_worker_soft_error_reports_message(data_file):
    path, data = data_file
    ck = CkIO(num_pes=2)
    fh = ck.open_sync(path, FileOptions(
        num_readers=1, splinter_bytes=256 * 1024, backend="process",
        worker_fault=RaiseAfter(2, "disk-on-fire")))
    sess = ck.start_read_session_sync(fh, len(data), 0)
    with pytest.raises(WorkerCrashed, match="disk-on-fire"):
        ck.read_sync(sess, len(data), 0, timeout=60)
    ck.close_read_session_sync(sess)
    ck.close_sync(fh)


def test_session_close_races_inflight_publishes(data_file):
    """Closing a session while workers are still reading/publishing drains
    gracefully (stop request → workers exit between splinters) — no
    deadlock, no leaked segments."""
    path, data = data_file
    ck = CkIO(num_pes=2)
    fh = ck.open_sync(path, FileOptions(
        num_readers=2, splinter_bytes=16 * 1024, backend="process",
        max_workers=2, delay_model=StallReader(reader=0, seconds=0.002)))
    sess = ck.start_read_session_sync(fh, len(data), 0)
    sess.readers.wait_attached(60)                # mid-drain, not pre-spawn
    t0 = time.monotonic()
    ck.close_read_session_sync(sess, timeout=120)
    assert time.monotonic() - t0 < 60
    assert sess.readers.stop(30)
    ck.close_sync(fh)
    assert _shm_leftovers() == []


def test_spawn_failure_cleans_up_and_propagates(data_file):
    """An unpicklable hook makes spawn fail at session start: the error
    reaches the caller, nothing leaks in /dev/shm, and no half-created
    session lingers in the Director tables."""
    path, data = data_file
    ck = CkIO(num_pes=2)
    fh = ck.open_sync(path, FileOptions(
        num_readers=2, backend="process",
        delay_model=lambda r, sp: 0.0))        # lambdas can't cross spawn
    with pytest.raises(Exception, match=UNPICKLABLE):
        ck.start_read_session_sync(fh, len(data), 0)
    assert ck.director.sessions == {}
    assert _shm_leftovers() == []
    ck.close_sync(fh)


def test_sequenced_start_failure_releases_sequence_lock(data_file):
    """A failed sequenced start must release the global sequence lock —
    the next sequenced session would otherwise deadlock forever."""
    path, data = data_file
    ck = CkIO(num_pes=2)
    fh = ck.open_sync(path, FileOptions(
        num_readers=1, backend="process",
        delay_model=lambda r, sp: 0.0))
    with pytest.raises(Exception, match=UNPICKLABLE):
        ck.start_read_session_sync(fh, len(data), 0, sequenced=True)
    fh.opts.delay_model = None                 # fix the options and retry
    sess = ck.start_read_session_sync(fh, len(data), 0, sequenced=True,
                                      timeout=120)
    assert sess.readers.join(120)
    ck.close_read_session_sync(sess)
    ck.close_sync(fh)
    assert _shm_leftovers() == []


def test_process_backend_empty_session(data_file):
    path, _ = data_file
    ck = CkIO(num_pes=2)
    fh = ck.open_sync(path, FileOptions(backend="process", num_readers=2))
    sess = ck.start_read_session_sync(fh, 0, 0)
    assert sess.readers.join(10)
    ck.close_read_session_sync(sess)
    ck.close_sync(fh)
    assert _shm_leftovers() == []


# -- bit-identity: process vs thread ------------------------------------------
def _pipe(path, backend, streaming=False):
    return CkIOPipeline(
        path, 16, 127,
        ckio=CkIO(num_pes=4),
        file_opts=FileOptions(num_readers=2, splinter_bytes=32 * 1024,
                              backend=backend, max_workers=2),
        streaming=streaming,
    )


def test_host_batches_bit_identical_process_vs_thread(token_file):
    pt, pp = _pipe(token_file, "thread"), _pipe(token_file, "process")
    try:
        for s in range(3):
            xt, yt = pt.get_batch(s)
            xp, yp = pp.get_batch(s)
            np.testing.assert_array_equal(xt, xp)
            np.testing.assert_array_equal(yt, yp)
        assert pp.ingest.summary()["host_permute_bytes"] > 0  # host path
    finally:
        pt.close()
        pp.close()
    assert _shm_leftovers() == []


def test_device_batches_bit_identical_process_vs_thread(token_file):
    """Whole-window AND streamed device ingest: backend="process" must be
    bit-identical to the thread backend (the acceptance gate's equality
    half; perf_shm.py re-proves it at benchmark scale)."""
    whole_t, whole_p = _pipe(token_file, "thread"), _pipe(token_file, "process")
    strm_p = _pipe(token_file, "process", streaming=True)
    try:
        for s in range(2):
            xt, yt = whole_t.get_batch_device(s)
            xp, yp = whole_p.get_batch_device(s)
            xs, ys = strm_p.get_batch_device(s)
            np.testing.assert_array_equal(np.asarray(xt), np.asarray(xp))
            np.testing.assert_array_equal(np.asarray(yt), np.asarray(yp))
            np.testing.assert_array_equal(np.asarray(xt), np.asarray(xs))
            np.testing.assert_array_equal(np.asarray(yt), np.asarray(ys))
        # streamed staging really consumed cross-process ring events
        assert strm_p.stream.summary()["splinters_staged"] > 0
        assert strm_p.ingest.summary()["host_permute_bytes"] == 0
    finally:
        whole_t.close()
        whole_p.close()
        strm_p.close()
    assert _shm_leftovers() == []


# -- NetworkModel borrowed-view accounting regression -------------------------
class _CountingNet(NetworkModel):
    def __init__(self):
        super().__init__(bw_bytes_per_s=1e12, latency_s=1e-6)
        self.modeled = []

    def deliver(self, nbytes, same_node, fn):
        if not same_node:
            self.modeled.append(nbytes)
        super().deliver(nbytes, same_node, fn)


def test_borrowed_view_not_double_counted_as_transfer(data_file):
    """Regression (shm groundwork): a cross-node piece delivered as a
    same-address-space view must not count as a modeled transfer AND a
    zero-copy delivery. Pinned: copy deliveries keep cross_node_bytes and
    the NetworkModel transfer; view deliveries move those bytes to
    cross_node_view_bytes, skip the model, and copy nothing."""
    path, data = data_file
    net = _CountingNet()
    ck = CkIO(num_pes=2, pes_per_node=1)          # 2 nodes, client on node 0
    fh = ck.open_sync(path, FileOptions(
        num_readers=2, splinter_bytes=128 * 1024, network=net))
    n = len(data)
    sess = ck.start_read_session_sync(fh, n, 0)
    half = n // 2                                 # reader 1's stripe ≈ [half, n)

    out = ck.read_sync(sess, n, 0)                # copy path
    assert bytes(out) == data
    m = sess.metrics
    copied_cross = m.cross_node_bytes
    assert copied_cross > 0                       # node-1 stripe crossed
    assert m.cross_node_view_bytes == 0
    assert m.bytes_copied == n
    assert sum(net.modeled) == copied_cross       # model saw exactly those

    view = ck.read_view_sync(sess, n - half, half)  # borrowed-view path
    assert bytes(view) == data[half:]
    # reader 1's (cross-node) stripe starts on the aligned boundary
    cross_view = n - sess.plan.stripe_bounds[1][0]
    assert m.cross_node_bytes == copied_cross     # unchanged: no transfer
    assert m.cross_node_view_bytes == cross_view  # locality signal preserved
    assert m.bytes_copied == n                    # nothing copied
    assert sum(net.modeled) == copied_cross       # model never invoked
    summary = m.summary()
    assert summary["cross_node_view_bytes"] == float(cross_view)
    ck.close_read_session_sync(sess)
    ck.close_sync(fh)
    net.shutdown()


# -- streamed per-call sharding: explicit fallback ----------------------------
def test_streamed_sharding_fallback_warns_once(token_file):
    import jax

    sharding = jax.sharding.SingleDeviceSharding(jax.devices()[0])
    ps = _pipe(token_file, "thread", streaming=True)
    pw = _pipe(token_file, "thread", streaming=False)
    try:
        # branch 1: no sharding → streamed path, no warning
        with warnings.catch_warnings():
            warnings.simplefilter("error")
            x0, y0 = ps.get_batch_device(0)
        # branch 2: per-call sharding → whole-window fallback + one warning
        with pytest.warns(RuntimeWarning, match="whole-window"):
            x1, y1 = ps.get_batch_device(1, sharding=sharding)
        with warnings.catch_warnings():           # warned ONCE per pipeline
            warnings.simplefilter("error")
            x2, y2 = ps.get_batch_device(2, sharding=sharding)
        for s, (x, y) in enumerate([(x0, y0), (x1, y1), (x2, y2)]):
            xr, yr = pw.get_batch_device(s)
            np.testing.assert_array_equal(np.asarray(x), np.asarray(xr))
            np.testing.assert_array_equal(np.asarray(y), np.asarray(yr))
    finally:
        ps.close()
        pw.close()
