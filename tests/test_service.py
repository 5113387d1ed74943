"""Persistent reader service: re-arm protocol, recycling, admission, faults.

Covers ``ipc/service.py`` end to end:

* ``ArenaPool`` unit behavior: power-of-two size classes, recycle hits
  keep the segment (generation bumped), ``check_generation`` fails stale
  views fast, quarantined releases unlink instead of recycling, the free
  list is bounded;
* the re-arm protocol matrix on BOTH pool substrates (``backend="thread"``
  and ``"process"``): K back-to-back sessions through one pool are
  bit-identical and zero-copy, epochs strictly increase, sessions 2..K
  recycle the arena, the service counters (admitted / checkout / rearms /
  completed) reconcile;
* FileSet shards through the pool: a sharded session drains bit-identically
  with per-shard read accounting intact;
* faults on the pooled path (process substrate — the crash hooks call
  ``os._exit`` and must NEVER run inside the pytest process): a seeded
  ``FaultPlan`` crash mid-re-arm recovers per the session's own
  ``recovery`` option (supervisor re-issue, or a supplementary re-arm wave
  for ``"respawn"``) and the service keeps serving afterwards;
* sibling containment (the shutdown-vs-recovery fix): a pooled worker
  crash under ``recovery="none"`` fails ITS session alone — the concurrent
  sibling session completes bit-identically, exactly the dead worker is
  evicted, and the pool lazily replaces it for the next session;
* MPSC hygiene: a ring event carrying an epoch that matches no live
  session is dropped + counted (``ServiceMetrics.stale_events``), never
  delivered;
* admission: with the inflight cap and queue both full, ``submit`` raises
  a descriptive ``ServiceBusy`` (counted as rejected); with
  ``use_service`` left at auto the Director falls back to legacy
  per-session spawn and the session completes un-pooled.

Thread-substrate tests keep the matrix fast; the process substrate pays
one real spawn per service and is used where process death semantics are
the subject.
"""
from __future__ import annotations

import os
import time

import numpy as np
import pytest

from repro.core import CkIO, FileOptions, WorkerCrashed
from repro.core.faults import CrashReader, FaultPlan
from repro.data import FileSet, write_token_shards
from repro.io.posix import write_file
from repro.ipc.ring import RingEvent
from repro.ipc.service import (
    ArenaPool,
    ReaderService,
    ServiceBusy,
    ServiceOptions,
    _size_class,
)
from repro.ipc.shm import StaleArenaView

SEED = int(os.environ.get("CKIO_FAULT_SEED", "20260809"))


def _shm_leftovers():
    # This process's segments only (every name carries its creator's pid):
    # test files running in parallel processes hold live segments too.
    d = "/dev/shm"
    if not os.path.isdir(d):
        return []
    mine = f"-{os.getpid()}-"
    return [n for n in os.listdir(d) if n.startswith("ckio-") and mine in n]


@pytest.fixture(autouse=True)
def _clean_shm():
    # Leftover-free /dev/shm is asserted per test; scrub debris a PRIOR
    # (failed) test left behind so the assertion stays self-contained.
    for n in _shm_leftovers():
        try:
            os.unlink(os.path.join("/dev/shm", n))
        except OSError:
            pass
    yield


@pytest.fixture
def data_file(tmp_path):
    rng = np.random.default_rng(SEED)
    data = rng.integers(0, 256, 1 << 20, dtype=np.uint8).tobytes()
    path = str(tmp_path / "service_blob.bin")
    write_file(path, data)
    return path, data


def _opts(**kw):
    base = dict(num_readers=2, splinter_bytes=128 * 1024,
                backend="process", max_workers=2)
    base.update(kw)
    return FileOptions(**base)


def _service(ck, **kw):
    base = dict(pool_workers=2, backend="thread")
    base.update(kw)
    svc = ReaderService(ServiceOptions(**base))
    ck.director.attach_service(svc)
    return svc


# -- ArenaPool ----------------------------------------------------------------
def test_size_class_pow2_buckets():
    q = 1 << 20
    assert _size_class(1, q) == q
    assert _size_class(q, q) == q
    assert _size_class(q + 1, q) == 2 * q
    assert _size_class(3 * q, q) == 4 * q


def test_arena_pool_recycles_and_bumps_generation():
    pool = ArenaPool(max_segments=4, quantum=1 << 16)
    try:
        a1, recycled = pool.acquire(10_000)
        assert not recycled and a1.generation == 1
        assert a1.nbytes == 1 << 16               # size-class, not request
        name = a1.path
        pool.release(a1)
        assert pool.free_segments() == 1
        a2, recycled = pool.acquire(50_000)       # fits the same class
        assert recycled and a2 is a1 and a2.generation == 2
        # a view captured under generation 1 fails fast, never aliases
        with pytest.raises(StaleArenaView):
            a2.check_generation(1)
        a2.check_generation(2)
        assert a2.path == name                    # same prefaulted segment
        pool.release(a2)
    finally:
        pool.shutdown()
    assert _shm_leftovers() == []


def test_arena_pool_quarantine_unlinks_instead_of_recycling():
    pool = ArenaPool(max_segments=4, quantum=1 << 16)
    try:
        a, _ = pool.acquire(1 << 16)
        pool.release(a, quarantine=True)          # pinned export: never reuse
        assert pool.free_segments() == 0
        assert a.closed
    finally:
        pool.shutdown()
    assert _shm_leftovers() == []


def test_arena_pool_free_list_is_bounded():
    pool = ArenaPool(max_segments=1, quantum=1 << 16)
    try:
        a, _ = pool.acquire(1 << 16)
        b, _ = pool.acquire(1 << 16)
        pool.release(a)
        pool.release(b)                           # over capacity: unlinked
        assert pool.free_segments() == 1
        assert b.closed and not a.closed
    finally:
        pool.shutdown()
    assert _shm_leftovers() == []


# -- re-arm protocol matrix ---------------------------------------------------
@pytest.mark.parametrize("substrate", ["thread", "process"])
def test_back_to_back_sessions_rearm_one_pool(data_file, substrate):
    """Three sessions through one pool: bit-identical, zero-copy, strictly
    increasing epochs, arena recycled from session 2 on, and the service
    counters reconcile with what ran."""
    path, data = data_file
    ck = CkIO(num_pes=4)
    svc = _service(ck, backend=substrate)
    try:
        fh = ck.open_sync(path, _opts())
        epochs = []
        for i in range(3):
            sess = ck.start_read_session_sync(fh, len(data), 0, timeout=120)
            view = ck.read_view_sync(sess, len(data), 0, timeout=120)
            assert bytes(view) == data
            del view
            m = sess.metrics.summary()
            assert m["pooled"] == 1.0
            assert sess.metrics.bytes_copied == 0
            assert bool(m["arena_recycled"]) == (i > 0)
            epochs.append(sess.metrics.service_epoch)
            ck.close_read_session_sync(sess)
        ck.close_sync(fh)
        assert epochs == sorted(epochs) and len(set(epochs)) == 3
        sm = svc.metrics
        assert sm.admitted == 3 and sm.checkout_count == 3
        assert sm.rearms == 6                     # 3 sessions x 2 workers
        assert sm.completed == 3                  # Director observer path
        assert sm.arena_hits == 2 and sm.arena_misses == 1
        assert sm.workers_spawned == 2 and sm.workers_evicted == 0
        assert svc.pool_size() == 2 and svc.idle_workers() == 2
    finally:
        svc.shutdown()
    assert _shm_leftovers() == []


def test_concurrent_sessions_share_one_pool(data_file):
    """Four concurrent sessions over disjoint windows, one 2-worker pool:
    the MPSC poller keeps per-session fan-out separate (bit-identity per
    window, per-session zero-copy)."""
    path, data = data_file
    ck = CkIO(num_pes=4)
    svc = _service(ck, pool_workers=2, max_sessions=4)
    try:
        fh = ck.open_sync(path, _opts(num_readers=1, max_workers=1))
        win = len(data) // 4
        sessions = [ck.start_read_session_sync(fh, win, i * win, timeout=120)
                    for i in range(4)]
        for i, sess in enumerate(sessions):
            view = ck.read_view_sync(sess, win, i * win, timeout=120)
            assert bytes(view) == data[i * win:(i + 1) * win]
            del view
            assert sess.metrics.pooled
            assert sess.metrics.bytes_copied == 0
        for sess in sessions:
            ck.close_read_session_sync(sess)
        ck.close_sync(fh)
        assert svc.metrics.stale_events == 0
        assert svc.metrics.occupancy_hwm <= 2     # never more than the pool
    finally:
        svc.shutdown()
    assert _shm_leftovers() == []


def test_fileset_shards_through_service(tmp_path):
    """A sharded FileSet session on the pool: splinters route to the right
    backing files (bit-identity + per-shard read accounting) and a second
    session re-arms over the same shards."""
    rows = 32 * 1024                              # 128 KiB per shard (uint32)
    rng = np.random.default_rng(SEED)
    arr = rng.integers(0, 2**31, size=2 * rows, dtype=np.uint32)
    fs = FileSet.build(write_token_shards(str(tmp_path), arr, [rows, rows]))
    ck = CkIO(num_pes=4)
    svc = _service(ck)
    try:
        fh = ck.open_fileset_sync(fs, _opts(splinter_bytes=64 * 1024))
        for _ in range(2):
            sess = ck.start_read_session_sync(fh, fs.data_bytes, 0,
                                              timeout=120)
            view = ck.read_view_sync(sess, fs.data_bytes, 0, timeout=120)
            assert bytes(view) == arr.tobytes()
            del view
            assert sess.metrics.pooled
            assert sess.metrics.bytes_copied == 0
            assert sess.metrics.shard_bytes[0] == rows * 4
            assert sess.metrics.shard_bytes[1] == rows * 4
            ck.close_read_session_sync(sess)
        ck.close_sync(fh)
    finally:
        svc.shutdown()
    assert _shm_leftovers() == []


# -- faults on the pooled path (process substrate: crash hooks os._exit) ------
def test_crash_mid_rearm_respawn_keeps_service_alive(data_file):
    """Session 2 of 3 loses a pooled worker mid-drain: the unfinished tail
    re-arms on a supplementary wave (session-level ``recovery="respawn"``),
    completion is bit-identical, exactly one worker is evicted, and
    session 3 runs on the lazily replenished pool."""
    path, data = data_file
    ck = CkIO(num_pes=4)
    svc = _service(ck, backend="process")
    try:
        fh_ok = ck.open_sync(path, _opts(splinter_bytes=256 * 1024))
        fh_bad = ck.open_sync(path, _opts(
            splinter_bytes=256 * 1024, recovery="respawn", max_respawns=2,
            worker_fault=CrashReader(reader=1, after=1, code=66)))

        def drain(fh):
            sess = ck.start_read_session_sync(fh, len(data), 0, timeout=120)
            view = ck.read_view_sync(sess, len(data), 0, timeout=120)
            assert bytes(view) == data
            del view
            assert sess.metrics.pooled
            assert sess.metrics.bytes_copied == 0
            m = sess.metrics
            ck.close_read_session_sync(sess)
            return m

        drain(fh_ok)                              # session 1: clean re-arm
        m2 = drain(fh_bad)                        # session 2: crash + respawn
        assert m2.recovery.respawns == 1
        assert m2.recovery.reissued_splinters >= 1
        m3 = drain(fh_ok)                         # session 3: pool healed
        assert m3.recovery.respawns == 0
        ck.close_sync(fh_ok)
        ck.close_sync(fh_bad)
        assert svc.metrics.workers_evicted == 1
        assert svc.metrics.sessions_failed == 0
        assert svc.pool_size() == 2               # lazy replacement landed
    finally:
        svc.shutdown()
    assert _shm_leftovers() == []


def test_fault_plan_crash_reissue_on_pool(data_file):
    """Seeded FaultPlan crash against the pooled backend with
    ``recovery="reissue"``: the supervisor re-reads the dead worker's tail,
    the session completes bit-identically, the service keeps serving."""
    path, data = data_file
    plan = FaultPlan(seed=SEED, crash=True, num_readers=2, num_splinters=8)
    ck = CkIO(num_pes=4)
    svc = _service(ck, backend="process")
    try:
        fh = ck.open_sync(path, _opts(recovery="reissue", fault_plan=plan))
        sess = ck.start_read_session_sync(fh, len(data), 0, timeout=120)
        view = ck.read_view_sync(sess, len(data), 0, timeout=300)
        assert bytes(view) == data
        del view
        m = sess.metrics.recovery
        assert m.reissues >= 1 and m.reissued_splinters >= 1
        ck.close_read_session_sync(sess)
        ck.close_sync(fh)
        assert svc.metrics.workers_evicted >= 1
        # the pool still serves: a clean session after the crash
        fh2 = ck.open_sync(path, _opts())
        sess2 = ck.start_read_session_sync(fh2, len(data), 0, timeout=120)
        assert bytes(ck.read_view_sync(sess2, len(data), 0,
                                       timeout=120)) == data
        ck.close_read_session_sync(sess2)
        ck.close_sync(fh2)
    finally:
        svc.shutdown()
    assert _shm_leftovers() == []


def test_worker_crash_never_tears_down_sibling_session(data_file):
    """The containment fix: session A (``recovery="none"``) loses its
    pooled worker and fails ALONE with a WorkerCrashed; concurrent sibling
    session B on the same pool completes bit-identically, and only the
    dead worker was evicted."""
    path, data = data_file
    ck = CkIO(num_pes=4)
    svc = _service(ck, backend="process", pool_workers=4, max_sessions=2)
    try:
        fh_bad = ck.open_sync(path, _opts(
            recovery="none",
            worker_fault=CrashReader(reader=0, after=0, code=67)))
        fh_ok = ck.open_sync(path, _opts())
        sess_a = ck.start_read_session_sync(fh_bad, len(data), 0, timeout=120)
        sess_b = ck.start_read_session_sync(fh_ok, len(data), 0, timeout=120)
        with pytest.raises(WorkerCrashed):
            ck.read_sync(sess_a, len(data), 0, timeout=120)
        view = ck.read_view_sync(sess_b, len(data), 0, timeout=120)
        assert bytes(view) == data                # sibling unharmed
        del view
        assert sess_b.metrics.bytes_copied == 0
        ck.close_read_session_sync(sess_a)
        ck.close_read_session_sync(sess_b)
        assert svc.metrics.sessions_failed == 1
        assert svc.metrics.workers_evicted == 1   # only the dead one
        # lazy replacement: the next session still gets a full grant
        sess_c = ck.start_read_session_sync(fh_ok, len(data), 0, timeout=120)
        assert bytes(ck.read_view_sync(sess_c, len(data), 0,
                                       timeout=120)) == data
        ck.close_read_session_sync(sess_c)
        assert svc.pool_size() == 4
        ck.close_sync(fh_bad)
        ck.close_sync(fh_ok)
    finally:
        svc.shutdown()
    assert _shm_leftovers() == []


# -- MPSC hygiene -------------------------------------------------------------
def test_stale_epoch_event_dropped_and_counted(data_file):
    """An event published under an epoch no live session owns (late worker
    of a torn-down generation, or corruption) is dropped + counted — and
    the pool keeps serving normally afterwards."""
    path, data = data_file
    ck = CkIO(num_pes=4)
    svc = _service(ck)
    try:
        fh = ck.open_sync(path, _opts())
        sess = ck.start_read_session_sync(fh, len(data), 0, timeout=120)
        assert bytes(ck.read_view_sync(sess, len(data), 0,
                                       timeout=120)) == data
        ck.close_read_session_sync(sess)
        # Inject into a parked worker's ring: epoch 9999 matches nothing.
        with svc._lock:
            ring = svc._idle[0].ring
        assert ring.publish(RingEvent(
            index=0, reader=0, offset=0, nbytes=4096, arena_off=0,
            t_arrival=0.0, read_dt=0.0, epoch=9999), timeout=5.0)
        deadline = time.monotonic() + 10.0
        while (svc.metrics.stale_events < 1
               and time.monotonic() < deadline):
            time.sleep(0.01)
        assert svc.metrics.stale_events == 1
        # undamaged: the same pool serves the next session
        sess2 = ck.start_read_session_sync(fh, len(data), 0, timeout=120)
        assert bytes(ck.read_view_sync(sess2, len(data), 0,
                                       timeout=120)) == data
        ck.close_read_session_sync(sess2)
        ck.close_sync(fh)
        assert svc.metrics.stale_events == 1      # counted once, not leaked
    finally:
        svc.shutdown()
    assert _shm_leftovers() == []


# -- admission ----------------------------------------------------------------
def test_admission_rejects_with_descriptive_servicebusy(data_file):
    """Inflight cap + queue both full and ``use_service=True`` pins the
    session to the pool: submit raises a ServiceBusy naming the caps, and
    the rejection is counted."""
    path, data = data_file
    ck = CkIO(num_pes=2)
    svc = _service(ck, pool_workers=1, max_sessions=1, max_queue=0)
    try:
        fh = ck.open_sync(path, _opts(
            num_readers=1, max_workers=1, use_service=True))
        sess = ck.start_read_session_sync(fh, len(data), 0, timeout=120)
        with pytest.raises(ServiceBusy, match="saturated"):
            ck.start_read_session_sync(fh, len(data), 0, timeout=120)
        assert svc.metrics.rejected == 1
        assert bytes(ck.read_view_sync(sess, len(data), 0,
                                       timeout=120)) == data
        ck.close_read_session_sync(sess)
        # capacity freed: the pool admits again
        sess2 = ck.start_read_session_sync(fh, len(data), 0, timeout=120)
        ck.close_read_session_sync(sess2)
        ck.close_sync(fh)
    finally:
        svc.shutdown()
    assert _shm_leftovers() == []


def test_saturated_service_falls_back_to_spawn(data_file):
    """With ``use_service`` left at auto, a saturated pool degrades to the
    legacy per-session spawn path: the session completes un-pooled and
    nothing in the service is disturbed."""
    path, data = data_file
    ck = CkIO(num_pes=2)
    svc = _service(ck, pool_workers=1, max_sessions=1, max_queue=0)
    try:
        fh = ck.open_sync(path, _opts(num_readers=1, max_workers=1))
        sess_a = ck.start_read_session_sync(fh, len(data), 0, timeout=120)
        assert sess_a.readers.wait_attached(120.0)
        assert sess_a.metrics.pooled
        sess_b = ck.start_read_session_sync(fh, len(data), 0, timeout=120)
        assert not sess_b.metrics.pooled          # legacy spawn fallback
        assert bytes(ck.read_view_sync(sess_b, len(data), 0,
                                       timeout=120)) == data
        assert sess_b.metrics.bytes_copied == 0
        assert bytes(ck.read_view_sync(sess_a, len(data), 0,
                                       timeout=120)) == data
        ck.close_read_session_sync(sess_b)
        ck.close_read_session_sync(sess_a)
        ck.close_sync(fh)
        assert svc.metrics.rejected == 1
        assert svc.metrics.sessions_failed == 0
        # non-sticky: with capacity back, the next session pools again
        fh2 = ck.open_sync(path, _opts(num_readers=1, max_workers=1))
        sess_c = ck.start_read_session_sync(fh2, len(data), 0, timeout=120)
        assert sess_c.readers.wait_attached(120.0)
        assert sess_c.metrics.pooled
        ck.close_read_session_sync(sess_c)
        ck.close_sync(fh2)
    finally:
        svc.shutdown()
    assert _shm_leftovers() == []


def test_use_service_false_always_spawns(data_file):
    """``use_service=False`` pins to legacy spawn even with a healthy
    service attached."""
    path, data = data_file
    ck = CkIO(num_pes=2)
    svc = _service(ck)
    try:
        fh = ck.open_sync(path, _opts(
            num_readers=1, max_workers=1, use_service=False))
        sess = ck.start_read_session_sync(fh, len(data), 0, timeout=120)
        assert not sess.metrics.pooled
        assert bytes(ck.read_view_sync(sess, len(data), 0,
                                       timeout=120)) == data
        ck.close_read_session_sync(sess)
        ck.close_sync(fh)
        assert svc.metrics.admitted == 0          # never touched the pool
    finally:
        svc.shutdown()
    assert _shm_leftovers() == []
