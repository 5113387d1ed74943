"""Fault-tolerant reader runtime: respawn, re-issue, retry, fault harness.

Covers the recovery layer added across ``io/posix.py`` (transient-I/O retry
with deadline-capped backoff, narrowed advisory-error suppression),
``ipc/ring.py`` (torn-slot CRC retry, worker I/O counter words),
``core/faults.py`` (the seeded deterministic fault-injection harness),
``core/buffers.py`` (worker respawn / splinter re-issue / no-progress
watchdog) and ``core/director.py`` (graceful thread-backend degradation):

* retry policy edges: a transient EIO is absorbed and counted, exhaustion
  surfaces the real errno, short reads loop to completion, a zero deadline
  fails fast;
* advisory narrowing: only the expected-errno class is suppressed (and
  counted); ``EBADF`` propagates;
* ``FaultPlan``: same seed -> identical plan and identical recovery
  counters (the CKIO_FAULT_SEED matrix leg in scripts/ci.sh sweeps this);
* respawn: a crashed worker's replacement attaches to the SAME arena and
  the session completes bit-identically with ``bytes_copied == 0`` and
  every splinter streamed exactly once; budget exhaustion is terminal
  with a descriptive ``WorkerCrashed``;
* re-issue: the supervisor re-reads the dead worker's unfinished tail;
* watchdog: a stalled (not dead) worker is killed and recovered from;
* degraded mode: ``fallback_backend="thread"`` rebuilds a failed process
  session on the thread backend, warning once per FileOptions;
* the ``train/fault.py`` StepSupervisor counts ``WorkerCrashed`` from the
  batch path as a reader failure and replays the step.
"""
from __future__ import annotations

import errno
import os
import threading
import time
import warnings

import numpy as np
import pytest

from repro.core import CkIO, FaultPlan, FileOptions, WorkerCrashed
from repro.core.faults import (
    ComposedIOFault,
    CrashReader,
    CrashSplinter,
    DelayEach,
    FlakyEIO,
    ShortRead,
    TornSlot,
)
from repro.core.metrics import RecoveryMetrics
from repro.io.posix import IOEventCounts, PosixFile, RetryPolicy, write_file
from repro.ipc.ring import EventRing, RingEvent, ring_bytes
from repro.ipc.worker import StallReader

SEED = int(os.environ.get("CKIO_FAULT_SEED", "20260809"))


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
    data = rng.integers(0, 256, 1 << 20, dtype=np.uint8).tobytes()
    path = str(tmp_path / "recovery_blob.bin")
    write_file(path, data)
    return path, data


def _proc_opts(**kw):
    base = dict(num_readers=2, splinter_bytes=128 * 1024,
                backend="process", max_workers=2)
    base.update(kw)
    return FileOptions(**base)


# -- io/posix.py retry policy -------------------------------------------------
def test_retry_absorbs_transient_eio(data_file):
    path, data = data_file
    f = PosixFile.open(path)
    try:
        # ShortRead forces many syscalls (a full-range preadv would finish
        # in one), so the every-3rd EIO actually fires mid-read.
        f.fault = ComposedIOFault((ShortRead(every=1, max_bytes=128 * 1024),
                                   FlakyEIO(every=3)))
        stats = RecoveryMetrics()
        out = np.empty(len(data), dtype=np.uint8)
        n = f.pread_into(0, memoryview(out), stats=stats, fault=f.fault)
        assert n == len(data)
        assert out.tobytes() == data
        assert stats.io_retries > 0
        assert stats.retried_errnos.get(errno.EIO) == stats.io_retries
    finally:
        f.close()


def test_retry_exhaustion_surfaces_errno(data_file):
    path, _ = data_file
    f = PosixFile.open(path)
    try:
        out = np.empty(4096, dtype=np.uint8)
        with pytest.raises(OSError) as ei:
            f.pread_into(0, memoryview(out), fault=FlakyEIO(every=1))
        assert ei.value.errno == errno.EIO
    finally:
        f.close()


def test_retry_zero_deadline_fails_fast(data_file):
    path, _ = data_file
    f = PosixFile.open(path)
    try:
        f.retry = RetryPolicy(deadline_s=0.0)
        out = np.empty(4096, dtype=np.uint8)
        t0 = time.monotonic()
        with pytest.raises(OSError):
            f.pread_into(0, memoryview(out), fault=FlakyEIO(every=1))
        assert time.monotonic() - t0 < 1.0
    finally:
        f.close()


def test_short_reads_loop_to_completion(data_file):
    path, data = data_file
    f = PosixFile.open(path)
    try:
        stats = RecoveryMetrics()
        out = np.empty(len(data), dtype=np.uint8)
        n = f.pread_into(0, memoryview(out), stats=stats,
                         fault=ShortRead(every=1, max_bytes=64 * 1024))
        assert n == len(data)
        assert out.tobytes() == data
        # short reads are normal POSIX behavior, not retries
        assert stats.io_retries == 0
    finally:
        f.close()


def test_composed_fault_short_plus_flaky(data_file):
    path, data = data_file
    f = PosixFile.open(path)
    try:
        stats = RecoveryMetrics()
        hook = ComposedIOFault((ShortRead(every=1, max_bytes=32 * 1024),
                                FlakyEIO(every=7)))
        out = np.empty(len(data), dtype=np.uint8)
        n = f.pread_into(0, memoryview(out), stats=stats, fault=hook)
        assert n == len(data)
        assert out.tobytes() == data
        assert stats.io_retries > 0
    finally:
        f.close()


# -- io/posix.py narrowed advisory suppression --------------------------------
def test_fadvise_expected_errno_suppressed_and_counted(data_file,
                                                       monkeypatch):
    path, _ = data_file
    f = PosixFile.open(path)
    try:
        def raise_einval(*a, **kw):
            raise OSError(errno.EINVAL, "Invalid argument")

        monkeypatch.setattr(os, "posix_fadvise", raise_einval)
        stats = RecoveryMetrics()
        assert f.advise_sequential(0, 4096, stats=stats) is False
        assert stats.suppressed_errors == 1
    finally:
        f.close()


def test_fadvise_unexpected_errno_propagates(data_file, monkeypatch):
    path, _ = data_file
    f = PosixFile.open(path)
    try:
        def raise_ebadf(*a, **kw):
            raise OSError(errno.EBADF, "Bad file descriptor")

        monkeypatch.setattr(os, "posix_fadvise", raise_ebadf)
        with pytest.raises(OSError) as ei:
            f.advise_sequential(0, 4096)
        assert ei.value.errno == errno.EBADF
    finally:
        f.close()


def test_drop_page_cache_missing_path_counted(tmp_path):
    from repro.io.posix import drop_page_cache

    stats = RecoveryMetrics()
    assert drop_page_cache(str(tmp_path / "nope.bin"), stats=stats) is False
    assert stats.suppressed_errors == 1


def test_io_event_counts_module_fallback(data_file, monkeypatch):
    """Without an explicit stats sink, suppressions land in IO_EVENTS."""
    from repro.io import posix as px

    path, _ = data_file
    f = PosixFile.open(path)
    try:
        fresh = IOEventCounts()
        monkeypatch.setattr(px, "IO_EVENTS", fresh)

        def raise_einval(*a, **kw):
            raise OSError(errno.EINVAL, "Invalid argument")

        monkeypatch.setattr(os, "posix_fadvise", raise_einval)
        assert f.advise_sequential(0, 4096) is False
        assert fresh.suppressed == 1
    finally:
        f.close()


# -- core/faults.py: deterministic plan ---------------------------------------
def test_fault_plan_deterministic():
    a = FaultPlan(seed=SEED, crash=True, short_reads=True, flaky_io=True,
                  torn_slots=True, num_readers=2, num_splinters=16)
    b = FaultPlan(seed=SEED, crash=True, short_reads=True, flaky_io=True,
                  torn_slots=True, num_readers=2, num_splinters=16)
    assert a.describe() == b.describe()
    c = FaultPlan(seed=SEED + 1, crash=True, short_reads=True,
                  flaky_io=True, torn_slots=True, num_readers=2,
                  num_splinters=16)
    assert a.describe() != c.describe()


# -- thread backend: retry counters through a session -------------------------
def test_thread_backend_session_counts_retries(data_file):
    path, data = data_file
    ck = CkIO(num_pes=2)
    fh = ck.open_sync(path, FileOptions(num_readers=2,
                                        splinter_bytes=128 * 1024,
                                        io_fault=FlakyEIO(every=3)))
    sess = ck.start_read_session_sync(fh, len(data), 0, timeout=120)
    out = ck.read_sync(sess, len(data), 0, timeout=120)
    assert bytes(out) == data
    assert sess.metrics.recovery.io_retries > 0
    ck.close_read_session_sync(sess)
    ck.close_sync(fh)


# -- process backend: respawn -------------------------------------------------
def test_respawn_completes_bit_identical(data_file):
    path, data = data_file
    ck = CkIO(num_pes=4)
    fh = ck.open_sync(path, _proc_opts(
        recovery="respawn", max_respawns=2,
        worker_fault=CrashReader(reader=0, after=2, code=67)))
    sess = ck.start_read_session_sync(fh, len(data), 0, timeout=120)
    seen, lock = [], threading.Lock()
    sess.subscribe_splinters(
        lambda ev: (lock.acquire(), seen.append(ev.index), lock.release()),
        replay=True)
    view = ck.read_view_sync(sess, len(data), 0, timeout=120)
    assert bytes(view) == data
    m = sess.metrics.recovery
    assert m.respawns == 1
    assert m.reissued_splinters == 2          # the dead worker's tail
    assert m.reissued_bytes == 2 * 128 * 1024
    assert m.recovery_latency_s > 0
    assert sess.metrics.bytes_copied == 0     # still zero-copy
    with lock:
        assert sorted(seen) == list(range(8))  # exactly once each
    assert sorted(sess.arrival_order) == list(range(8))
    ck.close_read_session_sync(sess)
    # recovery counters feed the Director-lifetime aggregate on close
    assert ck.director.recovery.respawns >= 1
    ck.close_sync(fh)
    assert _shm_leftovers() == []


def test_respawn_budget_exhaustion_is_terminal(data_file):
    path, data = data_file
    ck = CkIO(num_pes=4)
    # splinter 0 is poisoned for every generation: each replacement dies
    # on it too, so a budget of 1 must exhaust.
    fh = ck.open_sync(path, _proc_opts(
        recovery="respawn", max_respawns=1,
        worker_fault=CrashSplinter(index=0, code=71)))
    sess = ck.start_read_session_sync(fh, len(data), 0, timeout=120)
    with pytest.raises(WorkerCrashed, match="respawn budget exhausted"):
        ck.read_sync(sess, len(data), 0, timeout=120)
    ck.close_sync(fh)


def test_cascading_respawns_within_budget(data_file):
    """after=1 kills every generation until the tail fits: 3 respawns."""
    path, data = data_file
    ck = CkIO(num_pes=4)
    fh = ck.open_sync(path, _proc_opts(
        recovery="respawn", max_respawns=3,
        worker_fault=CrashReader(reader=0, after=1, code=69)))
    sess = ck.start_read_session_sync(fh, len(data), 0, timeout=120)
    view = ck.read_view_sync(sess, len(data), 0, timeout=120)
    assert bytes(view) == data
    assert sess.metrics.recovery.respawns == 3
    ck.close_read_session_sync(sess)
    ck.close_sync(fh)
    assert _shm_leftovers() == []


# -- process backend: re-issue ------------------------------------------------
def test_reissue_completes_bit_identical(data_file):
    path, data = data_file
    ck = CkIO(num_pes=4)
    fh = ck.open_sync(path, _proc_opts(
        recovery="reissue",
        worker_fault=CrashReader(reader=1, after=1, code=68)))
    sess = ck.start_read_session_sync(fh, len(data), 0, timeout=120)
    seen, lock = [], threading.Lock()
    sess.subscribe_splinters(
        lambda ev: (lock.acquire(), seen.append(ev.index), lock.release()),
        replay=True)
    view = ck.read_view_sync(sess, len(data), 0, timeout=120)
    assert bytes(view) == data
    m = sess.metrics.recovery
    assert m.reissues == 1
    assert m.reissued_splinters == 3
    assert m.respawns == 0
    assert sess.metrics.bytes_copied == 0
    with lock:
        assert sorted(seen) == list(range(8))
    ck.close_read_session_sync(sess)
    ck.close_sync(fh)
    assert _shm_leftovers() == []


# -- process backend: watchdog ------------------------------------------------
def test_watchdog_recovers_stalled_worker(data_file):
    path, data = data_file
    ck = CkIO(num_pes=4)
    fh = ck.open_sync(path, _proc_opts(
        recovery="reissue", worker_watchdog_s=1.0,
        delay_model=StallReader(0, 30.0)))   # would stall 30s unkilled
    sess = ck.start_read_session_sync(fh, len(data), 0, timeout=120)
    t0 = time.monotonic()
    view = ck.read_view_sync(sess, len(data), 0, timeout=120)
    assert time.monotonic() - t0 < 20.0       # did NOT wait out the stall
    assert bytes(view) == data
    m = sess.metrics.recovery
    assert m.watchdog_kills >= 1
    assert m.reissues >= 1
    ck.close_read_session_sync(sess)
    ck.close_sync(fh)


# -- degraded mode: thread-backend fallback -----------------------------------
def test_fallback_to_thread_backend_warns_once(data_file):
    path, data = data_file
    ck = CkIO(num_pes=2)
    # a lambda delay_model is unpicklable -> spawn fails at session start
    fh = ck.open_sync(path, FileOptions(
        num_readers=2, splinter_bytes=256 * 1024, backend="process",
        fallback_backend="thread", delay_model=lambda r, sp: 0.0))
    with warnings.catch_warnings(record=True) as wlog:
        warnings.simplefilter("always")
        sess = ck.start_read_session_sync(fh, len(data), 0, timeout=120)
        out = ck.read_sync(sess, len(data), 0, timeout=120)
        assert bytes(out) == data
        assert sess.metrics.recovery.degraded_mode
        ck.close_read_session_sync(sess)
        sess2 = ck.start_read_session_sync(fh, len(data), 0, timeout=120)
        out2 = ck.read_sync(sess2, len(data), 0, timeout=120)
        assert bytes(out2) == data
        assert sess2.metrics.recovery.degraded_mode
        ck.close_read_session_sync(sess2)
    fb = [w for w in wlog if "falling back" in str(w.message)]
    assert len(fb) == 1                       # sticky: warned once, not per
    assert issubclass(fb[0].category, RuntimeWarning)   # session
    assert ck.director.recovery.degraded_mode
    ck.close_sync(fh)


def test_no_fallback_without_opt_in(data_file):
    path, data = data_file
    ck = CkIO(num_pes=2)
    fh = ck.open_sync(path, FileOptions(
        num_readers=2, backend="process",
        delay_model=lambda r, sp: 0.0))       # unpicklable, no fallback
    with pytest.raises(Exception):
        ck.start_read_session_sync(fh, len(data), 0, timeout=120)
    ck.close_sync(fh)
    assert _shm_leftovers() == []


def test_option_validation():
    with pytest.raises(ValueError, match="recovery"):
        FileOptions(recovery="retry").reader_options()
    with pytest.raises(ValueError, match="fallback"):
        FileOptions(fallback_backend="process").reader_options()


# -- deterministic replay from a seed -----------------------------------------
def test_deterministic_fault_replay(data_file):
    path, data = data_file

    def run_once():
        plan = FaultPlan(seed=SEED, crash=True, num_readers=2,
                         num_splinters=8)
        ck = CkIO(num_pes=4)
        fh = ck.open_sync(path, _proc_opts(
            recovery="reissue", fault_plan=plan))
        sess = ck.start_read_session_sync(fh, len(data), 0, timeout=120)
        view = ck.read_view_sync(sess, len(data), 0, timeout=120)
        ok = bytes(view) == data
        m = sess.metrics.recovery
        counters = (m.reissues, m.reissued_splinters, m.reissued_bytes,
                    m.respawns)
        ck.close_read_session_sync(sess)
        ck.close_sync(fh)
        return plan.describe(), counters, ok

    d1, c1, ok1 = run_once()
    d2, c2, ok2 = run_once()
    assert ok1 and ok2
    assert d1 == d2
    assert c1 == c2
    assert c1[1] > 0                          # the seeded crash really fired


# -- ring CRC-retry path (torn/stale slot stamps) -----------------------------
def test_ring_torn_slot_injection_retried_never_delivered():
    """A stamped-before-payload slot must be re-read, delivered exactly
    once with the CORRECT payload, and never deadlock the consumer."""
    slots = 4
    buf = memoryview(bytearray(ring_bytes(slots)))
    prod = EventRing(buf, slots, create=True)
    prod.fault = TornSlot(every=3, delay_s=0.005)
    cons = EventRing(buf, slots)
    n = 64
    got, errs = [], []

    def producer():
        try:
            for i in range(n):
                ok = prod.publish(RingEvent(
                    index=i, reader=i % 2, offset=i * 100, nbytes=100,
                    arena_off=i * 100, t_arrival=0.0, read_dt=0.0),
                    timeout=30.0)
                assert ok
        except BaseException as e:            # pragma: no cover
            errs.append(e)

    th = threading.Thread(target=producer, daemon=True)
    th.start()
    deadline = time.monotonic() + 30.0
    while len(got) < n:
        assert time.monotonic() < deadline, "consumer deadlocked"
        got.extend(cons.consume())
    th.join(10.0)
    assert not errs
    assert [ev.index for ev in got] == list(range(n))       # in order, once
    assert all(ev.offset == ev.index * 100 for ev in got)   # never torn


def test_process_session_with_torn_ring_slots(data_file):
    path, data = data_file
    ck = CkIO(num_pes=4)
    fh = ck.open_sync(path, _proc_opts(
        ring_fault=TornSlot(every=2, delay_s=0.002)))
    sess = ck.start_read_session_sync(fh, len(data), 0, timeout=120)
    view = ck.read_view_sync(sess, len(data), 0, timeout=120)
    assert bytes(view) == data
    assert sorted(sess.arrival_order) == list(range(8))
    ck.close_read_session_sync(sess)
    ck.close_sync(fh)
    assert _shm_leftovers() == []


# -- worker-side I/O counters cross the ring header ---------------------------
def test_worker_io_retries_folded_into_session(data_file):
    path, data = data_file
    ck = CkIO(num_pes=4)
    fh = ck.open_sync(path, _proc_opts(io_fault=FlakyEIO(every=2)))
    sess = ck.start_read_session_sync(fh, len(data), 0, timeout=120)
    out = ck.read_sync(sess, len(data), 0, timeout=120)
    assert bytes(out) == data
    ck.close_read_session_sync(sess)
    assert sess.metrics.recovery.worker_io_retries > 0
    assert ck.director.recovery.worker_io_retries > 0
    ck.close_sync(fh)


# -- metrics plumbing ---------------------------------------------------------
def test_recovery_metrics_merge_and_summary():
    a = RecoveryMetrics()
    a.record_respawn(2, 1024)
    a.record_io_retry(errno.EIO)
    a.record_watchdog_kill()
    a.record_recovery_latency(0.25)
    b = RecoveryMetrics()
    b.record_reissue(3, 2048)
    b.record_suppressed(errno.EINVAL)
    b.mark_degraded()
    b.merge(a)
    assert b.respawns == 1 and b.reissues == 1
    assert b.reissued_splinters == 5
    assert b.reissued_bytes == 3072
    assert b.io_retries == 1 and b.retried_errnos == {errno.EIO: 1}
    assert b.suppressed_errors == 1
    assert b.watchdog_kills == 1
    assert b.recovery_latency_s == pytest.approx(0.25)
    assert b.degraded_mode
    assert b.recoveries() == 2
    s = b.summary()
    assert s["respawns"] == 1.0 and s["reissues"] == 1.0


# -- train/fault.py: WorkerCrashed is a step failure --------------------------
def test_step_supervisor_recovers_reader_crash(tmp_path):
    import jax.numpy as jnp

    from repro.train.checkpoint import AsyncCheckpointer
    from repro.train.fault import StepSupervisor

    ck = AsyncCheckpointer(str(tmp_path / "ckpts"), keep=2)
    crash = {"left": 1}
    recovered = []

    def batches(step):
        if step == 2 and crash["left"] > 0:
            crash["left"] -= 1
            raise WorkerCrashed("reader worker 0 (pid 1) exited")
        return jnp.asarray(float(step))

    def step_fn(state, batch):
        return {"x": state["x"] + batch}, {}

    sup = StepSupervisor(step_fn, ck, ckpt_every=1, max_retries=3,
                         input_recover=recovered.append)
    state = sup.run({"x": jnp.zeros(())}, batches, 4)
    assert sup.stats.reader_failures == 1
    assert sup.stats.failures == 1
    assert sup.stats.restores == 1
    assert recovered == [2]                   # hook saw the failing step
    assert float(state["x"]) == 0.0 + 1.0 + 2.0 + 3.0
    ck.shutdown()


def test_step_supervisor_terminal_reader_crash(tmp_path):
    import jax.numpy as jnp

    from repro.train.checkpoint import AsyncCheckpointer
    from repro.train.fault import StepSupervisor

    ck = AsyncCheckpointer(str(tmp_path / "c2"), keep=1)

    def batches(step):
        raise WorkerCrashed("respawn budget exhausted")

    sup = StepSupervisor(lambda s, b: (s, {}), ck, ckpt_every=1,
                         max_retries=2)
    with pytest.raises(RuntimeError, match="retries exhausted"):
        sup.run({"x": jnp.zeros(())}, batches, 3)
    assert sup.stats.reader_failures == sup.stats.failures == 3
    ck.shutdown()
