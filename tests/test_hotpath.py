"""Zero-copy hot path: preadv semantics, borrowed-view lifetime, piece
coalescing, bytes_copied accounting, and scheduler batch/O(1) dispatch."""
import os

import numpy as np
import pytest

from repro.core import CkIO, FileOptions
from repro.core.scheduler import TaskScheduler
from repro.io.layout import pieces_for_range, plan_session
from repro.io.posix import HAVE_PREADV, PosixFile


@pytest.fixture(scope="module")
def data_file(tmp_path_factory):
    path = str(tmp_path_factory.mktemp("hotpath") / "data.bin")
    rng = np.random.default_rng(7)
    data = rng.integers(0, 256, size=1_000_000, dtype=np.uint8).tobytes()
    with open(path, "wb") as f:
        f.write(data)
    return path, data


# -- posix pread_into ---------------------------------------------------------

@pytest.mark.parametrize("use_preadv", [True, False])
def test_pread_into_full_read(data_file, use_preadv):
    path, data = data_file
    f = PosixFile.open(path)
    f.use_preadv = use_preadv
    buf = bytearray(4096)
    n = f.pread_into(1000, memoryview(buf))
    assert n == 4096
    assert bytes(buf) == data[1000:5096]
    f.close()


@pytest.mark.parametrize("use_preadv", [True, False])
def test_pread_into_short_read_at_eof(data_file, use_preadv):
    """A range crossing EOF fills up to EOF and returns the partial count
    (the short-read loop must stop, not spin or raise)."""
    path, data = data_file
    f = PosixFile.open(path)
    f.use_preadv = use_preadv
    want = 5000
    buf = bytearray(want)
    off = len(data) - 1234
    n = f.pread_into(off, memoryview(buf))
    assert n == 1234
    assert bytes(buf[:n]) == data[off:]
    # entirely past EOF -> 0 bytes, no error
    assert f.pread_into(len(data) + 10, memoryview(bytearray(64))) == 0
    f.close()


def test_preadv_available_on_this_platform():
    # The container targets Linux; if this ever fails the fallback still
    # keeps everything correct, but the zero-copy claim needs preadv.
    assert HAVE_PREADV


def test_advise_sequential_best_effort(data_file):
    path, _ = data_file
    f = PosixFile.open(path)
    # Must not raise either way; on Linux it should succeed.
    assert f.advise_sequential(0, f.size) in (True, False)
    f.close()


# -- layout coalescing --------------------------------------------------------

def test_pieces_coalesce_by_key():
    plan = plan_session(0, 40960, 4, splinter_bytes=4096, align=1)
    # no key: exact per-stripe split
    raw = pieces_for_range(plan, 0, 40960)
    assert len(raw) == 4
    # all readers same node -> one piece covering the whole range
    one = pieces_for_range(plan, 0, 40960, coalesce_key=lambda r: 0)
    assert one == [(0, 0, 40960)]
    # two-node split (readers 0,1 | 2,3) -> two contiguous runs
    two = pieces_for_range(plan, 0, 40960, coalesce_key=lambda r: r // 2)
    assert len(two) == 2
    assert two[0][1] + two[0][2] == two[1][1]
    assert sum(p[2] for p in two) == 40960


def test_coalesced_read_single_waiter_same_node(data_file):
    """All readers co-located -> a request spanning every stripe is served
    as ONE piece (one waiter, one delivery task)."""
    path, data = data_file
    ck = CkIO(num_pes=4, pes_per_node=4)           # one node
    fh = ck.open_sync(path, FileOptions(num_readers=4,
                                        splinter_bytes=64 * 1024))
    sess = ck.start_read_session_sync(fh, 800_000, 0)
    out = ck.read_sync(sess, 800_000, 0)
    assert bytes(out) == data[:800_000]
    assert sess.metrics.pieces_served == 1
    ck.close_read_session_sync(sess)
    ck.close_sync(fh)


def test_cross_node_read_one_piece_per_node_run(data_file):
    """Readers on distinct nodes -> one piece per contiguous node run (here:
    4 readers, 4 nodes, so 4 pieces), preserving cross-node accounting."""
    path, data = data_file
    ck = CkIO(num_pes=4, pes_per_node=1)           # four nodes
    fh = ck.open_sync(path, FileOptions(num_readers=4,
                                        splinter_bytes=64 * 1024))
    sess = ck.start_read_session_sync(fh, 800_000, 0)
    out = ck.read_sync(sess, 800_000, 0)
    assert bytes(out) == data[:800_000]
    assert sess.metrics.pieces_served == 4
    assert sess.metrics.cross_node_bytes > 0       # client on PE 0, node 0
    ck.close_read_session_sync(sess)
    ck.close_sync(fh)


# -- borrowed-view (zero-copy) path -------------------------------------------

def test_read_view_zero_copy_and_correct(data_file):
    path, data = data_file
    ck = CkIO(num_pes=2, pes_per_node=2)
    fh = ck.open_sync(path, FileOptions(num_readers=3,
                                        splinter_bytes=128 * 1024))
    sess = ck.start_read_session_sync(fh, 500_000, 1000)
    view = ck.read_view_sync(sess, 200_000, 2000)
    assert isinstance(view, memoryview)
    assert view.readonly
    assert bytes(view) == data[2000:202_000]
    # the zero-copy guarantee, proven by the counter:
    assert sess.metrics.bytes_copied == 0
    assert sess.metrics.bytes_served == 200_000
    ck.close_read_session_sync(sess)
    ck.close_sync(fh)


def test_zero_length_read_completes(data_file):
    """A 0-byte read has no pieces; its callback must still fire (split-
    phase) instead of hanging the future."""
    path, _ = data_file
    ck = CkIO(num_pes=2)
    fh = ck.open_sync(path, FileOptions(num_readers=2))
    sess = ck.start_read_session_sync(fh, 10_000, 0)
    out = ck.read_sync(sess, 0, 100, timeout=10)
    assert len(bytes(out)) == 0
    view = ck.read_view_sync(sess, 0, 0, timeout=10)
    assert len(view) == 0
    ck.close_read_session_sync(sess)
    ck.close_sync(fh)


def test_copy_path_counts_bytes_copied(data_file):
    path, data = data_file
    ck = CkIO(num_pes=2, pes_per_node=2)
    fh = ck.open_sync(path, FileOptions(num_readers=2))
    sess = ck.start_read_session_sync(fh, 100_000, 0)
    out = ck.read_sync(sess, 60_000, 100)
    assert bytes(out) == data[100:60_100]
    assert sess.metrics.bytes_copied == 60_000
    ck.close_read_session_sync(sess)
    ck.close_sync(fh)


def test_view_invalidated_after_close(data_file):
    path, data = data_file
    ck = CkIO(num_pes=2, pes_per_node=2)
    fh = ck.open_sync(path, FileOptions(num_readers=2))
    sess = ck.start_read_session_sync(fh, 100_000, 0)
    view = ck.read_view_sync(sess, 10_000, 500)
    assert bytes(view) == data[500:10_500]
    ck.close_read_session_sync(sess)
    with pytest.raises(ValueError):
        view[0]                       # session-lifetime borrow: released
    with pytest.raises(ValueError):
        bytes(view)
    ck.close_sync(fh)


def test_view_with_live_export_survives_close(data_file):
    """A borrow pinned by a live buffer export (np.frombuffer) cannot be
    released — close must not raise, and the memory stays valid for the
    exporter (Python pins it)."""
    path, data = data_file
    ck = CkIO(num_pes=2, pes_per_node=2)
    fh = ck.open_sync(path, FileOptions(num_readers=2))
    sess = ck.start_read_session_sync(fh, 100_000, 0)
    view = ck.read_view_sync(sess, 8_192, 0)
    arr = np.frombuffer(view, dtype=np.uint8)
    ck.close_read_session_sync(sess)   # must not raise BufferError
    assert bytes(arr.tobytes()) == data[:8_192]
    ck.close_sync(fh)


def test_view_survives_until_close(data_file):
    """Views from multiple reads all stay valid while the session is open."""
    path, data = data_file
    ck = CkIO(num_pes=2, pes_per_node=2)
    fh = ck.open_sync(path, FileOptions(num_readers=3))
    sess = ck.start_read_session_sync(fh, 300_000, 0)
    views = [ck.read_view_sync(sess, 10_000, i * 50_000) for i in range(5)]
    for i, v in enumerate(views):
        assert bytes(v) == data[i * 50_000:i * 50_000 + 10_000]
    assert sess.metrics.bytes_copied == 0
    ck.close_read_session_sync(sess)
    ck.close_sync(fh)


# -- pipeline on the zero-copy path -------------------------------------------

def test_pipeline_zero_copy_matches_and_copies_nothing(tmp_path):
    from repro.data import CkIOPipeline, make_token_file

    path = str(tmp_path / "corpus.bin")
    make_token_file(path, 50_000, vocab_size=321, seed=11)
    raw = np.fromfile(path, dtype=np.uint32, offset=4096)
    pipe = CkIOPipeline(path, global_batch=4, seq_len=64, num_pes=2,
                        num_consumers=8, zero_copy=True,
                        file_opts=FileOptions(num_readers=2,
                                              splinter_bytes=32 * 1024))
    need = 4 * 65
    sessions = []
    for s in range(min(pipe.num_steps, 4)):
        x, y = pipe.get_batch(s)
        ref = raw[s * need:(s + 1) * need].reshape(4, 65)
        np.testing.assert_array_equal(x, ref[:, :-1])
        np.testing.assert_array_equal(y, ref[:, 1:])
        sessions.append(pipe._retired[-1])
    for sess in sessions:
        assert sess.metrics.bytes_copied == 0
    pipe.close()


def test_pipeline_copy_mode_still_works(tmp_path):
    from repro.data import CkIOPipeline, make_token_file

    path = str(tmp_path / "corpus_copy.bin")
    make_token_file(path, 30_000, vocab_size=99, seed=12)
    raw = np.fromfile(path, dtype=np.uint32, offset=4096)
    pipe = CkIOPipeline(path, global_batch=2, seq_len=32, num_pes=2,
                        zero_copy=False,
                        file_opts=FileOptions(num_readers=2))
    need = 2 * 33
    x, y = pipe.get_batch(0)
    np.testing.assert_array_equal(x, raw[:need].reshape(2, 33)[:, :-1])
    pipe.close()


# -- scheduler: O(1) dispatch + batching --------------------------------------

def test_enqueue_many_single_batch():
    s = TaskScheduler(num_pes=8)
    order = []
    n = s.enqueue_many((pe, order.append, (f"t{pe}",)) for pe in range(8))
    assert n == 8
    assert s.stats["enqueued"] == 8
    s.pump()
    assert sorted(order) == [f"t{i}" for i in range(8)]


def test_batch_context_defers_and_flushes():
    s = TaskScheduler(num_pes=2)
    seen = []
    with s.batch():
        s.enqueue(0, seen.append, "a")
        s.enqueue(1, seen.append, "b")
        assert s.pump() == 0          # nothing visible until flush
    assert s.pump() == 2
    assert sorted(seen) == ["a", "b"]


def test_batch_nesting_flushes_once_at_outermost():
    s = TaskScheduler(num_pes=1)
    seen = []
    with s.batch():
        s.enqueue(0, seen.append, 1)
        with s.batch():               # nested: no-op
            s.enqueue(0, seen.append, 2)
        assert s.pump() == 0
    assert s.pump() == 2
    assert seen == [1, 2]


def test_ready_deque_many_pes_fifo_and_fair():
    """Dispatch must stay correct with sparse activity across many PEs
    (the O(1) ready-deque replaces a per-pop scan of all queues)."""
    s = TaskScheduler(num_pes=512)
    order = []
    for i in range(3):
        s.enqueue(500, order.append, f"x{i}")
        s.enqueue(7, order.append, f"y{i}")
    s.pump()
    assert [o for o in order if o.startswith("x")] == ["x0", "x1", "x2"]
    assert [o for o in order if o.startswith("y")] == ["y0", "y1", "y2"]
    # interleaved round-robin, not one queue drained wholesale
    assert order[0][0] != order[1][0]


def test_piece_timing_sampled_off_by_default(data_file, tmp_path):
    import jax

    from repro.core import tracing

    path, _ = data_file
    tracing.reset()
    ck = CkIO(num_pes=2)
    fh = ck.open_sync(path, FileOptions(num_readers=2))
    sess = ck.start_read_session_sync(fh, 100_000, 0)
    ck.read_sync(sess, 50_000, 0)
    assert tracing.snapshot().spans == []          # off the hot path
    ck.close_read_session_sync(sess)
    # timed while a profiler trace is being taken: one span per piece
    jax.profiler.start_trace(str(tmp_path))
    try:
        sess2 = ck.start_read_session_sync(fh, 100_000, 0)
        ck.read_sync(sess2, 50_000, 0)
        ck.close_read_session_sync(sess2)
    finally:
        jax.profiler.stop_trace()
    delivered = tracing.snapshot().named("ckio.deliver")
    assert len(delivered) == sess2.metrics.pieces_served > 0
    assert sum(s.ids["nbytes"] for s in delivered) == 50_000
    assert all(s.ids["session"] == sess2.id and s.ns >= 0
               for s in delivered)
    ck.close_sync(fh)
