"""Multi-file sharded sessions: FileSet addressing, shard-aware planning,
sharded streaming, and the cross-backend bit-identity matrix.

Covers the FileSet layer end to end:

* ``read_meta`` torn-header regressions: truncated header, garbage JSON,
  wrong magic, bad dtype/shape fields — each a descriptive ``ValueError``
  naming the path;
* ``FileSet.build`` validation: dtype / inner-shape mismatch across shards,
  truncated shard body;
* global row addressing vs a NumPy concat oracle (seeded sweeps +
  hypothesis when installed): arbitrary shard sizes including empty and
  remainder shards, windows straddling shard boundaries;
* ``ShardedFile``: global-space preads across boundaries, ``bounds_in``,
  ``shard_of``, refcounted close;
* ``plan_session(hard_bounds=...)``: no stripe/splinter spans a shard
  start, >= one reader per hard segment, too-few-readers raises;
* ``device_token_spans``: the pure chunk->device placement function, unit
  tested with fake multi-device index maps (including a non-addressable
  remote span — no jax devices needed);
* the cross-backend bit-identity matrix {thread, process} x {whole-window,
  streaming} x {single-file, FileSet}: identical batches with consumer
  ``bytes_copied == 0``;
* sharded streaming (constructor ``sharding=``): per-chunk staging with NO
  whole-window-fallback ``RuntimeWarning``, ``host_permute_bytes == 0``,
  bit-identical to the unsharded path, ``ShardMetrics`` staged-bytes
  ledger balanced; per-call-sharding mismatch raises;
* recovery interop: ``recovery="respawn"`` on a FileSet session — the
  worker owning one shard dies mid-drain, completion is bit-identical and
  ``RecoveryMetrics.reissued_bytes_by_shard`` attributes the re-read to
  exactly that shard;
* ``drop_remainder`` both ways over a FileSet (the remainder window's
  padding path).
"""
from __future__ import annotations

import os
import threading
import warnings

import numpy as np
import pytest

from hypothesis_compat import HAVE_HYPOTHESIS, given, settings, st

from repro.core import CkIO, FileOptions
from repro.core.faults import CrashReader
from repro.data import CkIOPipeline, FileSet, make_token_file, write_token_shards
from repro.data.fileset import ShardInfo
from repro.data.pipeline import device_token_spans
from repro.data.tokenfile import HEADER_BYTES, MAGIC, read_meta, write_token_file
from repro.io.layout import plan_session
from repro.io.posix import ShardedFile

SEED = 20260809


def _shm_leftovers():
    # This process's segments only (every name carries its creator's pid):
    # test files running in parallel processes hold live segments too.
    d = "/dev/shm"
    if not os.path.isdir(d):
        return []
    mine = f"-{os.getpid()}-"
    return [n for n in os.listdir(d) if n.startswith("ckio-") and mine in n]


@pytest.fixture(scope="module")
def corpus(tmp_path_factory):
    """One flat token file + its token array (the oracle)."""
    d = tmp_path_factory.mktemp("fileset_corpus")
    path = str(d / "tokens.bin")
    make_token_file(path, 16 * 128 * 4 + 64, vocab_size=32000, seed=SEED)
    meta = read_meta(path)
    arr = np.fromfile(path, dtype=meta.dtype, offset=HEADER_BYTES)
    return path, arr


@pytest.fixture(scope="module")
def sharded(corpus, tmp_path_factory):
    """The same corpus split into 4 shards: remainder sizes, one empty."""
    _, arr = corpus
    d = tmp_path_factory.mktemp("fileset_shards")
    counts = [3000, 0, 4096, len(arr) - 7096]
    paths = write_token_shards(str(d), arr, counts)
    return FileSet.build(paths), paths


# -- read_meta torn/corrupt header regressions --------------------------------
def test_read_meta_truncated_header(tmp_path):
    p = str(tmp_path / "torn.bin")
    with open(p, "wb") as f:
        f.write(b"x" * 100)
    with pytest.raises(ValueError, match="truncated token-file header"):
        read_meta(p)
    with pytest.raises(ValueError, match="torn.bin"):
        read_meta(p)


def test_read_meta_garbage_header(tmp_path):
    p = str(tmp_path / "garbage.bin")
    with open(p, "wb") as f:
        f.write(b"\xff" * HEADER_BYTES)
    with pytest.raises(ValueError, match="garbage.bin.*corrupt token-file"):
        read_meta(p)


def test_read_meta_wrong_magic(tmp_path):
    p = str(tmp_path / "notmine.bin")
    with open(p, "wb") as f:
        f.write(b'{"magic": "SOMETHING-ELSE"}'.ljust(HEADER_BYTES))
    with pytest.raises(ValueError, match=f"notmine.bin: not a {MAGIC} file"):
        read_meta(p)


def test_read_meta_bad_fields(tmp_path):
    bad_dtype = str(tmp_path / "bad_dtype.bin")
    with open(bad_dtype, "wb") as f:
        f.write((f'{{"magic": "{MAGIC}", "dtype": "notadtype", '
                 f'"shape": [4]}}').encode().ljust(HEADER_BYTES))
    with pytest.raises(ValueError, match="bad_dtype.bin.*bad dtype/shape"):
        read_meta(bad_dtype)
    bad_shape = str(tmp_path / "bad_shape.bin")
    with open(bad_shape, "wb") as f:
        f.write((f'{{"magic": "{MAGIC}", "dtype": "uint32", '
                 f'"shape": [-4]}}').encode().ljust(HEADER_BYTES))
    with pytest.raises(ValueError, match="bad_shape.bin.*shape"):
        read_meta(bad_shape)


# -- FileSet.build validation --------------------------------------------------
def test_build_rejects_dtype_mismatch(tmp_path):
    a = str(tmp_path / "a.bin")
    b = str(tmp_path / "b.bin")
    write_token_file(a, np.arange(10, dtype=np.uint32))
    write_token_file(b, np.arange(10, dtype=np.uint16))
    with pytest.raises(ValueError, match=r"b\.bin: shard dtype"):
        FileSet.build([a, b])


def test_build_rejects_inner_shape_mismatch(tmp_path):
    a = str(tmp_path / "a.bin")
    b = str(tmp_path / "b.bin")
    write_token_file(a, np.zeros((10, 3), dtype=np.uint32))
    write_token_file(b, np.zeros((10, 4), dtype=np.uint32))
    with pytest.raises(ValueError, match=r"b\.bin: shard inner shape"):
        FileSet.build([a, b])


def test_build_rejects_truncated_body(tmp_path):
    a = str(tmp_path / "a.bin")
    write_token_file(a, np.arange(1000, dtype=np.uint32))
    with open(a, "r+b") as f:
        f.truncate(HEADER_BYTES + 100)
    with pytest.raises(ValueError, match=r"a\.bin: truncated shard body"):
        FileSet.build([a])


def test_build_empty_list_rejected():
    with pytest.raises(ValueError, match="empty path list"):
        FileSet.build([])


# -- global row addressing vs the NumPy concat oracle --------------------------
def _oracle_window(fs: FileSet, arr: np.ndarray, start: int, n: int) -> bytes:
    """Read rows [start, start+n) through shard_ranges_for_rows, straight
    from the shard files, and compare against the concat oracle."""
    got = bytearray()
    for shard_idx, file_off, nb in fs.shard_ranges_for_rows(start, n):
        with open(fs.shards[shard_idx].path, "rb") as f:
            f.seek(file_off)
            piece = f.read(nb)
        assert len(piece) == nb
        got += piece
    assert bytes(got) == arr[start: start + n].tobytes()
    return bytes(got)


def test_addressing_seeded_sweep(tmp_path):
    """Arbitrary shard splits (empty + remainder shards) x random windows,
    every window checked against the concat oracle."""
    rng = np.random.default_rng(SEED)
    arr = rng.integers(0, 2**31, size=5000, dtype=np.uint32)
    for case in range(6):
        nshards = int(rng.integers(1, 7))
        cuts = np.sort(rng.integers(0, len(arr) + 1, size=nshards - 1))
        counts = np.diff(np.concatenate([[0], cuts, [len(arr)]]))
        d = str(tmp_path / f"sweep{case}")
        fs = FileSet.build(write_token_shards(d, arr, counts.tolist()))
        assert fs.num_rows == len(arr)
        assert fs.data_bytes == arr.nbytes
        assert fs.data_offset == 0
        for _ in range(20):
            start = int(rng.integers(0, len(arr)))
            n = int(rng.integers(1, len(arr) - start + 1))
            off, nb = fs.byte_range_for_rows(start, n)
            assert (off, nb) == (start * 4, n * 4)
            _oracle_window(fs, arr, start, n)
        # shard_of_row agrees with searchsorted over the cut points
        for _ in range(50):
            row = int(rng.integers(0, len(arr)))
            i = fs.shard_of_row(row)
            s = fs.shards[i]
            assert s.row_start <= row < s.row_end
            assert fs.shard_of_byte(row * 4) == i


def test_addressing_bounds_checked(sharded):
    fs, _ = sharded
    with pytest.raises(ValueError, match="out of bounds"):
        fs.byte_range_for_rows(-1, 1)
    with pytest.raises(ValueError, match="out of bounds"):
        fs.byte_range_for_rows(0, fs.num_rows + 1)
    with pytest.raises(ValueError, match="out of bounds"):
        fs.shard_of_row(fs.num_rows)
    with pytest.raises(ValueError, match="out of bounds"):
        fs.shard_of_byte(fs.data_bytes)


@settings(max_examples=30, deadline=None)
@given(
    counts=st.lists(st.integers(min_value=0, max_value=40), min_size=1,
                    max_size=6),
    start_frac=st.floats(min_value=0.0, max_value=1.0),
    len_frac=st.floats(min_value=0.0, max_value=1.0),
)
def test_addressing_property(tmp_path_factory, counts, start_frac, len_frac):
    total = sum(counts)
    if total == 0:
        counts = counts + [3]
        total = 3
    rng = np.random.default_rng(SEED + total)
    arr = rng.integers(0, 2**31, size=total, dtype=np.uint32)
    d = tmp_path_factory.mktemp("prop")
    fs = FileSet.build(write_token_shards(str(d), arr, counts))
    start = min(int(start_frac * total), total - 1)
    n = max(1, min(int(len_frac * total), total - start))
    _oracle_window(fs, arr, start, n)
    # straddling resolution covers the window exactly once, in order
    ranges = fs.shard_ranges_for_rows(start, n)
    assert sum(nb for _, _, nb in ranges) == n * 4
    assert [i for i, _, _ in ranges] == sorted({i for i, _, _ in ranges})


# -- ShardedFile: the physical byte space --------------------------------------
def test_sharded_file_preads_across_boundaries(sharded, corpus):
    fs, _ = sharded
    _, arr = corpus
    raw = arr.tobytes()
    f = fs.sharded_file()
    try:
        assert f.size == len(raw)
        assert f.offset == 0
        # windows straddling both populated boundaries
        for off, n in [(0, 100), (12000 * 1 - 8, 64), (3000 * 4 - 4, 12),
                       (7096 * 4 - 100, 300), (len(raw) - 64, 64)]:
            assert f.pread(off, n) == raw[off: off + n]
            out = bytearray(n)
            assert f.pread_into(off, memoryview(out)) == n
            assert bytes(out) == raw[off: off + n]
        assert f.bounds_in(0, len(raw)) == [3000 * 4, 7096 * 4]
        assert f.shard_of(0) == 0
        assert f.shard_of(3000 * 4) == 2      # shard 1 is empty
        assert f.shard_of(len(raw) - 1) == 3
        f.advise_sequential(0, len(raw))
    finally:
        f.close()
    assert f.closed


def test_sharded_file_rejects_gaps():
    with pytest.raises(ValueError, match="gap"):
        ShardedFile.from_segments(
            [("/nonexistent-a", 0, HEADER_BYTES, 100, 0),
             ("/nonexistent-b", 150, HEADER_BYTES, 100, 1)])


# -- shard-aware planning ------------------------------------------------------
def test_plan_hard_bounds_never_spanned(sharded):
    fs, _ = sharded
    bounds = fs.shard_bounds_in(0, fs.data_bytes)
    assert bounds == [3000 * 4, 7096 * 4]
    plan = plan_session(0, fs.data_bytes, 4, splinter_bytes=8 * 1024,
                        hard_bounds=bounds)
    assert plan.hard_bounds == tuple(bounds)
    for b in bounds:
        for lo, hi in plan.stripe_bounds:
            assert not (lo < b < hi), f"stripe [{lo},{hi}) spans bound {b}"
        for sp in plan.splinters:
            assert not (sp.offset < b < sp.end), (
                f"splinter [{sp.offset},{sp.end}) spans bound {b}")
        # every segment got at least one reader: some stripe starts at b
        assert any(lo == b for lo, hi in plan.stripe_bounds if hi > lo)
    # full coverage, in order, no overlap
    pos = 0
    for sp in sorted(plan.splinters, key=lambda s: s.offset):
        assert sp.offset == pos
        pos += sp.nbytes
    assert pos == fs.data_bytes


def test_plan_too_few_readers_for_segments():
    with pytest.raises(ValueError, match="cannot honour"):
        plan_session(0, 4000, 2, splinter_bytes=1024,
                     hard_bounds=[1000, 2000, 3000])


def test_session_bumps_readers_to_cover_shards(sharded):
    """A FileSet session transparently raises num_readers to the hard
    segment count (the Director's pre-plan bump)."""
    fs, _ = sharded
    ck = CkIO(num_pes=4)
    fh = ck.open_fileset_sync(fs, FileOptions(num_readers=1,
                                              splinter_bytes=8 * 1024))
    sess = ck.start_read_session_sync(fh, fs.data_bytes, 0, timeout=120)
    assert sess.plan.num_readers >= 3          # 3 populated segments
    assert sess.plan.hard_bounds == (3000 * 4, 7096 * 4)
    ck.close_read_session_sync(sess)
    ck.close_sync(fh)


# -- device_token_spans: pure placement function -------------------------------
def test_device_token_spans_fake_maps():
    W = 128
    # 4 fake devices, batch split 8 rows -> 2 rows each, full width
    fake = {f"dev{i}": (slice(2 * i, 2 * i + 2), slice(None)) for i in range(4)}
    spans = device_token_spans(fake, 8, W)
    assert spans == {f"dev{i}": (2 * i * W, (2 * i + 2) * W) for i in range(4)}
    # spans tile the window exactly
    ordered = sorted(spans.values())
    assert ordered[0][0] == 0 and ordered[-1][1] == 8 * W
    for (a0, a1), (b0, b1) in zip(ordered, ordered[1:]):
        assert a1 == b0
    # replicated devices (same block on two devices) both get the span
    rep = {"d0": (slice(0, 8), slice(None)), "d1": (slice(0, 8), slice(None))}
    assert device_token_spans(rep, 8, W) == {"d0": (0, 8 * W),
                                             "d1": (0, 8 * W)}


def test_device_token_spans_rejects_seq_split():
    with pytest.raises(ValueError, match="splits the sequence dimension"):
        device_token_spans({"d0": (slice(None), slice(0, 64)),
                            "d1": (slice(None), slice(64, 128))}, 8, 128)


def test_device_token_spans_rejects_strides_and_rank():
    with pytest.raises(ValueError, match="unit-stride"):
        device_token_spans({"d0": (slice(0, 8, 2), slice(None))}, 8, 128)
    with pytest.raises(ValueError, match="2-d"):
        device_token_spans({"d0": (slice(None),)}, 8, 128)


def test_chunk_routing_with_remote_spans():
    """Interval intersection against fake spans: an arriving chunk is split
    between a local and a remote device's span; only the local slice would
    be staged (the multi-host routing math, no jax devices needed)."""
    W = 128
    spans = device_token_spans(
        {"local": (slice(0, 4), slice(None)),
         "remote": (slice(4, 8), slice(None))}, 8, W)
    tok0, ntok = 3 * W, 2 * W                    # straddles the 4*W boundary
    pieces = {}
    for dev, (s0, s1) in spans.items():
        lo, hi = max(tok0, s0), min(tok0 + ntok, s1)
        if lo < hi:
            pieces[dev] = (lo, hi)
    assert pieces == {"local": (3 * W, 4 * W), "remote": (4 * W, 5 * W)}


# -- cross-backend bit-identity matrix -----------------------------------------
B, S = 16, 127


def _pipe(source, backend, streaming=False, **kw):
    return CkIOPipeline(
        source, B, S, ckio=CkIO(num_pes=4),
        file_opts=FileOptions(num_readers=2, splinter_bytes=32 * 1024,
                              backend=backend, max_workers=2),
        streaming=streaming, **kw)


def _drain_device(pipe):
    out = []
    for s in range(pipe.num_steps):
        x, y = pipe.get_batch_device(s)
        out.append((np.asarray(x), np.asarray(y)))
    pipe.close()
    return out


@pytest.fixture(scope="module")
def reference_batches(corpus):
    path, _ = corpus
    return _drain_device(_pipe(path, "thread"))


@pytest.mark.parametrize("backend", ["thread", "process"])
@pytest.mark.parametrize("streaming", [False, True])
@pytest.mark.parametrize("source", ["file", "fileset"])
def test_bit_identity_matrix(corpus, sharded, reference_batches,
                             backend, streaming, source):
    path, _ = corpus
    fs, _ = sharded
    src = fs if source == "fileset" else path
    pipe = _pipe(src, backend, streaming=streaming)
    copied = []
    pipe.ck.director.add_observer(lambda sm: copied.append(sm.bytes_copied))
    out = _drain_device(pipe)
    assert len(out) == len(reference_batches) == 4
    for (x, y), (rx, ry) in zip(out, reference_batches):
        assert np.array_equal(x, rx)
        assert np.array_equal(y, ry)
    # consumer-side zero-copy in every cell of the matrix
    assert copied and all(c == 0 for c in copied)
    assert pipe.ingest.summary()["host_permute_bytes"] == 0
    if backend == "process":
        assert _shm_leftovers() == []


def test_host_path_drop_remainder_both_ways(corpus, sharded):
    """get_batch over a FileSet == single file, with and without the
    remainder window (the 64 leftover tokens pad with pad_id)."""
    path, _ = corpus
    fs, _ = sharded
    for drop in (True, False):
        ref = CkIOPipeline(path, B, S, ckio=CkIO(num_pes=4),
                           file_opts=FileOptions(num_readers=2),
                           drop_remainder=drop, pad_id=7)
        got = CkIOPipeline(fs, B, S, ckio=CkIO(num_pes=4),
                           file_opts=FileOptions(num_readers=2),
                           drop_remainder=drop, pad_id=7)
        assert ref.num_steps == got.num_steps == (4 if drop else 5)
        for s in range(ref.num_steps):
            rx, ry = ref.get_batch(s)
            gx, gy = got.get_batch(s)
            assert np.array_equal(np.asarray(rx), np.asarray(gx))
            assert np.array_equal(np.asarray(ry), np.asarray(gy))
        ref.close()
        got.close()


# -- sharded streaming (constructor sharding=) ---------------------------------
def _one_device_sharding():
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    mesh = Mesh(np.array(jax.devices()[:1]), ("dp",))
    return NamedSharding(mesh, PartitionSpec("dp", None))


@pytest.mark.parametrize("backend", ["thread", "process"])
@pytest.mark.parametrize("streaming", [False, True])
def test_sharded_staging_no_fallback(corpus, sharded, reference_batches,
                                     backend, streaming):
    """Constructor sharding streams each chunk INTO the sharding: batches
    bit-identical to the unsharded path, host_permute_bytes == 0, and the
    whole-window fallback RuntimeWarning NEVER fires."""
    fs, _ = sharded
    sh = _one_device_sharding()
    pipe = _pipe(fs, backend, streaming=streaming, sharding=sh)
    out = []
    with warnings.catch_warnings():
        warnings.simplefilter("error")         # any RuntimeWarning fails
        for s in range(pipe.num_steps):
            x, y = pipe.get_batch_device(s)
            assert x.sharding.is_equivalent_to(sh, 2)
            out.append((np.asarray(x), np.asarray(y)))
        pipe.close()
    for (x, y), (rx, ry) in zip(out, reference_batches):
        assert np.array_equal(x, rx)
        assert np.array_equal(y, ry)
    assert pipe.ingest.summary()["host_permute_bytes"] == 0
    m = pipe.ck.director.shards.summary()
    window = 4 * B * (S + 1) * 4               # 4 steps of (B, S+1) uint32
    assert m["window_bytes"] == window
    # single host: every byte addressable, nothing crosses hosts, and the
    # staged ledger balances — each host stages exactly its slice
    assert m["addressable_bytes"] == window
    assert m["cross_host_placements"] == 0
    if streaming:
        assert m["device_put_calls"] > 4       # per-chunk, not per-window
    else:
        assert m["device_put_calls"] == 4      # one per step per device


def test_sharded_remainder_window(corpus, sharded):
    """drop_remainder=False + sharding: the final short window pads
    on-device and still matches the host path."""
    fs, _ = sharded
    sh = _one_device_sharding()
    host = CkIOPipeline(fs, B, S, ckio=CkIO(num_pes=4),
                        file_opts=FileOptions(num_readers=2,
                                              splinter_bytes=32 * 1024),
                        drop_remainder=False, pad_id=3)
    dev = _pipe(fs, "thread", streaming=True, sharding=sh,
                drop_remainder=False, pad_id=3)
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        for s in range(host.num_steps):
            hx, hy = host.get_batch(s)
            dx, dy = dev.get_batch_device(s)
            assert np.array_equal(np.asarray(hx), np.asarray(dx))
            assert np.array_equal(np.asarray(hy), np.asarray(dy))
    host.close()
    dev.close()


def test_per_call_sharding_mismatch_raises(corpus):
    path, _ = corpus
    import jax

    sh = _one_device_sharding()
    pipe = _pipe(path, "thread", streaming=True, sharding=sh)
    try:
        with pytest.raises(ValueError, match="constructor sharding"):
            pipe.get_batch_device(
                0, sharding=jax.sharding.SingleDeviceSharding(jax.devices()[0]))
        # the matching sharding (and None) both work
        x, _ = pipe.get_batch_device(0, sharding=sh)
        x2, _ = pipe.get_batch_device(1)
        assert np.asarray(x).shape == (B, S)
        assert np.asarray(x2).shape == (B, S)
    finally:
        pipe.close()


# -- recovery interop ----------------------------------------------------------
def test_respawn_attributes_reissue_to_shard(tmp_path):
    """Kill the worker owning shard 1 mid-drain on a 2-shard FileSet:
    completion is bit-identical and RecoveryMetrics attributes the re-read
    bytes to shard 1 (exact — splinters never span shards)."""
    rows = 64 * 1024                            # 256 KiB per shard (uint32)
    rng = np.random.default_rng(SEED)
    arr = rng.integers(0, 2**31, size=2 * rows, dtype=np.uint32)
    fs = FileSet.build(write_token_shards(str(tmp_path), arr, [rows, rows]))
    ck = CkIO(num_pes=4)
    # 2 hard segments -> reader k owns shard k; max_workers=2 -> worker k
    # runs reader k alone. CrashReader(reader=1, after=1) kills worker 1
    # before its 2nd splinter: the unfinished tail is entirely in shard 1.
    fh = ck.open_fileset_sync(fs, FileOptions(
        num_readers=2, splinter_bytes=128 * 1024, backend="process",
        max_workers=2, recovery="respawn", max_respawns=2,
        worker_fault=CrashReader(reader=1, after=1, code=66)))
    sess = ck.start_read_session_sync(fh, fs.data_bytes, 0, timeout=120)
    seen, lock = [], threading.Lock()
    sess.subscribe_splinters(
        lambda ev: (lock.acquire(), seen.append(ev.index), lock.release()),
        replay=True)
    view = ck.read_view_sync(sess, fs.data_bytes, 0, timeout=120)
    assert bytes(view) == arr.tobytes()         # bit-identical completion
    m = sess.metrics.recovery
    assert m.respawns == 1
    assert m.reissued_splinters == 1
    assert dict(m.reissued_bytes_by_shard) == {1: 128 * 1024}
    assert sess.metrics.bytes_copied == 0
    with lock:
        assert sorted(seen) == list(range(4))   # each splinter exactly once
    # per-shard read accounting: re-reads land on the right shard too
    assert sess.metrics.shard_bytes[0] == rows * 4
    assert sess.metrics.shard_bytes[1] == rows * 4
    ck.close_read_session_sync(sess)
    assert ck.director.recovery.reissued_bytes_by_shard.get(1) == 128 * 1024
    ck.close_sync(fh)
    assert _shm_leftovers() == []
