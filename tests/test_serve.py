"""Serving subsystem: churn/admission interop, batching policy, metrics.

Covers ``src/repro/serve/`` end to end:

* continuous batching vs the sequential oracle is BIT-identical across the
  {thread, process, service} reader backends — slot assignment, admission
  order, and co-residency never change a request's token stream;
* the backpressure path: a saturated ``ReaderService`` (``ServiceBusy``)
  queues admitted requests in the ingester's bounded FIFO and sheds new
  submits with ``ServeOverloaded`` once the queue is full — no admitted
  request is lost or double-answered, and the state machine walks
  open -> queueing -> shedding and back down as the queue drains;
* the inflight-ingest-byte budget trips the same queueing path without a
  service;
* mid-decode eviction/admission: slots turn over while neighbours keep
  decoding (a later request starts before the longest finishes);
* a seeded ``FaultPlan`` worker crash mid-churn recovers exactly one
  request's session (per its own ``recovery`` option) while sibling
  requests keep serving through the same pool;
* the metrics fold: nearest-rank percentiles are monotone in q, and the
  legacy ``BatchServer`` reports true arrival->response latency split into
  queueing + service time.
"""
from __future__ import annotations

import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import CkIO, FileOptions, ServeMetrics, percentile
from repro.core.faults import FaultPlan
from repro.data import FileSet, write_token_shards
from repro.data.tokenfile import read_meta, write_token_file
from repro.ipc.service import ReaderService, ServiceOptions
from repro.serve import (
    BatchServer,
    ContinuousBatcher,
    ModeledEngine,
    ModelEngine,
    Request,
    RequestIngester,
    ServeOverloaded,
    ServeRequest,
    StaticBatcher,
    greedy_generate,
    sequential_oracle,
)

SEED = int(os.environ.get("CKIO_FAULT_SEED", "20260809"))
VOCAB = 97


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
    for n in _shm_leftovers():
        try:
            os.unlink(os.path.join("/dev/shm", n))
        except OSError:
            pass
    yield


def _token_file(tmp_path, n_rows, name="prompts.bin"):
    rng = np.random.default_rng(SEED)
    arr = rng.integers(0, 512, size=(n_rows,), dtype=np.int32)
    path = str(tmp_path / name)
    write_token_file(path, arr)
    return path, arr, read_meta(path)


def _requests(n, rows_per, max_new, eos_id=None, **kw):
    return [
        ServeRequest(rid=i, row_start=i * rows_per, num_rows=rows_per,
                     max_new_tokens=max_new[i], eos_id=eos_id, **kw)
        for i in range(n)
    ]


def _oracle(arr, reqs):
    return sequential_oracle(
        ModeledEngine(slots=1, vocab=VOCAB),
        [arr[r.row_start: r.row_start + r.num_rows] for r in reqs],
        [r.max_new_tokens for r in reqs],
        eos_id=reqs[0].eos_id if reqs else None,
    )


# -- continuous == sequential oracle, across reader backends ------------------
def test_continuous_matches_oracle_thread_fileset(tmp_path):
    """Thread backend over a sharded FileSet: prompt spans cross no shard
    (rows land wholly in one), outputs bit-identical to the oracle."""
    n, L = 8, 64
    rng = np.random.default_rng(SEED)
    arr = rng.integers(0, 512, size=(n * L,), dtype=np.int32)
    fs = FileSet.build(write_token_shards(
        str(tmp_path), arr, [n * L // 2, n * L // 2]))
    ck = CkIO(num_pes=2)
    metrics = ServeMetrics()
    ck.director.add_observer(metrics.record_session)
    fh = ck.open_fileset_sync(fs, FileOptions(num_readers=2,
                                              backend="thread"))
    ing = RequestIngester(ck, fh, fs, metrics)
    bat = ContinuousBatcher(ModeledEngine(slots=3, vocab=VOCAB), ing)
    reqs = _requests(n, L, [3 + (i * 5) % 9 for i in range(n)])
    for r in reqs:
        ing.submit(r)
    done = bat.run()
    assert sorted(r.rid for r in done) == list(range(n))
    outs = {r.rid: r.result for r in done}
    for r, want in zip(reqs, _oracle(arr, reqs)):
        assert outs[r.rid] == want
    assert metrics.ingest_bytes_copied == 0       # zero-copy ingest
    assert metrics.ingest_sessions == n           # one session per request
    ck.close_sync(fh)


def test_continuous_matches_oracle_process(tmp_path):
    """Legacy per-session-spawn process backend: same bit-identity (small
    N — each request session pays a real worker spawn)."""
    n, L = 3, 2048
    path, arr, meta = _token_file(tmp_path, n * L)
    ck = CkIO(num_pes=2)
    fh = ck.open_sync(path, FileOptions(
        num_readers=1, max_workers=1, backend="process"))
    ing = RequestIngester(ck, fh, meta)
    bat = ContinuousBatcher(ModeledEngine(slots=2, vocab=VOCAB), ing)
    reqs = _requests(n, L, [4, 6, 5])
    for r in reqs:
        ing.submit(r)
    done = bat.run(timeout_s=120.0)
    outs = {r.rid: r.result for r in done}
    for r, want in zip(reqs, _oracle(arr, reqs)):
        assert outs[r.rid] == want
    ck.close_sync(fh)
    assert _shm_leftovers() == []


def test_continuous_matches_oracle_service(tmp_path):
    """Pooled ReaderService routing: bit-identity + arena recycling (no
    quarantine — the prompt view never outlives its session)."""
    n, L = 8, 256
    path, arr, meta = _token_file(tmp_path, n * L)
    ck = CkIO(num_pes=2)
    svc = ReaderService(ServiceOptions(pool_workers=2, backend="thread"))
    ck.director.attach_service(svc)
    metrics = ServeMetrics()
    ck.director.add_observer(metrics.record_session)
    try:
        fh = ck.open_sync(path, FileOptions(
            num_readers=1, max_workers=1, backend="process",
            use_service=True))
        # budget = one prompt span: sessions serialize, so recycling MUST
        # happen for the run to finish — a quarantined (pinned) arena would
        # show up as all-miss checkouts below
        ing = RequestIngester(ck, fh, meta, metrics, service=svc,
                              max_inflight_bytes=L * 4)
        bat = ContinuousBatcher(ModeledEngine(slots=3, vocab=VOCAB), ing)
        reqs = _requests(n, L, [2 + (i * 3) % 7 for i in range(n)])
        for r in reqs:
            ing.submit(r)
        done = bat.run(timeout_s=120.0)
        outs = {r.rid: r.result for r in done}
        for r, want in zip(reqs, _oracle(arr, reqs)):
            assert outs[r.rid] == want
        assert metrics.pooled_sessions == n
        assert metrics.ingest_bytes_copied == 0
        # released views never pin the arena -> segments recycle
        assert svc.metrics.arena_hit_rate() > 0.0
        ck.close_sync(fh)
    finally:
        svc.shutdown()
    assert _shm_leftovers() == []


# -- backpressure -------------------------------------------------------------
def test_servicebusy_queues_then_sheds_no_request_lost(tmp_path):
    """Saturated service (1 inflight session, queue 0) + tiny ingest queue:
    early submits are admitted (some via the queue), the rest shed with a
    descriptive ServeOverloaded; every admitted request completes exactly
    once and the state machine walks open->queueing->shedding and back."""
    n, L = 8, 64
    path, arr, meta = _token_file(tmp_path, n * L)
    ck = CkIO(num_pes=2)
    svc = ReaderService(ServiceOptions(pool_workers=2, backend="thread",
                                       max_sessions=1, max_queue=0))
    ck.director.attach_service(svc)
    metrics = ServeMetrics()
    try:
        fh = ck.open_sync(path, FileOptions(
            num_readers=1, max_workers=1, backend="process",
            use_service=True))
        ing = RequestIngester(ck, fh, meta, metrics, max_pending=2,
                              service=svc)
        bat = ContinuousBatcher(ModeledEngine(slots=2, vocab=VOCAB), ing)
        reqs = _requests(n, L, [4] * n)
        admitted, shed = [], []
        for r in reqs:
            try:
                ing.submit(r)
                admitted.append(r)
            except ServeOverloaded as e:
                shed.append(r)
                assert "shed" in str(e) and "queue full" in str(e)
        assert shed, "expected the bounded queue to overflow"
        assert len(admitted) >= 3                 # 1 started + 2 queued
        done = bat.run(timeout_s=120.0)
        # no admitted request lost, none double-answered
        assert sorted(r.rid for r in done) == sorted(r.rid for r in admitted)
        assert all(r.result is not None for r in admitted)
        assert all(r.result is None for r in shed)
        outs = {r.rid: r.result for r in done}
        for r, want in zip(admitted, _oracle(arr, admitted)):
            assert outs[r.rid] == want
        assert metrics.shed == len(shed)
        assert metrics.busy_events >= 1
        assert metrics.transitions.get("open->queueing", 0) >= 1
        assert metrics.transitions.get("queueing->shedding", 0) >= 1
        assert metrics.state == "open"            # walked back down
        ck.close_sync(fh)
    finally:
        svc.shutdown()
    assert _shm_leftovers() == []


def test_inflight_byte_budget_queues_without_service(tmp_path):
    """The second backpressure trigger: open-session prompt bytes over
    ``max_inflight_bytes`` queue new submits even on the thread backend."""
    n, L = 6, 64
    path, arr, meta = _token_file(tmp_path, n * L)
    ck = CkIO(num_pes=2)
    fh = ck.open_sync(path, FileOptions(num_readers=1, backend="thread"))
    nbytes_one = L * 4
    metrics = ServeMetrics()
    ing = RequestIngester(ck, fh, meta, metrics, max_pending=n,
                          max_inflight_bytes=nbytes_one)   # one session max
    bat = ContinuousBatcher(ModeledEngine(slots=2, vocab=VOCAB), ing)
    reqs = _requests(n, L, [3] * n)
    for r in reqs:
        ing.submit(r)
    assert metrics.over_budget_events >= 1
    assert metrics.state == "queueing"
    done = bat.run()
    assert sorted(r.rid for r in done) == list(range(n))
    outs = {r.rid: r.result for r in done}
    for r, want in zip(reqs, _oracle(arr, reqs)):
        assert outs[r.rid] == want
    assert metrics.inflight_bytes_hwm <= nbytes_one
    ck.close_sync(fh)


# -- slot turnover ------------------------------------------------------------
def test_eviction_and_admission_mid_decode(tmp_path):
    """With more requests than slots, a slot must turn over mid-decode:
    some request's first token lands AFTER another's eviction, which a
    static batch never does within a batch."""
    n, L = 5, 32
    path, arr, meta = _token_file(tmp_path, n * L)
    ck = CkIO(num_pes=2)
    fh = ck.open_sync(path, FileOptions(num_readers=1, backend="thread"))
    metrics = ServeMetrics()
    ing = RequestIngester(ck, fh, meta, metrics)
    eng = ModeledEngine(slots=2, vocab=VOCAB)
    bat = ContinuousBatcher(eng, ing)
    reqs = _requests(n, L, [8, 1, 1, 1, 8])
    for r in reqs:
        ing.submit(r)
    done = bat.run()
    assert len(done) == n
    outs = {r.rid: r.result for r in done}
    for r, want in zip(reqs, _oracle(arr, reqs)):
        assert outs[r.rid] == want
    assert metrics.admissions == n > eng.slots    # slots were reused
    assert metrics.evictions == n
    first_evict = min(r.t_done for r in done)
    last_first_token = max(r.t_first_token for r in done)
    assert first_evict < last_first_token         # admission mid-decode
    assert 0.0 < metrics.mean_occupancy() <= 1.0
    ck.close_sync(fh)


def test_eos_eviction(tmp_path):
    """EOS mid-stream evicts early (EOS token included, stream truncated)
    and matches the oracle under the same completion rule."""
    n, L = 2, 32
    path, arr, meta = _token_file(tmp_path, n * L)
    base = sequential_oracle(
        ModeledEngine(slots=1, vocab=VOCAB),
        [arr[i * L:(i + 1) * L] for i in range(n)], [8, 8])
    eos = base[0][2]                              # request 0 hits EOS at pos 2
    ck = CkIO(num_pes=2)
    fh = ck.open_sync(path, FileOptions(num_readers=1, backend="thread"))
    ing = RequestIngester(ck, fh, meta)
    bat = ContinuousBatcher(ModeledEngine(slots=2, vocab=VOCAB), ing)
    reqs = _requests(n, L, [8, 8], eos_id=eos)
    for r in reqs:
        ing.submit(r)
    done = bat.run()
    outs = {r.rid: r.result for r in done}
    for r, want in zip(reqs, _oracle(arr, reqs)):
        assert outs[r.rid] == want
    assert outs[0][-1] == eos and len(outs[0]) <= 8
    ck.close_sync(fh)


# -- static baseline (engine-based) -------------------------------------------
def test_static_batcher_bit_identical_but_batched_latency(tmp_path):
    """The StaticBatcher baseline produces the same tokens (bit-identity)
    but returns every batch member at batch end — its per-request e2e
    latency is bounded below by the batch straggler."""
    n, L = 4, 32
    path, arr, meta = _token_file(tmp_path, n * L)
    ck = CkIO(num_pes=2)
    fh = ck.open_sync(path, FileOptions(num_readers=1, backend="thread"))
    metrics = ServeMetrics()
    ing = RequestIngester(ck, fh, meta, metrics)
    bat = StaticBatcher(ModeledEngine(slots=4, vocab=VOCAB), ing,
                        batch_size=4)
    reqs = _requests(n, L, [1, 2, 3, 9])
    for r in reqs:
        ing.submit(r)
    done = bat.run()
    outs = {r.rid: r.result for r in done}
    for r, want in zip(reqs, _oracle(arr, reqs)):
        assert outs[r.rid] == want
    t_dones = {r.rid: r.t_done for r in done}
    assert len(set(round(t, 6) for t in t_dones.values())) == 1  # batch end
    ck.close_sync(fh)


# -- faults under churn -------------------------------------------------------
def test_fault_plan_crash_mid_churn_recovers_one_request(tmp_path):
    """Seeded FaultPlan worker crash on ONE request's pooled session
    (process substrate — crash hooks os._exit): that session recovers via
    its own ``recovery="reissue"`` and the sibling requests keep serving
    through the same pool, all bit-identical."""
    n, L = 3, 64 * 1024                           # 256 KiB per prompt span
    path, arr, meta = _token_file(tmp_path, n * L)
    plan = FaultPlan(seed=SEED, crash=True, num_readers=2, num_splinters=8)
    ck = CkIO(num_pes=4)
    svc = ReaderService(ServiceOptions(pool_workers=2, backend="process"))
    ck.director.attach_service(svc)
    metrics = ServeMetrics()
    session_metrics = []
    ck.director.add_observer(metrics.record_session)
    ck.director.add_observer(session_metrics.append)
    try:
        common = dict(num_readers=2, max_workers=2,
                      splinter_bytes=32 * 1024, backend="process",
                      use_service=True)
        fh_ok = ck.open_sync(path, FileOptions(**common))
        fh_bad = ck.open_sync(path, FileOptions(
            recovery="reissue", fault_plan=plan, **common))
        ing = RequestIngester(ck, fh_ok, meta, metrics, service=svc)
        bat = ContinuousBatcher(ModeledEngine(slots=2, vocab=VOCAB), ing)
        reqs = _requests(n, L, [4, 4, 4])
        reqs[1].file = fh_bad                     # the faulted request
        for r in reqs:
            ing.submit(r)
        done = bat.run(timeout_s=300.0)
        assert sorted(r.rid for r in done) == list(range(n))
        outs = {r.rid: r.result for r in done}
        for r, want in zip(reqs, _oracle(arr, reqs)):
            assert outs[r.rid] == want
        assert metrics.failed == 0
        # exactly one session recovered; siblings rode clean workers
        recovered = [m for m in session_metrics if m.recovery.reissues > 0]
        assert len(recovered) == 1
        assert svc.metrics.workers_evicted >= 1
        assert svc.metrics.sessions_failed == 0
        ck.close_sync(fh_ok)
        ck.close_sync(fh_bad)
    finally:
        svc.shutdown()
    assert _shm_leftovers() == []


# -- metrics fold -------------------------------------------------------------
def test_percentile_fold_monotone():
    rng = np.random.default_rng(SEED)
    for n in (1, 2, 7, 100, 999):
        vals = rng.exponential(1.0, size=n).tolist()
        qs = [0.0, 10.0, 50.0, 90.0, 99.0, 99.9, 100.0]
        ps = [percentile(vals, q) for q in qs]
        assert ps == sorted(ps)                   # monotone in q
        assert ps[-1] == max(vals)
        assert min(vals) <= ps[0]
    assert percentile([], 99.0) == 0.0


def test_serve_metrics_percentiles_and_states():
    m = ServeMetrics()
    for v in (0.1, 0.5, 0.2, 0.9, 0.3):
        m.record_ingested(v)
    p = m.latency_percentiles("ingest")
    assert p["p50"] <= p["p99"] <= p["p999"] <= 0.9
    m.set_state("queueing")
    m.set_state("queueing")                       # no self-transition
    m.set_state("shedding")
    m.set_state("queueing")
    m.set_state("open")
    assert m.transitions == {"open->queueing": 1, "queueing->shedding": 1,
                             "shedding->queueing": 1, "queueing->open": 1}
    s = m.summary()
    assert s["bp_transitions"] == 4.0
    for k in ("ingest_p50_s", "first_token_p99_s", "e2e_p999_s",
              "mean_occupancy", "sessions_per_s"):
        assert k in s


# -- legacy static path: arrival-time accounting ------------------------------
class _FakeModel:
    """Duck-typed model_zoo.Model: deterministic hash-state decode, jit-safe."""

    vocab = 61

    def init(self, key):
        return {"w": jnp.zeros(())}

    def init_decode_state(self, params, B, budget, frames=None):
        return {"h": jnp.ones((B,), jnp.int32)}

    def decode(self, params, state, batch):
        tok = batch["tokens"][:, -1].astype(jnp.int32)
        h = (state["h"] * 31 + tok + 7) % 1009
        logits = jax.nn.one_hot((h * 17) % self.vocab, self.vocab,
                                dtype=jnp.float32)
        return logits[:, None, :], {"h": h}


def test_batchserver_latency_measured_from_arrival():
    model = _FakeModel()
    params = model.init(None)
    rng = np.random.default_rng(SEED)
    prompts = rng.integers(0, 61, size=(3, 8), dtype=np.int32)
    t_arrive = time.perf_counter() - 0.5          # arrived 500 ms ago
    reqs = [Request(rid=i, prompt=prompts[i], max_new_tokens=4,
                    arrival_t=t_arrive) for i in range(3)]
    server = BatchServer(model, params, batch_size=2)
    done = server.serve(reqs)
    for r in done:
        assert r.latency_s >= 0.5                 # queueing time included
        assert r.queue_wait_s >= 0.5
        assert r.service_s > 0.0
        assert abs((r.queue_wait_s + r.service_s) - r.latency_s) < 0.05
    # legacy callers without arrival stamps: latency == service-side time
    legacy = [Request(rid=9, prompt=prompts[0], max_new_tokens=4)]
    server.serve(legacy)
    assert legacy[0].latency_s < 0.5
    assert legacy[0].arrival_t is not None


def test_model_engine_matches_greedy_generate(tmp_path):
    """ModelEngine continuous decode == per-request greedy_generate (the
    serve_step reference), prompts ingested through CkIO."""
    n, L = 4, 8
    rng = np.random.default_rng(SEED)
    arr = rng.integers(0, 61, size=(n * L,), dtype=np.int32)
    path = str(tmp_path / "fake_prompts.bin")
    write_token_file(path, arr)
    meta = read_meta(path)
    model = _FakeModel()
    params = model.init(None)
    ck = CkIO(num_pes=2)
    fh = ck.open_sync(path, FileOptions(num_readers=1, backend="thread"))
    ing = RequestIngester(ck, fh, meta)
    eng = ModelEngine(model, params, slots=2, seq_budget=L + 6)
    bat = ContinuousBatcher(eng, ing)
    reqs = _requests(n, L, [5, 3, 4, 5])
    for r in reqs:
        ing.submit(r)
    done = bat.run()
    outs = {r.rid: r.result for r in done}
    for r in reqs:
        prompt = arr[r.row_start: r.row_start + r.num_rows]
        want = np.asarray(greedy_generate(
            model, params, jnp.asarray(prompt[None, :]),
            r.max_new_tokens))[0].tolist()
        assert outs[r.rid] == want
    ck.close_sync(fh)
