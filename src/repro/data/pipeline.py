"""CkIO-backed training input pipeline — the "ChaNGa integration" analog.

Over-decomposed consumers (feeder clients, many per PE) collectively read each
training step's token window through a CkIO read session, while the device
runs the previous step: a double-buffered, split-phase pipeline that
implements the paper's compute/input overlap at the training-loop level.

Key structural mirror of the paper:
  * consumer count (`num_consumers`) is chosen by the *application* (here:
    microbatch×prefetch structure), completely decoupled from `num_readers`
    (chosen for the file system) — paper §III-B.
  * one read session per step window, prefetched greedily (paper §III-A:
    "read the file chunk-by-chunk (one chunk per session)").
  * consumers are migratable; `resize()` implements elastic scaling by
    re-registering consumers, leaving the reader layer untouched; shrunk
    consumers are deregistered from the location manager (no leaked ids).

Delivery modes:
  * ``zero_copy=True`` (default): consumer reads ride the borrowed-view path
    (``read(dest=None)``) and ``get_batch`` materializes the step's tokens as
    a NumPy array *aliasing the session arena* — zero host copies between the
    preadv into the arena and ``device_put``.
  * ``zero_copy=False``: consumer reads land directly in a per-step NumPy
    arena (one copy, session arena → step arena), with no lifetime caveat.

Device ingest (``get_batch_device``) and its lifetime contract
--------------------------------------------------------------
``get_batch_device(step)`` replaces the host tail of the pipeline: the
borrowed **whole-window arena view** is handed to ``jax.device_put`` exactly
once (the step's only host→device transfer), and batch-major ``(inputs,
labels)`` — including the label shift-by-one and remainder-window padding —
are produced **on device** by the ``kernels/reassemble.py`` gather kernels
(the paper's phase-2 data permutation, moved to where bandwidth is
cheapest). Per step, host code touches file *metadata* only; the
``ingest`` counters (``core.metrics.IngestMetrics``) prove it:
``host_permute_bytes`` stays 0 and ``h2d_transfers`` advances by exactly 1.
(With ``zero_copy=False`` the session→step-arena copy still happens and is
counted as host bytes — only the zero-copy default earns the 0.)

Streamed staging (``streaming=True``)
-------------------------------------
With ``streaming=True`` the device path goes **event-driven**: the pipeline
subscribes to each step session's per-splinter completion stream
(``CkIO.read_stream``) and ships splinters host→device *as they arrive* —
**one ``device_put`` per splinter** (uniform splinter sizes keep the chunk
shapes, and with them the fused consume executable's signature, stable
across steps and arrival permutations; ``h2d_transfers`` advances once per
splinter). ``stage_chunk_bytes`` only batches event-task wakeups: staging
work runs once at least that many bytes are pending (0 = ship on every
event). Transfers respect a bounded in-flight budget
(``max_inflight_stage_bytes``: before exceeding it, the oldest outstanding
transfer — from whichever step stream issued it — is awaited).
``get_batch_device`` then only stages the tail and
reassembles **on device** in one fused dispatch: the arrival-order→
file-order permutation is applied to the chunk *handles* (each splinter is
its own device buffer, so reordering the argument list is free host
metadata work), and ``ops.ingest_chunks_window`` fuses the concatenate with
the batch-major window kernel. A contiguous arrival-ordered staging buffer
— the multi-host/TPU layout — keeps its on-device gather path:
``ops.ingest_chunks_block`` / ``ops.device_ingest`` over the
``data/packing.py`` index maps. Reads for step N+1, H2D staging for step N's tail,
and compute on step N-1 genuinely overlap; ``StreamMetrics``
(``pipe.stream``) proves it — per-splinter arrival→staged latency,
in-flight high-water mark, and the overlap fraction. ``host_permute_bytes``
stays 0 (every staged byte goes straight from the session arena into
``device_put``); ``h2d_transfers`` counts one per chunk. Completeness never
depends on the stream: splinters whose events were dropped (a delivery
racing ``resize()`` — dropped and counted, never rerouted to a reused
consumer slot) are staged from the authoritative event log at finalize.
Batches are bit-identical to the ``streaming=False`` whole-window path.

Sharded staging (constructor ``sharding=``)
-------------------------------------------
A **constructor** ``sharding=`` (any ``jax.sharding.Sharding``) composes
with both device paths instead of fighting them: the sharding's device
blocks over the ``(global_batch, seq_len+1)`` window grid are resolved
ONCE into contiguous flat-token spans (``device_token_spans`` — batch-dim
shardings only; a sharding that splits the sequence dimension raises at
construction). With ``streaming=True`` every arriving splinter is then
routed to its destination device(s) at stage time by pure interval
intersection: each addressable sub-slice is ``device_put`` straight from
the borrowed arena view onto its device (``host_permute_bytes`` stays 0),
and spans owned by another host's devices are *counted*
(``ShardMetrics.cross_host``) and skipped — each host stages exactly its
addressable slice of the window, never the whole window.
``get_batch_device`` then proves coverage from the event log, pads the
remainder tail on-device, binds the per-device row blocks into one global
array with ``jax.make_array_from_single_device_arrays`` (metadata only —
no restage) and applies the label shift under ``jit``. Batches are
bit-identical to the unsharded paths. ``streaming=False`` +
constructor sharding runs the same per-device slicing over the resident
whole-window view (one ``device_put`` per addressable device) through the
same assembly code. A **per-call** ``sharding`` without a constructor
sharding keeps the legacy behaviour: it forces that call onto the
whole-window path — streamed chunks are placed before the call-site
sharding is known, so they cannot satisfy it — and the fallback is
explicit: the first sharded call on a streaming pipeline emits a
``RuntimeWarning`` (once per pipeline) because it forfeits the read/stage
overlap on every sharded step; a run that passes a sharding each step
should pass it at construction time (or construct ``streaming=False``).
``ShardMetrics`` (``pipe.ck.director.shards``) carries both sides of the
ledger: the *read* side (per-shard physical bytes, fed through the
Director's session-close observer) and the *stage* side
(``record_stage``/``record_window``/``record_cross_host`` written here) —
``addressable_bytes < window_bytes`` with ``cross_host_placements > 0``
is the multi-host proof that no host staged bytes it cannot address.

Multi-file corpora (``data/fileset.py`` ``FileSet``)
----------------------------------------------------
Passing a ``FileSet`` manifest as ``path`` opens the whole shard list as
ONE logical byte space (``CkIO.open_fileset``): global row/byte
addressing concatenates the shards' data regions (header pages excluded),
and interior shard starts become hard stripe bounds in every session plan
— no stripe, splinter, or single ``preadv`` ever spans two files. Every
delivery contract in this docstring holds unchanged over a FileSet:

  * **view lifetimes**: a borrowed view or streamed chunk view aliases
    bytes read from exactly one shard (splinters never cross shards) but
    lives in the one session arena, so the lifetime rules are untouched —
    valid until the step retires at the next ``get_batch*``/``close``,
    then ``ValueError`` on access;
  * **process backend**: each ``WorkerSpec`` ships the shard segment
    table and the worker rebuilds its OWN ``ShardedFile`` — one fresh fd
    per shard path, nothing inherited (the same fd-hygiene contract as
    single files);
  * **recovery**: if the worker owning one shard's stripes dies mid-drain
    under ``recovery="respawn"``/``"reissue"``, the standard machinery
    re-reads exactly the unfinished splinters — all within that shard —
    and ``RecoveryMetrics.reissued_bytes_by_shard`` attributes the
    re-read bytes to it (exact, not sampled, because splinters never span
    shards). Completion stays bit-identical; terminal failures behave
    exactly as the single-file contract above;
  * **sharded streaming composes**: per-shard physical reads land in the
    same global window token space, so chunk→device routing and the
    staged-bytes ledger are file-count agnostic.
Note on ``FileOptions(adaptive_splinters=True)``: each splinter-size
change changes the chunk count/shape signature and retraces the fused
consume executable once; the sizer EMA-smooths and 256 KiB-quantizes its
suggestions so sizes converge after the first few sessions, but a
latency-critical run should pin ``splinter_bytes`` statically.

Persistent reader service (constructor ``service=``)
----------------------------------------------------
Passing a ``repro.ipc.service.ReaderService`` attaches it to this
pipeline's Director: every ``backend="process"`` step session then checks
its workers out of the service's persistent pool and its arena out of the
recycled-arena pool instead of spawning processes and creating a fresh
shm segment per step — the per-step session setup drops from worker-spawn
cost (~0.5 s/worker) to one mailbox write + attach barrier
(``benchmarks/perf_service.py`` gates the ratio at >= 5x). Every delivery
contract above holds unchanged (the pooled arena is the same kind of
mapped segment, so zero-copy borrowed views, streamed chunk staging and
``bytes_copied == 0`` are untouched), with these service-specific
amendments:

  * **View lifetime across arena recycling**: borrowed views still die at
    step retirement (``ValueError`` on access), but the pages behind them
    now outlive the session — the segment returns to the pool and is
    recycled into a later session. A view that survives invalidation via
    a live buffer export (an ``np.frombuffer`` array you kept) therefore
    QUARANTINES the segment: the service unlinks it instead of recycling,
    so the export can never silently alias a later step's bytes. Code
    that caches views across sessions can re-validate explicitly with
    ``SharedArena.check_generation(gen)`` (raises ``StaleArenaView``);
    the generation a session ran under is
    ``session.metrics.summary()["service_epoch"]``-adjacent bookkeeping
    on the reader set (``ServiceReaderSet.arena_generation``).
  * **When ``ServiceBusy`` is raised**: admission rejects a session only
    when BOTH the inflight cap (``ServiceOptions.max_sessions``) and the
    FIFO queue (``max_queue``) are full. With ``FileOptions.use_service``
    left at auto (``None``) the Director catches it and falls back to the
    legacy per-session spawn path — the step still runs, it just pays the
    spawn; ``use_service=True`` pins the step to the pool and surfaces
    ``ServiceBusy`` out of the step's futures instead. ``use_service=
    False`` (or simply not attaching a service) keeps the legacy path
    unconditionally.
  * **Degraded fallback to spawn** is per session and non-sticky —
    unlike the ``fallback_backend="thread"`` downgrade, a later step
    re-tries the pool as soon as admission has room.
  * **Failure containment**: a pooled worker crash evicts that worker
    only; the affected step recovers per its own ``FileOptions.recovery``
    (or fails alone) and concurrently running steps/pipelines sharing the
    pool are untouched.
  * **Ownership**: the pipeline never shuts the service down — call
    ``service.shutdown()`` after the last pipeline using it closes
    (``/dev/shm`` is clean only after that).

Serving ingest (``repro.serve`` — the other session-lifetime regime)
--------------------------------------------------------------------
This pipeline is the paper's TRAINING shape: a handful of long-lived
sessions, each spanning a whole step window. The serving subsystem
(``src/repro/serve/``) drives the same CkIO surface from the opposite
end: thousands of short-lived sessions per second, one per request,
each covering only that request's prompt rows of the corpus/FileSet.
The contracts compose rather than fork:

  * **session lifetime per request**: a request's session is opened by
    the ``RequestIngester`` at admission, carries exactly one zero-copy
    ``read_view``, and closes the moment the decode engine has consumed
    the prompt (``engine.admit``) — it never lives past batching, so the
    arena-pool pressure of N inflight requests is N prompt spans, not N
    windows. The borrowed-view lifetime rule is identical to this
    pipeline's: no export may outlive the session, or the pooled segment
    quarantines instead of recycling.
  * **slot eviction is not a CkIO event**: by the time a request decodes
    in a slot its session is already closed; EOS/max-token eviction
    (``ContinuousBatcher``) touches engine state only.
  * **backpressure replaces fallback**: where a training step under
    ``use_service`` auto mode degrades a ``ServiceBusy`` to per-session
    spawn, the serving path *queues* the request (bounded FIFO in the
    ingester) and — only when that queue is also full — rejects the
    submit with ``ServeOverloaded``. An admitted request is never
    dropped; see ``serve/ingest.py`` for the state machine and
    ``core.metrics.ServeMetrics`` (on the same Director observer path as
    every sink above) for the histograms that prove the tail.

Cold-cache reads (``direct_io`` / ``queue_depth`` — io/submit.py)
-----------------------------------------------------------------
First-epoch corpora are COLD: nothing below survives in the page cache,
and the blocking one-pread-per-splinter loop leaves the device idle
between requests. Two ``FileOptions`` knobs change the read engine under
this pipeline without touching any delivery contract above:

  * ``queue_depth >= 2`` keeps that many splinter reads in flight per
    reader (io_uring where the kernel allows, else a preadv pool with
    WILLNEED pipelining; ``readahead_bytes`` advises ahead of the
    submission frontier). Splinters complete — and stream, under
    ``streaming=True`` — in completion order, which the event-driven
    staging path was built for; borrowed views, bit-identity, retry
    accounting and fault hooks are unchanged.
  * ``direct_io=True`` opens the corpus O_DIRECT: reads DMA straight into
    the session arena, bypassing the page cache (the right mode when the
    corpus is read once and would only pollute it). The contract is
    *fail-fast, never fall back silently*: session windows, splinter grid
    and arena must sit on the probed FS block grid (the Director plans
    with ``align=block_size`` automatically; odd session offsets raise
    ``DirectIOError`` at start), sub-block tails go through the buffered
    fd and are counted (``RecoveryMetrics.direct_tail_reads``), and a
    FileSet needs block-aligned shard data regions — an odd-sized
    interior shard is rejected at open, by name.
``adaptive_queue=True`` hands both knobs to the Director's QueueTuner
(core/autotune.py), which hill-climbs (depth, readahead) from observed
session throughput across steps; the explicit fields seed session one.

Topology-aware reader runtime (``FileOptions.topology`` / ``numa_pin``)
-----------------------------------------------------------------------
Passing a ``core.placement.Topology`` in ``file_opts`` turns on the NUMA
levers under this pipeline (``launch/train.py`` exposes them as
``--topology`` — ``auto`` detects the host's NUMA nodes from sysfs, an
integer gives domains-per-node — and ``--numa-pin``):

* **reader placement** sees memory domains: ``placement="near_consumers"``
  spreads readers over the PEs of the consumers' NUMA domains (this
  pipeline passes its consumers' PEs to every session), and
  ``placement="domain_spread"`` puts one reader per domain before doubling
  up. ``consumer_pes=[...]`` pins this pipeline's consumer clients to
  specific PEs (default: round-robin over all PEs) — the lever for
  skewed-consumer locality studies.
* **first-touch arena contract**: with a topology, ``prefault_arena=True``
  no longer zero-fills the session arena up front — instead each reader
  I/O thread faults exactly its own stripe's pages (one byte per page) on
  its own thread before its first read, with ``numa_pin=True`` pinning
  that thread to its domain's host CPUs first. Under Linux first-touch,
  every stripe's memory therefore lands on the domain that reads and
  serves it; the ``np.empty`` arena stays non-zero-filled (no memset pass
  on the session-start critical path), and stolen splinters land in
  already-placed pages. Zero-copy delivery is unchanged: borrowed views
  alias the same arena; ``bytes_copied`` stays 0.
* **accounting**: pieces coalesce per NUMA domain and every delivered
  piece is classified same- vs cross-domain in ``LocalityMetrics``
  (per-session, merged into ``pipe.ck.director.locality`` as step sessions
  close) — ``benchmarks/perf_numa.py`` gates on cross-domain bytes
  dropping under NUMA-aware placement with bit-identical batches.

Multi-process reader backend (``FileOptions(backend="process")``)
-----------------------------------------------------------------
With ``backend="process"`` each step session's arena is a **shared-memory
segment** (``src/repro/ipc/shm.py``) filled by real reader worker
processes (``preadv`` directly into the mapping) and consumed here through
the very same borrowed-view machinery — every mode above (host zero-copy,
device ingest, streamed staging) works unchanged, with splinter events
arriving over cross-process rings instead of in-process callbacks.
``bytes_copied`` stays 0 *in this consumer process*: the views ``np``
arrays and staged chunks alias are the mapped segment itself.

Shm view lifetime and failure-semantics contract (the cross-process
sharpening of the rules below):

  * a borrowed view into the shm arena is valid until **its session
    closes**, exactly like the thread backend — session close releases
    the view and unmaps the segment (pages a staged transfer still pins
    survive until that exporter is dropped at the next ``get_batch*``);
  * worker processes never inherit fds: each opens the data file and the
    shm segments by name (``io/posix.py`` fd-hygiene notes).

Worker death now splits into **recoverable** and **terminal** (see
``FileOptions.recovery`` and ``core.buffers.ProcessReaderSet``):

  * **recoverable** (``recovery="respawn"`` or ``"reissue"``, post-gate
    crash/hang within budget): the failure is *invisible* at this layer.
    A replacement worker attaches to the **same arena mapping** (respawn)
    or the supervisor re-reads the unfinished tail in-process (reissue),
    so the session completes bit-identically: borrowed views and staged
    chunks handed out before the crash stay valid (same pages),
    ``bytes_copied`` stays 0, and splinter subscriptions observe **each
    splinter exactly once** — arrival *order* may change (the recovered
    tail lands late) but replay/barrier semantics and the
    arrival-order→file-order device reassembly are order-agnostic by
    construction. Recovery is visible only in
    ``session.metrics.recovery`` (respawns, re-issued splinters/bytes,
    recovery latency);
  * **terminal** (default ``recovery="none"``, respawn budget exhausted,
    or an attach-phase death — the placement barrier cannot re-run): a
    descriptive ``WorkerCrashed`` is raised from every blocked
    ``read``/``get_batch*`` call within the supervisor's poll interval —
    no hang, no partial delivery. The failed session is unusable: its
    borrowed views die at session close as usual, staged chunks of the
    failed step are dropped when the pipeline retires it, and
    subscriptions receive no further events. A *new* session has a *new*
    mapping, so never hold views across a terminal failure — re-read
    through the new session (``train/fault.py`` StepSupervisor does
    exactly this: ``WorkerCrashed`` from the batch path counts as a step
    failure, the optional ``input_recover`` hook rebuilds the pipeline,
    and the step replays from the last checkpoint);
  * **degraded mode** (``fallback_backend="thread"``): a process-backend
    *setup* failure (spawn/shm errors) rebuilds the session on the
    in-process thread backend instead of raising — one ``RuntimeWarning``
    per FileOptions, ``metrics.recovery.degraded_mode`` set, every
    delivery contract above unchanged (the thread backend shares the
    borrowed-view machinery).

Lifetime rules:
  * the returned ``(inputs, labels)`` are ordinary JAX device arrays — they
    own their storage and stay valid as long as the caller holds them;
  * the *staged host view* (the borrowed arena view fed to ``device_put``)
    and its session stay alive until the **next** ``get_batch*``/``close``
    call. At that point the pipeline blocks on the staged transfer, drops
    its host references and retires the session — any access to the old
    borrowed view afterwards raises ``ValueError`` (never a silent read of
    recycled arena memory);
  * **streamed chunk views**: each staged chunk's borrowed arena view is
    pinned from the moment it is handed to ``device_put`` (mid-read, while
    the session is still filling) until its step retires — i.e. valid until
    staged, then until the next ``get_batch*``/``close`` call, at which
    point the pipeline blocks on the step's transfers and releases every
    chunk view along with the session (same use-after-retire ``ValueError``
    guarantee);
  * host-path ``get_batch`` keeps its PR-1 contract: the returned arrays
    alias the session arena and are valid until the next
    ``get_batch*``/``close`` call.
"""
from __future__ import annotations

import threading
import time
import warnings
from collections import deque
from dataclasses import dataclass, field
from typing import Deque, Dict, List, Optional, Tuple

import numpy as np

from repro.core import CkIO, Client, FileOptions, Session, WorkerCrashed
from repro.core import tracing
from repro.core.buffers import SplinterEvent
from repro.core.futures import CkCallback, CkFuture
from repro.core.metrics import IngestMetrics, StreamMetrics
from repro.data.packing import batch_from_tokens, window_rows
from repro.data.tokenfile import read_meta


def device_token_spans(indices_map, global_batch: int, width: int) -> Dict:
    """Resolve a sharding's ``devices_indices_map`` over the ``(batch,
    width)`` window grid into contiguous flat-token spans.

    Returns ``{device: (tok_start, tok_end)}`` in the window's flat token
    space. Raises ``ValueError`` unless every device block is a contiguous
    row range × the FULL width — the only layouts whose blocks are
    contiguous token spans, which is what lets an arriving chunk be routed
    to its destination device(s) by pure interval intersection (no host
    permutation). Pure function of the plain ``{device: (row_slice,
    col_slice)}`` map — unit-testable with fake multi-device maps, no jax
    required.
    """
    spans: Dict = {}
    for dev, idx in indices_map.items():
        if len(idx) != 2:
            raise ValueError(
                f"sharded pipeline expects a 2-d (batch, seq+1) sharding; "
                f"device {dev} has a {len(idx)}-d index")
        rows, cols = idx
        r0, r1, rstep = rows.indices(global_batch)
        c0, c1, cstep = cols.indices(width)
        if rstep != 1 or cstep != 1:
            raise ValueError(
                f"sharded pipeline needs unit-stride device blocks; "
                f"device {dev} has strides ({rstep}, {cstep})")
        if (c0, c1) != (0, width):
            raise ValueError(
                f"sharding splits the sequence dimension (device {dev} "
                f"covers columns [{c0},{c1}) of {width}); only batch-dim "
                f"shardings map to contiguous token spans")
        spans[dev] = (r0 * width, max(r0, r1) * width)
    return spans


@dataclass
class _StreamState:
    """Per-step streamed-staging state (``streaming=True`` device path)."""

    session: Optional[Session] = None
    token: Optional[int] = None            # read_stream subscription token
    pending: List[SplinterEvent] = field(default_factory=list)
    events: List[SplinterEvent] = field(default_factory=list)  # staged order
    chunks: List[object] = field(default_factory=list)         # device arrays
    chunk_hosts: List[tuple] = field(default_factory=list)     # (np, view)
    t_first_stage: float = 0.0
    t_last_stage: float = 0.0
    stagers: int = 0                       # _stage_group calls in flight
    retired: bool = False
    # Constructor-sharding mode: chunks are routed per device span at stage
    # time; abs_off anchors event offsets in the window's token space and
    # dev_pieces collects {device: [(tok_start, device_chunk), ...]}.
    sharded: bool = False
    abs_off: int = 0
    dev_pieces: Dict = field(default_factory=dict)


@dataclass
class _StepBuffer:
    step: int
    abs_off: int = 0
    nbytes: int = 0
    num_rows: int = 0                  # actual rows (< full for remainder)
    session: Optional[Session] = None
    arena: Optional[np.ndarray] = None
    outstanding: int = 0
    stream: Optional[_StreamState] = None
    ready: CkFuture = field(default_factory=CkFuture)


@dataclass
class _StagedStep:
    """Host-side references pinning one device-ingested step (see module
    docstring lifetime rules): released by the next ``get_batch*``."""

    staged: object                     # jax.Array (whole-window tokens)
    host_tokens: object                # np view(s) aliasing the arena
    host_view: Optional[memoryview]    # the borrowed arena view


class CkIOPipeline:
    """Double-buffered LM batch pipeline over a flat token file."""

    def __init__(
        self,
        path,
        global_batch: int,
        seq_len: int,
        *,
        ckio: Optional[CkIO] = None,
        num_pes: int = 4,
        num_consumers: Optional[int] = None,
        consumer_pes: Optional[List[int]] = None,
        file_opts: Optional[FileOptions] = None,
        service=None,
        prefetch_depth: int = 2,
        start_step: int = 0,
        drop_remainder: bool = True,
        zero_copy: bool = True,
        streaming: bool = False,
        sharding=None,
        stage_chunk_bytes: int = 0,
        max_inflight_stage_bytes: int = 32 << 20,
        pad_id: int = 0,
    ):
        # ``path``: a filesystem path (single token file) or a
        # ``data.fileset.FileSet`` manifest (duck-typed — it carries the
        # same meta surface with ``data_offset == 0``, so every offset in
        # this pipeline is a global data-space byte either way).
        is_fileset = hasattr(path, "sharded_file")
        self.meta = path if is_fileset else read_meta(path)
        if len(self.meta.shape) != 1:
            raise ValueError("LM pipeline expects a flat token file")
        self.global_batch = global_batch
        self.seq_len = seq_len
        self.ck = ckio or CkIO(num_pes=num_pes)
        self.file_opts = file_opts or FileOptions()
        # Persistent reader service (ipc/service.py): attach BEFORE any
        # step session starts so every process-backend session checks its
        # workers/arena out of the pool. The caller keeps ownership of the
        # service (and its shutdown) — pipelines, like sessions, come and
        # go faster than the pool they share.
        if service is not None:
            self.ck.director.attach_service(service)
        if is_fileset:
            self.file = self.ck.open_fileset_sync(path, self.file_opts)
        else:
            self.file = self.ck.open_sync(path, self.file_opts)
        self.prefetch_depth = max(1, prefetch_depth)
        self.drop_remainder = drop_remainder
        self.pad_id = pad_id
        rows_per_step = global_batch * (seq_len + 1)
        self.num_steps = self.meta.num_rows // rows_per_step
        if not drop_remainder and self.meta.num_rows % rows_per_step:
            self.num_steps += 1
        # Over-decomposition: consumers default to 4 per PE (paper: apps
        # commonly run 16+ objects/core; tunable independently of readers).
        self.num_consumers = num_consumers or 4 * self.ck.sched.num_pes
        # consumer_pes pins the consumer clients to specific PEs (cycled)
        # instead of round-robin over every PE — skewed-consumer layouts
        # for NUMA locality studies (near_consumers placement then keeps
        # readers on the consumers' memory domains).
        if consumer_pes:
            bad = [p for p in consumer_pes
                   if not 0 <= p < self.ck.sched.num_pes]
            if bad:
                raise ValueError(
                    f"consumer_pes {bad} out of range "
                    f"[0,{self.ck.sched.num_pes})")
            pe_of = lambda i: consumer_pes[i % len(consumer_pes)]  # noqa: E731
        else:
            pe_of = lambda i: i % self.ck.sched.num_pes            # noqa: E731
        self._consumer_pe_of = pe_of
        self.consumers: List[Client] = [
            self.ck.make_client(pe=pe_of(i))
            for i in range(self.num_consumers)
        ]
        self.zero_copy = zero_copy
        if streaming and not zero_copy:
            raise ValueError(
                "streaming=True stages borrowed arena views and requires "
                "zero_copy=True")
        if streaming and self.file_opts.splinter_bytes % self.meta.itemsize:
            # Fail fast: streamed staging views each splinter's bytes as
            # whole tokens; a misaligned size would otherwise surface as an
            # opaque np.frombuffer error inside a scheduler task. (The
            # whole-window path views the full window and doesn't care.)
            raise ValueError(
                f"streaming=True requires splinter_bytes "
                f"({self.file_opts.splinter_bytes}) to be a multiple of the "
                f"token itemsize ({self.meta.itemsize})")
        self.streaming = streaming
        # Constructor sharding: resolve the device blocks over the (B, S+1)
        # window grid into contiguous token spans ONCE (ValueError unless
        # the sharding is batch-dim only). Per-chunk routing at stage time
        # is then pure interval intersection against these spans.
        self.sharding = sharding
        self._dev_spans: Optional[Dict] = None
        self._addr_devices = frozenset()
        self._shift_fn = None
        if sharding is not None:
            self._dev_spans = device_token_spans(
                sharding.devices_indices_map((global_batch, seq_len + 1)),
                global_batch, seq_len + 1)
            self._addr_devices = frozenset(sharding.addressable_devices)
        # 0 (default) ships every splinter the moment its event lands —
        # maximum overlap; a larger threshold batches pending arrivals into
        # fewer staging tasks (the tail is always shipped at finalize).
        self.stage_chunk_bytes = max(1, stage_chunk_bytes)
        self.max_inflight_stage_bytes = max(
            self.stage_chunk_bytes, max_inflight_stage_bytes)
        self.ingest = IngestMetrics()
        self.stream = StreamMetrics()
        self._warned_stream_sharding = False
        self._t_last_step = time.perf_counter()
        self._bufs: Dict[int, _StepBuffer] = {}
        self._retired: List[Session] = []   # zero-copy sessions pending close
        self._staged: List[_StagedStep] = []  # device steps pending release
        # Staged-but-not-awaited transfers across *all* step streams
        # (st, chunk, nbytes): the in-flight budget is global (prefetched
        # steps stage concurrently), so eviction must be too.
        self._stage_outstanding: Deque[tuple] = deque()
        # Condition, not bare Lock: _finalize_stream waits on it for
        # concurrent _stage_group calls (multi-threaded pumps) to drain.
        self._lock = threading.Condition()
        self._next_step = start_step
        for s in range(start_step, min(start_step + self.prefetch_depth, self.num_steps)):
            self.start_step(s)

    # -- elastic scaling -------------------------------------------------------
    def resize(self, num_consumers: int) -> None:
        """Elastically change the consumer decomposition (readers untouched)."""
        cur = len(self.consumers)
        if num_consumers > cur:
            self.consumers.extend(
                self.ck.make_client(pe=self._consumer_pe_of(i))
                for i in range(cur, num_consumers)
            )
        else:
            # Deregister before dropping: a shrunk consumer must not stay in
            # the migration manager's table (shrink→grow cycles would leak
            # one registered id per dropped consumer).
            for c in self.consumers[num_consumers:]:
                c.deregister()
            del self.consumers[num_consumers:]
        self.num_consumers = num_consumers

    def migrate_consumer(self, idx: int, new_pe: int) -> None:
        self.consumers[idx].migrate(new_pe)

    def reset_stream_metrics(self) -> StreamMetrics:
        """Open a fresh ``StreamMetrics`` window (e.g. after benchmark
        warmup) and return the old one. The in-flight balance carries over:
        transfers already issued by subscribed prefetch streams will retire
        against the new object, so a plain ``pipe.stream = StreamMetrics()``
        swap would drive its ``inflight_bytes`` negative and understate the
        high-water mark. Also restarts the step-time clock."""
        with self._lock:
            old, new = self.stream, StreamMetrics()
            new.inflight_bytes = old.inflight_bytes
            new.inflight_bytes_hwm = old.inflight_bytes
            self.stream = new
            self._t_last_step = time.perf_counter()
        return old

    # -- split-phase step input --------------------------------------------------
    def start_step(self, step: int) -> None:
        """Kick off the read session + consumer reads for ``step`` (async)."""
        with self._lock:
            if step in self._bufs or step >= self.num_steps:
                return
            buf = _StepBuffer(step=step)
            self._bufs[step] = buf

        start_row, num_rows = window_rows(step, self.global_batch, self.seq_len)
        # Remainder final window (drop_remainder=False): clamp to the file.
        num_rows = min(num_rows, self.meta.num_rows - start_row)
        abs_off, nbytes = self.meta.byte_range_for_rows(start_row, num_rows)
        buf.abs_off, buf.nbytes, buf.num_rows = abs_off, nbytes, num_rows
        mv: Optional[memoryview] = None
        if not self.zero_copy:
            buf.arena = np.empty(num_rows, dtype=self.meta.dtype)
            mv = memoryview(buf.arena).cast("B")

        def on_session(session: Session) -> None:
            buf.session = session
            if self.streaming:
                # Event-driven mode: the splinter stream drives staging, and
                # completeness is one whole-window residency waiter — not a
                # per-consumer read fan-out (the last read releases a single
                # completion task instead of num_consumers of them; the
                # consumers still own the *event* routing, so migration and
                # drop-stale semantics are unchanged).
                self._subscribe_stream(buf, session)
                buf.outstanding = 1

                def window_resident(_msg) -> None:
                    with self._lock:
                        buf.outstanding = 0
                    buf.ready.set(buf)

                self.ck.read_notify(
                    session, nbytes, abs_off,
                    CkCallback(window_resident, pe=0),
                    # The splinter stream classifies this window's bytes
                    # per event (against the routed consumer's domain);
                    # the residency probe must not classify them again.
                    classify_locality=False)
                return
            # Consumers collectively read disjoint slices of the window.
            n = self.num_consumers
            per = (nbytes + n - 1) // n
            itemsize = self.meta.itemsize
            per -= per % itemsize  # keep element alignment
            per = max(per, itemsize)
            plans = []
            pos = 0
            while pos < nbytes:
                take = min(per, nbytes - pos)
                plans.append((pos, take))
                pos += take
            buf.outstanding = len(plans)

            def make_done():
                def done(_msg) -> None:
                    with self._lock:
                        buf.outstanding -= 1
                        if buf.outstanding == 0:
                            buf.ready.set(buf)

                return done

            for i, (rel_off, take) in enumerate(plans):
                client = self.consumers[i % len(self.consumers)]
                if mv is None:
                    # zero-copy mode: residency signal only — get_batch
                    # takes one whole-window arena view itself.
                    self.ck.read_notify(
                        session,
                        take,
                        abs_off + rel_off,
                        client.callback(make_done()),
                        client=client,
                    )
                else:
                    self.ck.read(
                        session,
                        take,
                        abs_off + rel_off,
                        mv[rel_off : rel_off + take],
                        client.callback(make_done()),
                        client=client,
                    )

        self.ck.start_read_session(
            self.file,
            nbytes,
            abs_off,
            CkCallback(on_session, inline=True),
            consumer_pes=[c.pe for c in self.consumers],
        )

    # -- streamed staging (the event-driven device path) ----------------------
    def _subscribe_stream(self, buf: _StepBuffer, session: Session) -> None:
        """Attach the per-splinter staging loop to ``session``'s stream."""
        st = _StreamState(session=session, sharded=self.sharding is not None,
                          abs_off=buf.abs_off)
        buf.stream = st

        def route(ev: SplinterEvent) -> Optional[Client]:
            # Deliver each event through a consumer's virtual proxy: the
            # staging task chases migrations, and an event addressed to a
            # consumer retired by resize() is dropped + counted (drop-stale
            # delivery), never rerouted to a reused slot. Copy the list —
            # resize() mutates self.consumers in place from another thread,
            # and this runs on the completing I/O thread (a stale Client
            # picked from the copy is exactly the drop-stale case).
            cons = list(self.consumers)
            return cons[ev.index % len(cons)] if cons else None

        def on_splinter(ev: SplinterEvent) -> None:
            self._on_stream_event(buf, st, ev)

        st.token = self.ck.read_stream(session, on_splinter, route=route)

    def _on_stream_event(
        self, buf: _StepBuffer, st: _StreamState, ev: SplinterEvent
    ) -> None:
        """Scheduler task per streamed splinter arrival: accumulate until a
        chunk's worth of bytes is pending, then ship it."""
        with self._lock:
            if st.retired:
                # Late event racing finalize/resize: drop and count — the
                # splinter was (or will be) staged from the authoritative
                # event log, never twice.
                self.stream.record_stale_event()
                self.ck.locations.count_stale()
                return
            st.pending.append(ev)
            if (sum(e.nbytes for e in st.pending) < self.stage_chunk_bytes
                    and not buf.ready.done):
                return                 # accumulate; tail staged at finalize
            group, st.pending = st.pending, []
            st.stagers += 1            # claimed: finalize must wait for us
        try:
            self._stage_group(st, group)
        finally:
            with self._lock:
                st.stagers -= 1
                self._lock.notify_all()

    def _stage_group(self, st: _StreamState, group: List[SplinterEvent]) -> None:
        """``device_put`` a group of arrived splinters, one chunk per
        splinter, respecting the in-flight staging budget. Runs on the
        pumping thread — while reader threads are still filling the rest of
        the session.

        One chunk per splinter is deliberate: splinter sizes within a plan
        are uniform (modulo stripe tails), so the staged chunk *shapes* —
        and with them the device concatenate/gather signatures — are stable
        across steps and arrival permutations, keeping every step on cached
        executables. Coalescing arrival runs would produce arrival-dependent
        chunk shapes and recompile the consume path each step."""
        import jax

        if not group:
            return
        if st.sharded:
            return self._stage_group_sharded(st, group)
        sess = st.session
        assert sess is not None
        for ev in group:
            self._evict_for(ev.nbytes)
            view = sess.readers.borrow_view(ev.offset, ev.nbytes)
            tokens = np.frombuffer(view, dtype=self.meta.dtype)
            if tokens.dtype == np.uint32:
                tokens = tokens.view(np.int32)
            self.stream.stage_inflight(ev.nbytes)
            try:
                with tracing.span("pipeline.stage", nbytes=ev.nbytes):
                    t0 = time.perf_counter()
                    chunk = jax.device_put(tokens)
                    t1 = time.perf_counter()
            except BaseException:
                # A failed transfer never reaches _stage_outstanding, so
                # its budget charge must be rolled back here or
                # inflight_bytes stays inflated for the pipeline's life.
                self.stream.stage_inflight(-ev.nbytes)
                raise
            if st.t_first_stage == 0.0:
                st.t_first_stage = t0
            st.t_last_stage = t1
            with self._lock:
                st.chunks.append(chunk)
                st.chunk_hosts.append((tokens, view))
                st.events.append(ev)
                self._stage_outstanding.append((st, chunk, ev.nbytes))
            self.stream.record_chunk(
                ev.nbytes, 1, t1 - t0, [t1 - ev.t_arrival])

    def _evict_for(self, nbytes: int) -> None:
        """Bounded in-flight budget: make room for an ``nbytes`` transfer by
        awaiting the oldest outstanding transfer(s) — from whichever step
        stream issued them — before the caller issues another one."""
        while True:
            with self._lock:
                if (self.stream.inflight_bytes + nbytes
                        <= self.max_inflight_stage_bytes
                        or not self._stage_outstanding):
                    return
                _, old_chunk, old_n = self._stage_outstanding.popleft()
            old_chunk.block_until_ready()
            self.stream.stage_inflight(-old_n)

    def _stage_group_sharded(
        self, st: _StreamState, group: List[SplinterEvent]
    ) -> None:
        """Sharded streamed staging: route each arrived splinter's tokens to
        their destination device(s) by interval intersection against the
        resolved spans and ``device_put`` each *addressable* sub-slice onto
        its device. The sub-slices are numpy views of the session arena
        (zero host copies, ``host_permute_bytes`` stays 0); spans owned by
        another host's devices are counted (``ShardMetrics.cross_host``)
        and skipped — this host never stages bytes it cannot address."""
        import jax

        sess = st.session
        assert sess is not None
        itemsize = self.meta.itemsize
        shards = self.ck.director.shards
        for ev in group:
            view = sess.readers.borrow_view(ev.offset, ev.nbytes)
            tokens = np.frombuffer(view, dtype=self.meta.dtype)
            if tokens.dtype == np.uint32:
                tokens = tokens.view(np.int32)
            tok0 = (ev.offset - st.abs_off) // itemsize
            ntok = ev.nbytes // itemsize
            t0 = time.perf_counter()
            staged_bytes = 0
            npieces = 0
            for dev, (s0, s1) in self._dev_spans.items():
                lo, hi = max(tok0, s0), min(tok0 + ntok, s1)
                if lo >= hi:
                    continue
                nb = (hi - lo) * itemsize
                if dev not in self._addr_devices:
                    shards.record_cross_host(nb)
                    continue
                self._evict_for(nb)
                sub = tokens[lo - tok0: hi - tok0]
                self.stream.stage_inflight(nb)
                try:
                    with tracing.span("pipeline.stage", nbytes=nb):
                        chunk = jax.device_put(sub, dev)
                except BaseException:
                    self.stream.stage_inflight(-nb)
                    raise
                with self._lock:
                    st.dev_pieces.setdefault(dev, []).append((lo, chunk))
                    self._stage_outstanding.append((st, chunk, nb))
                shards.record_stage(str(dev), nb)
                staged_bytes += nb
                npieces += 1
            t1 = time.perf_counter()
            if st.t_first_stage == 0.0:
                st.t_first_stage = t0
            st.t_last_stage = t1
            with self._lock:
                # The event (and its pinning host refs) is recorded even if
                # every intersecting span was remote: the coverage proof at
                # finalize runs over the event log, not the staged pieces.
                st.chunk_hosts.append((tokens, view))
                st.events.append(ev)
            if npieces:
                self.stream.record_chunk(
                    staged_bytes, npieces, t1 - t0, [t1 - ev.t_arrival])

    def _finalize_stream(self, buf: _StepBuffer):
        """All reads are resident (``buf.ready``): stop the stream, stage the
        pending tail plus any splinters whose events were dropped, and return
        the arrival-order device chunks + their piece layout."""
        st = buf.stream
        assert st is not None and st.session is not None
        sess = st.session
        # No pipeline lock held here: end_stream takes the reader stream
        # lock (lock order is stream lock -> pipeline lock, never inverse).
        self.ck.end_stream(sess, st.token)
        with self._lock:
            # Retire FIRST: event tasks popped concurrently by another
            # pumping thread from here on drop + count instead of staging —
            # otherwise one could race the missing-scan below and stage its
            # splinter twice. Then drain stagers that already claimed a
            # group before the flip (their chunks must be in st.events
            # before the scan).
            st.retired = True
            group, st.pending = st.pending, []
            while st.stagers:
                self._lock.wait()
        self._stage_group(st, group)
        # Completeness: any splinter not staged (its event was dropped by
        # drop-stale routing mid-resize, or raced the retire flip) comes
        # from the authoritative event log — the session is complete, so
        # the log is too.
        with self._lock:
            seen = {e.index for e in st.events}
        missing = [ev for ev in sess.splinter_events if ev.index not in seen]
        self._stage_group(st, missing)
        with self._lock:
            own = [e for e in self._stage_outstanding if e[0] is st]
            self._stage_outstanding = deque(
                e for e in self._stage_outstanding if e[0] is not st)
        # The consuming gather forces every chunk; this stream's transfers
        # leave the in-flight budget (other steps' streams keep theirs).
        self.stream.stage_inflight(-sum(n for _, _, n in own))
        pieces = [(e.offset, e.nbytes) for e in st.events]
        return list(st.chunks), pieces, st

    def _abort_stream(self, buf: _StepBuffer) -> None:
        """Tear down a step's stream without consuming it (host-path fetch,
        per-call sharding override, or pipeline close)."""
        st = buf.stream
        if st is None:
            return
        buf.stream = None
        if st.session is not None and st.token is not None:
            self.ck.end_stream(st.session, st.token)
        with self._lock:
            st.retired = True
            st.pending = []
            while st.stagers:          # drain in-flight _stage_group calls
                self._lock.wait()
            chunks, st.chunks = list(st.chunks), []
            chunks.extend(c for ps in st.dev_pieces.values() for _, c in ps)
            st.dev_pieces = {}
            st.chunk_hosts = []
            own = [e for e in self._stage_outstanding if e[0] is st]
            self._stage_outstanding = deque(
                e for e in self._stage_outstanding if e[0] is not st)
        for chunk in chunks:
            # The arena must outlive the transfers; block before the chunk
            # views can be invalidated by the session retiring.
            chunk.block_until_ready()
        self.stream.stage_inflight(-sum(n for _, _, n in own))

    def _close_retired(self) -> None:
        with tracing.span("pipeline.close_retired"):
            self._close_retired_sessions()

    def _close_retired_sessions(self) -> None:
        with self._lock:
            retired, self._retired = self._retired, []
            staged, self._staged = self._staged, []
        if staged:
            import jax
        for st in staged:
            # The step's host→device transfer(s) may still be in flight;
            # the arena (and our host refs) must outlive them. Block, then
            # drop the references so the borrow(s) can actually be released.
            # (Streamed steps pin their outputs — blocking those forces
            # every chunk transfer they consumed.) A failed transfer
            # propagates (the device array is unusable and silence would
            # let ingest counters claim success); the host refs are dropped
            # either way — a failed transfer does not need the arena.
            try:
                jax.block_until_ready(st.staged)
            finally:
                st.host_tokens = None
                st.staged = None
        for sess in retired:
            # Invalidate borrows inline (idempotent — close_session repeats
            # it) so the lifetime contract is "valid until the next
            # get_batch*", not "until some later scheduler pump"; the
            # split-phase session close itself stays off the critical path.
            sess.readers.invalidate_borrows()
            self.ck.close_read_session(sess)

    def _wait_step(self, step: int, timeout: float) -> _StepBuffer:
        if step >= self.num_steps:
            raise IndexError(f"step {step} >= {self.num_steps}")
        self.start_step(step)  # no-op if already started
        buf = self._bufs[step]
        with tracing.span("pipeline.wait_ready", step=step):
            buf.ready.wait(self.ck.sched, timeout=timeout)
        # Launch the lookahead before handing the batch to the trainer.
        with tracing.span("pipeline.lookahead", step=step):
            self.start_step(step + self.prefetch_depth)
        with self._lock:
            self._bufs.pop(step, None)
        return buf

    def _window_tokens(self, buf: _StepBuffer):
        """Whole-window tokens (and the borrowed arena view backing them,
        zero-copy mode only). Retires the *previous* step first."""
        if buf.stream is not None:
            # Host-path / whole-window fetch of a streamed step: the stream
            # state is torn down first (its chunks are never consumed).
            self._abort_stream(buf)
        view: Optional[memoryview] = None
        if self.zero_copy:
            # Previous step's batch has been consumed by now — retire its
            # session (which invalidates its borrowed views).
            self._close_retired()
            assert buf.session is not None
            view = buf.session.readers.borrow_view(buf.abs_off, buf.nbytes)
            tokens = np.frombuffer(view, dtype=self.meta.dtype)
            with self._lock:
                self._retired.append(buf.session)
        else:
            self._close_retired()     # release any pending device-step refs
            if buf.session is not None:
                self.ck.close_read_session(buf.session)
            tokens = buf.arena
            assert tokens is not None
        if tokens.dtype == np.uint32:
            tokens = tokens.view(np.int32)   # zero-copy reinterpret
        return tokens, view

    def get_batch(self, step: int, timeout: float = 300.0) -> Tuple[np.ndarray, np.ndarray]:
        """Blocking (scheduler-pumping) fetch of step ``step``; prefetches
        ``step + prefetch_depth`` before returning (the overlap).

        In zero-copy mode the returned arrays alias the step's session arena
        and remain valid until the next ``get_batch*``/``close`` call."""
        buf = self._wait_step(step, timeout)
        tokens, _ = self._window_tokens(buf)
        inputs, labels = batch_from_tokens(
            tokens, self.global_batch, self.seq_len,
            allow_partial=not self.drop_remainder, pad_id=self.pad_id,
        )
        # Host-side phase-2 permutation: the window passes through host
        # reshaping/marshalling on its way to the device.
        self.ingest.record_host_step(buf.nbytes)
        self._t_last_step = time.perf_counter()
        return inputs, labels

    def get_batch_device(
        self,
        step: int,
        sharding=None,
        *,
        use_pallas: Optional[bool] = None,
        timeout: float = 300.0,
    ):
        """Device-ingest fetch: one ``device_put`` of the whole-window arena
        view, then on-device batch-major reassembly (fused label shift +
        remainder padding). Returns JAX device arrays ``(inputs, labels)``.

        See the module docstring for the staged-buffer lifetime contract.
        ``sharding`` is forwarded to ``device_put`` for the staged window;
        ``use_pallas`` picks the gather backend (default: Pallas on TPU,
        XLA reference elsewhere).

        With ``streaming=True`` (and no per-call ``sharding``), the window
        was being staged chunk-by-chunk while its reads were in flight; this
        call only ships the tail, concatenates on device, and runs the
        arrival-order gather — see "Streamed staging" in the module
        docstring."""
        with tracing.span("pipeline.get_batch_device", step=step):
            return self._batch_device(step, sharding, use_pallas, timeout)

    def _batch_device(self, step: int, sharding, use_pallas: Optional[bool],
                      timeout: float):
        import jax

        from repro.kernels import ops

        buf = self._wait_step(step, timeout)
        if self.sharding is not None:
            # Constructor sharding owns the step: per-call shardings must
            # agree (the spans were resolved — and streamed chunks placed —
            # against the constructor's). use_pallas is moot here: the
            # sharded assembly is concat+reshape+shift, no gather kernel.
            if sharding is not None and sharding != self.sharding:
                raise ValueError(
                    "get_batch_device(sharding=...) differs from the "
                    "pipeline's constructor sharding; streamed chunks are "
                    "already placed against the constructor's spans")
            if buf.stream is not None:
                return self._get_batch_device_streamed_sharded(buf)
            return self._get_batch_device_window_sharded(buf)
        if buf.stream is not None and sharding is None:
            return self._get_batch_device_streamed(buf, use_pallas=use_pallas)
        if buf.stream is not None and not self._warned_stream_sharding:
            # Explicit, not silent: streamed chunks were device_put with
            # default placement while the reads were landing — before this
            # call-site sharding existed — so they cannot satisfy it. The
            # step falls back to the whole-window path (stage once WITH the
            # sharding, reassemble on device); the already-staged chunks
            # are discarded. Warn once per pipeline: per-call sharding on a
            # streaming pipeline forfeits the read/stage overlap every
            # step, which is almost never what a multi-host run wants —
            # construct the pipeline with streaming=False (or ship the
            # sharding at construction time) instead.
            self._warned_stream_sharding = True
            warnings.warn(
                "get_batch_device(sharding=...) on a streaming pipeline: "
                "streamed chunks are placed before a per-call sharding is "
                "known; falling back to the whole-window staging path "
                "(overlap lost) for every sharded call. Use "
                "streaming=False if every step passes a sharding.",
                RuntimeWarning, stacklevel=2)
        tokens, view = self._window_tokens(buf)
        itemsize = self.meta.itemsize
        valid_tokens = buf.nbytes // itemsize
        # The step's single host→device transfer (sharding=None → default
        # device placement).
        with tracing.span("pipeline.stage", nbytes=buf.nbytes):
            staged = jax.device_put(tokens, sharding)
        if self.zero_copy:
            with self._lock:
                # borrow_view in _window_tokens appended the session; the
                # staged refs pin arena + transfer until the next call.
                self._staged.append(_StagedStep(
                    staged=staged,
                    host_tokens=tokens,
                    host_view=view,
                ))
        with tracing.span("pipeline.assemble"):
            inputs, labels = ops.device_ingest(
                staged,
                None,                   # arena view is file-order
                global_batch=self.global_batch,
                seq_len=self.seq_len,
                valid_tokens=valid_tokens,
                pad_id=self.pad_id,
                use_pallas=use_pallas,
            )
        # Copy mode still pays the session→step-arena host copy before
        # staging; only the zero-copy path truly has 0 host bytes.
        self.ingest.record_device_step(
            buf.nbytes, host_bytes=0 if self.zero_copy else buf.nbytes)
        self._t_last_step = time.perf_counter()
        return inputs, labels

    def _get_batch_device_streamed(
        self, buf: _StepBuffer, *, use_pallas: Optional[bool] = None
    ):
        """Streamed tail of ``get_batch_device``: finalize the step's chunk
        stream and reassemble on device in a single fused dispatch (concat +
        window kernel over file-order-sorted chunk handles)."""
        from repro.kernels import ops

        self._close_retired()          # release the previous step's refs
        chunks, pieces, st = self._finalize_stream(buf)
        sess = st.session
        itemsize = self.meta.itemsize
        valid_tokens = buf.nbytes // itemsize
        abs_off = buf.abs_off
        # The arrival-order→file-order permutation is applied to the chunk
        # *handles*: each splinter is its own device buffer, so reordering
        # the argument list (host metadata, O(#splinters log #splinters))
        # replaces the on-device gather a contiguous arrival-ordered staging
        # buffer would need (ops.ingest_chunks_block / device_ingest serve
        # that layout). Sorted order is also deterministic per plan, so the
        # fused executable's chunk-shape signature is identical across steps
        # whatever order the reads completed in.
        order = sorted(range(len(pieces)), key=lambda i: pieces[i][0])
        pieces = [pieces[i] for i in order]
        chunks = [chunks[i] for i in order]
        pos = abs_off
        for off, nb in pieces:        # exactly-once coverage, cheap to prove
            if off != pos:
                raise RuntimeError(
                    f"streamed pieces corrupt: expected offset {pos}, "
                    f"got {off}")
            pos += nb
        if pos != abs_off + buf.nbytes:
            raise RuntimeError("streamed pieces do not cover the window")
        with tracing.span("pipeline.assemble"):
            inputs, labels = ops.ingest_chunks_window(
                chunks, global_batch=self.global_batch, seq_len=self.seq_len,
                valid_limit=valid_tokens, pad_id=self.pad_id,
                use_pallas=use_pallas)
        with self._lock:
            self._retired.append(sess)
            # Pin the chunk views + outputs until the next step: the
            # streamed analog of the whole-window staged refs (module
            # docstring, "streamed chunk views"); blocking on the outputs
            # forces every chunk transfer they consumed.
            self._staged.append(_StagedStep(
                staged=(inputs, labels),
                host_tokens=st.chunk_hosts,
                host_view=None,
            ))
            nchunks = len(st.chunks)
            st.chunks = []
            st.chunk_hosts = []
        buf.stream = None
        self.ingest.record_device_step(
            buf.nbytes, transfers=nchunks, host_bytes=0)
        now = time.perf_counter()
        self.stream.record_step(
            (sess.metrics.t_start, sess.metrics.t_last_read),
            (st.t_first_stage, st.t_last_stage),
            now - self._t_last_step,
        )
        self._t_last_step = now
        return inputs, labels

    # -- sharded device path (constructor sharding=) ---------------------------
    def _np_token_dtype(self):
        """Host-side token dtype after the zero-copy uint32→int32 view."""
        dt = np.dtype(self.meta.dtype)
        return np.dtype(np.int32) if dt == np.uint32 else dt

    def _shift(self, window):
        """Jitted label shift over the assembled sharded window: the
        ``(w[:, :-1], w[:, 1:])`` split of ``batch_from_tokens``, computed
        on device. Column slicing never crosses a batch-dim shard, so the
        outputs keep the window's sharding without any communication."""
        import jax

        if self._shift_fn is None:
            self._shift_fn = jax.jit(lambda w: (w[:, :-1], w[:, 1:]))
        return self._shift_fn(window)

    def _assemble_sharded_window(self, dev_pieces: Dict, valid_tokens: int):
        """Bind per-device token pieces (already resident on their
        destination devices) into the global sharded ``(B, S+1)`` window:
        per addressable device — sort by token offset, prove its span is
        exactly covered, pad the remainder tail on-device, reshape to the
        device's row block — then
        ``jax.make_array_from_single_device_arrays`` (metadata only, no
        further transfer)."""
        import jax
        import jax.numpy as jnp

        width = self.seq_len + 1
        np_dtype = self._np_token_dtype()
        # Deterministic block order (addressable_devices is a set).
        devs = sorted(self._addr_devices, key=lambda d: d.id)
        blocks = []
        for dev in devs:
            s0, s1 = self._dev_spans[dev]
            pieces = sorted(dev_pieces.get(dev, ()), key=lambda p: p[0])
            pos = s0
            for t0, c in pieces:
                if t0 != pos:
                    raise RuntimeError(
                        f"sharded pieces corrupt on {dev}: expected token "
                        f"{pos}, got {t0}")
                pos += c.size
            expected = max(0, min(s1, valid_tokens) - s0)
            if pos - s0 != expected:
                raise RuntimeError(
                    f"sharded pieces do not cover {dev}'s span: "
                    f"{pos - s0} of {expected} tokens")
            parts = [c for _, c in pieces]
            pad = (s1 - s0) - expected
            with jax.default_device(dev):
                if pad:
                    parts.append(jnp.full((pad,), self.pad_id,
                                          dtype=np_dtype))
                if not parts:          # empty span (more devices than rows)
                    parts = [jnp.zeros((0,), dtype=np_dtype)]
                block = parts[0] if len(parts) == 1 else jnp.concatenate(parts)
                blocks.append(block.reshape((s1 - s0) // width, width))
        return jax.make_array_from_single_device_arrays(
            (self.global_batch, width), self.sharding, blocks)

    def _addressable_window_bytes(self, valid_tokens: int) -> int:
        """Bytes of this step's *valid* window owned by this host's
        devices (the staged-bytes ledger's addressable side)."""
        itemsize = self.meta.itemsize
        return sum(
            max(0, min(s1, valid_tokens) - min(s0, valid_tokens)) * itemsize
            for dev, (s0, s1) in self._dev_spans.items()
            if dev in self._addr_devices)

    def _get_batch_device_streamed_sharded(self, buf: _StepBuffer):
        """Sharded streamed tail: finalize the chunk stream (the per-device
        pieces were ``device_put`` as their splinters arrived), prove
        window coverage from the event log, assemble the global window and
        shift — no whole-window restage, no ``RuntimeWarning``."""
        self._close_retired()          # release the previous step's refs
        _, pieces, st = self._finalize_stream(buf)
        sess = st.session
        valid_tokens = buf.nbytes // self.meta.itemsize
        # Exactly-once coverage of the window, from the authoritative event
        # log (staged pieces can be a strict subset on multi-host runs).
        pos = buf.abs_off
        for off, nb in sorted(pieces):
            if off != pos:
                raise RuntimeError(
                    f"streamed pieces corrupt: expected offset {pos}, "
                    f"got {off}")
            pos += nb
        if pos != buf.abs_off + buf.nbytes:
            raise RuntimeError("streamed pieces do not cover the window")
        with tracing.span("pipeline.assemble"):
            window = self._assemble_sharded_window(st.dev_pieces,
                                                   valid_tokens)
            inputs, labels = self._shift(window)
        self.ck.director.shards.record_window(
            buf.nbytes, self._addressable_window_bytes(valid_tokens))
        with self._lock:
            self._retired.append(sess)
            self._staged.append(_StagedStep(
                staged=(inputs, labels),
                host_tokens=st.chunk_hosts,
                host_view=None,
            ))
            npieces = sum(len(v) for v in st.dev_pieces.values())
            st.dev_pieces = {}
            st.chunk_hosts = []
        buf.stream = None
        self.ingest.record_device_step(
            buf.nbytes, transfers=npieces, host_bytes=0)
        now = time.perf_counter()
        self.stream.record_step(
            (sess.metrics.t_start, sess.metrics.t_last_read),
            (st.t_first_stage, st.t_last_stage),
            now - self._t_last_step,
        )
        self._t_last_step = now
        return inputs, labels

    def _get_batch_device_window_sharded(self, buf: _StepBuffer):
        """Whole-window variant of the sharded path (``streaming=False``):
        slice the resident window's host tokens per addressable device span
        (numpy views — no host copy in zero-copy mode), one ``device_put``
        per addressable device, then the same assembly as the streamed
        path. Each host stages only its addressable slice."""
        import jax

        tokens, view = self._window_tokens(buf)
        itemsize = self.meta.itemsize
        valid_tokens = buf.nbytes // itemsize
        shards = self.ck.director.shards
        dev_pieces: Dict = {}
        npieces = 0
        for dev, (s0, s1) in self._dev_spans.items():
            lo, hi = min(s0, valid_tokens), min(s1, valid_tokens)
            if dev not in self._addr_devices:
                shards.record_cross_host((hi - lo) * itemsize)
                continue
            if hi > lo:
                with tracing.span("pipeline.stage",
                                  nbytes=(hi - lo) * itemsize):
                    chunk = jax.device_put(tokens[lo:hi], dev)
                dev_pieces[dev] = [(lo, chunk)]
                shards.record_stage(str(dev), (hi - lo) * itemsize)
                npieces += 1
        shards.record_window(
            buf.nbytes, self._addressable_window_bytes(valid_tokens))
        with tracing.span("pipeline.assemble"):
            window = self._assemble_sharded_window(dev_pieces, valid_tokens)
            inputs, labels = self._shift(window)
        if self.zero_copy:
            with self._lock:
                # borrow_view in _window_tokens appended the session; the
                # staged refs pin arena + transfers until the next call.
                self._staged.append(_StagedStep(
                    staged=(inputs, labels),
                    host_tokens=tokens,
                    host_view=view,
                ))
        self.ingest.record_device_step(
            buf.nbytes, transfers=npieces,
            host_bytes=0 if self.zero_copy else buf.nbytes)
        self._t_last_step = time.perf_counter()
        return inputs, labels

    def idle(self, seconds: float) -> int:
        """Pump pipeline tasks for ``seconds`` (call while the device step
        runs) — the Charm++ idle-PE behaviour that makes prefetch overlap
        real. Returns tasks processed."""
        import time as _time

        return self.ck.sched.pump_until_deadline(_time.monotonic() + seconds)

    def __iter__(self):
        for s in range(self._next_step, self.num_steps):
            yield self.get_batch(s)

    # -- device hand-off ---------------------------------------------------------
    @staticmethod
    def to_device(inputs: np.ndarray, labels: np.ndarray, sharding=None):
        import jax

        if sharding is None:
            return jax.device_put(inputs), jax.device_put(labels)
        return jax.device_put(inputs, sharding), jax.device_put(labels, sharding)

    def close(self) -> None:
        # A crashed reader worker in a *prefetched* session surfaces as a
        # raising task the moment anything pumps the scheduler. Teardown
        # must still run to completion (sessions stopped, shm unmapped,
        # the file fd closed) — so close catches those here, finishes, and
        # re-raises the first one at the end instead of aborting half-way
        # with the fd leaked.
        surfaced: List[BaseException] = []

        def pump_all() -> None:
            while True:
                try:
                    self.ck.pump()
                    return
                except WorkerCrashed as e:   # finite: ≤1 task per session
                    surfaced.append(e)

        # Flush queued session starts BEFORE tearing down streams: a
        # prefetch session that only starts during this pump subscribes its
        # splinter stream then (and may stage chunks) — aborting first
        # would miss it and leak its in-flight accounting. The pump is also
        # what makes close deterministic: every reader thread of this file
        # is joined below before the fd goes away (an in-flight prefetch
        # session must not pread a closed file; shutdown is off the hot
        # path).
        pump_all()
        for buf in list(self._bufs.values()):
            if buf.stream is not None:
                self._abort_stream(buf)
        self._close_retired()
        stopped = True
        for sess in list(self.ck.director.sessions.values()):
            if sess.file is self.file:
                stopped &= sess.readers.stop()
        for buf in list(self._bufs.values()):
            if buf.session is not None:
                self.ck.close_read_session(buf.session)
        if not stopped:
            # A straggling reader may still pread this fd; closing it now
            # risks EBADF or — after fd reuse — reading the wrong file.
            # Leak the fd and fail loud instead.
            raise RuntimeError(
                "pipeline close: reader thread(s) still running after stop "
                "timeout; file left open")
        while True:
            try:
                self.ck.close_sync(self.file)
                break
            except WorkerCrashed as e:
                surfaced.append(e)
        if surfaced:
            raise surfaced[0]
