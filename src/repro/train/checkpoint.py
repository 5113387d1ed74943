"""Checkpointing: packed single-file format, async save, CkIO-parallel
restore, elastic re-shard on load.

Save packs the whole (params, opt_state) tree into ONE file — header JSON
manifest (leaf path -> dtype/shape/offset) + contiguous blob — precisely the
"all relevant data in a single large file, collectively read by a collection
of tasks" layout the paper targets. Restore therefore *is* a CkIO workload:
one read session over the blob, one consumer client per leaf (over-
decomposed), reader count tuned independently — measured in
benchmarks/fig13_train_input.py alongside the training-ingest comparison.
The CkIO restore copies nothing on the host: each leaf is a read-only NumPy
view of its bytes in the one session arena, and holding any restored leaf
keeps the whole arena alive.

Saves are split-phase like everything else here: ``AsyncCheckpointer.save``
snapshots device arrays to host and hands the serialization + write to a
worker thread (paper §II-C: output is the simpler direction), keeping the
training loop running. ``restore_sharded`` re-lays-out leaves onto an
arbitrary new mesh/sharding — elastic scaling across restarts.
"""
from __future__ import annotations

import json
import os
import queue
import re
import threading
from typing import Any, Dict, List, Optional, Tuple

import jax
import numpy as np

from repro.core import tracing

MAGIC = b"CKPT-CKIO-v1\x00\x00\x00\x00"
ALIGN = 4096


def _leaf_paths(tree: Any) -> Tuple[List[str], List[Any], Any]:
    flat, treedef = jax.tree_util.tree_flatten_with_path(tree)
    names, leaves = [], []
    for path, leaf in flat:
        names.append(jax.tree_util.keystr(path))
        leaves.append(leaf)
    return names, leaves, treedef


def save_checkpoint(path: str, tree: Any, step: int) -> Dict[str, Any]:
    """Synchronous packed save. Returns the manifest."""
    names, leaves, _ = _leaf_paths(tree)
    arrays = [np.asarray(jax.device_get(x)) for x in leaves]
    entries = []
    offset = 0
    for name, a in zip(names, arrays):
        nbytes = a.nbytes
        entries.append({
            "name": name,
            "dtype": str(a.dtype),
            "shape": list(a.shape),
            "offset": offset,
            "nbytes": nbytes,
        })
        offset += nbytes
        offset = (offset + 127) // 128 * 128    # row-align leaves
    manifest = {"step": step, "total_bytes": offset, "leaves": entries}
    blob_head = json.dumps(manifest).encode()
    head_len = 16 + 8 + len(blob_head)
    data_off = (head_len + ALIGN - 1) // ALIGN * ALIGN
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    tmp = path + ".tmp"
    with open(tmp, "wb") as f:
        f.write(MAGIC)
        f.write(len(blob_head).to_bytes(8, "little"))
        f.write(blob_head)
        f.write(b"\x00" * (data_off - head_len))
        for e, a in zip(entries, arrays):
            f.seek(data_off + e["offset"])
            f.write(np.ascontiguousarray(a).tobytes())
        # extend the file, zero-filled, to the aligned total so read
        # sessions spanning [data_off, data_off+total_bytes) never cross EOF
        # (every leaf ends at or before it, so no data byte is touched)
        f.truncate(data_off + offset)
        f.flush()
        os.fsync(f.fileno())
    os.replace(tmp, path)
    manifest["data_offset"] = data_off
    return manifest


def read_manifest(path: str) -> Dict[str, Any]:
    with open(path, "rb") as f:
        magic = f.read(16)
        if magic != MAGIC:
            raise ValueError(f"{path}: bad checkpoint magic")
        n = int.from_bytes(f.read(8), "little")
        manifest = json.loads(f.read(n))
    head_len = 16 + 8 + n
    manifest["data_offset"] = (head_len + ALIGN - 1) // ALIGN * ALIGN
    return manifest


def restore_arrays(
    path: str,
    *,
    use_ckio: bool = True,
    num_readers: Optional[int] = None,
    num_pes: int = 4,
) -> Tuple[Dict[str, np.ndarray], Dict[str, Any]]:
    """Read every leaf; CkIO path reads the blob through one session with one
    over-decomposed consumer per leaf.

    On the CkIO path each leaf is a read-only view of the session arena (the
    zero-copy borrowed-view read), so every leaf shares that one buffer and
    holding any of them keeps the whole arena alive. ``use_ckio=False``
    gives each leaf a writable array of its own."""
    manifest = read_manifest(path)
    data_off = manifest["data_offset"]
    out: Dict[str, np.ndarray] = {}
    if not use_ckio:
        with open(path, "rb") as f:
            for e in manifest["leaves"]:
                f.seek(data_off + e["offset"])
                buf = f.read(e["nbytes"])
                out[e["name"]] = np.frombuffer(
                    buf, dtype=np.dtype(e["dtype"])
                ).reshape(e["shape"]).copy()
        return out, manifest

    with tracing.span("checkpoint.restore_arrays",
                      nbytes=manifest["total_bytes"]):
        return _restore_ckio(path, manifest, num_readers, num_pes), manifest


def _restore_ckio(path: str, manifest: Dict[str, Any],
                  num_readers: Optional[int],
                  num_pes: int) -> Dict[str, np.ndarray]:
    from repro.core import CkIO, FileOptions
    from repro.core.autotune import suggest_num_readers

    data_off = manifest["data_offset"]
    ck = CkIO(num_pes=num_pes)
    total = manifest["total_bytes"]
    readers = num_readers or suggest_num_readers(total, num_pes, 1)
    fh = ck.open_sync(path, FileOptions(num_readers=readers))
    sess = ck.start_read_session_sync(fh, total, data_off)
    futs = []
    for i, e in enumerate(manifest["leaves"]):
        client = ck.make_client(pe=i % num_pes)
        futs.append(
            ck.read_view_future(sess, e["nbytes"], data_off + e["offset"],
                                client=client)
        )
    msgs = [f.wait(ck.sched, timeout=600) for f in futs]
    out: Dict[str, np.ndarray] = {}
    # Each leaf is an array over its borrowed view of the arena, made before
    # the session closes: the array's buffer export keeps the arena alive
    # through close and for as long as any leaf is held.
    with tracing.span("checkpoint.unpack"):
        for e, msg in zip(manifest["leaves"], msgs):
            out[e["name"]] = np.frombuffer(
                msg.data, dtype=np.dtype(e["dtype"])
            ).reshape(e["shape"])
            tracing.count("checkpoint.view_bytes", e["nbytes"])
        tracing.count("checkpoint.unpack_bytes", 0)     # nothing is copied
    ck.close_read_session_sync(sess)
    ck.close_sync(fh)
    return out


def restore_tree(path: str, like: Any, **kw) -> Tuple[Any, int]:
    """Restore into the structure of ``like`` (names must match)."""
    arrays, manifest = restore_arrays(path, **kw)
    names, leaves, treedef = _leaf_paths(like)
    missing = [n for n in names if n not in arrays]
    if missing:
        raise KeyError(f"checkpoint missing leaves: {missing[:5]} ...")
    new_leaves = [arrays[n] for n in names]
    return jax.tree_util.tree_unflatten(treedef, new_leaves), manifest["step"]


def restore_sharded(path: str, like: Any, shardings: Any, **kw) -> Tuple[Any, int]:
    """Elastic restore: place leaves onto a (possibly different) mesh."""
    tree, step = restore_tree(path, like, **kw)
    flat_t, treedef = jax.tree_util.tree_flatten(tree)
    flat_s = treedef.flatten_up_to(shardings)
    placed = [
        jax.device_put(t, s) if s is not None else jax.device_put(t)
        for t, s in zip(flat_t, flat_s)
    ]
    return jax.tree_util.tree_unflatten(treedef, placed), step


class AsyncCheckpointer:
    """Background-thread checkpoint writer with retention."""

    def __init__(self, directory: str, keep: int = 3):
        self.dir = directory
        self.keep = keep
        os.makedirs(directory, exist_ok=True)
        self._q: "queue.Queue" = queue.Queue()
        self._errors: List[BaseException] = []
        self._thread = threading.Thread(target=self._worker, daemon=True)
        self._thread.start()

    def path_for(self, step: int) -> str:
        return os.path.join(self.dir, f"step_{step:08d}.ckpt")

    def save(self, tree: Any, step: int) -> None:
        """Snapshot to host, then write asynchronously."""
        names, leaves, treedef = _leaf_paths(tree)
        host = [np.asarray(jax.device_get(x)) for x in leaves]
        snap = jax.tree_util.tree_unflatten(treedef, host)
        self._q.put((snap, step))

    def _worker(self) -> None:
        while True:
            item = self._q.get()
            if item is None:
                return
            snap, step = item
            try:
                save_checkpoint(self.path_for(step), snap, step)
                self._gc()
            except BaseException as e:  # surfaced by wait()
                self._errors.append(e)
            finally:
                self._q.task_done()

    def _gc(self) -> None:
        ckpts = sorted(self.list_steps())
        for s in ckpts[: -self.keep] if self.keep > 0 else []:
            try:
                os.remove(self.path_for(s))
            except OSError:
                pass

    def list_steps(self) -> List[int]:
        out = []
        for fn in os.listdir(self.dir):
            m = re.fullmatch(r"step_(\d+)\.ckpt", fn)
            if m:
                out.append(int(m.group(1)))
        return sorted(out)

    def latest(self) -> Optional[str]:
        steps = self.list_steps()
        return self.path_for(steps[-1]) if steps else None

    def wait(self) -> None:
        self._q.join()
        if self._errors:
            raise self._errors[0]

    def shutdown(self) -> None:
        self._q.join()
        self._q.put(None)
        self._thread.join(timeout=10)
