"""CkIO-side read drivers shared by the benchmarks."""
from __future__ import annotations

import time
from typing import Dict, Optional, Tuple

from repro.core import CkIO, FileOptions


def ckio_read(
    path: str,
    num_clients: int,
    num_readers: int,
    num_pes: int = 8,
    pes_per_node: int = 4,
    splinter_bytes: int = 8 << 20,
    network=None,
    pfs=None,
    timeout: float = 300.0,
) -> Tuple[int, Dict[str, float]]:
    """Full-file session read with ``num_clients`` over-decomposed consumers.

    Returns (bytes_read, session-metrics summary)."""
    ck = CkIO(num_pes=num_pes, pes_per_node=pes_per_node)
    fh = ck.open_sync(path, FileOptions(
        num_readers=num_readers,
        splinter_bytes=splinter_bytes,
        network=network,
        delay_model=pfs.reader_delay_model() if pfs is not None else None,
    ))
    sess = ck.start_read_session_sync(fh, fh.size, 0)
    per = fh.size // num_clients
    futs = []
    for i in range(num_clients):
        off = i * per
        n = per if i < num_clients - 1 else fh.size - off
        c = ck.make_client(pe=i % num_pes)
        futs.append(ck.read_future(sess, n, off, client=c))
    done = 0
    for f in futs:
        done += f.wait(ck.sched, timeout=timeout).nbytes
    summary = sess.metrics.summary()
    ck.close_read_session_sync(sess)
    ck.close_sync(fh)
    return done, summary
