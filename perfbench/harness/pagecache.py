"""Page-cache eviction, verified with mincore (copied from
``benchmarks/common.py``): a cold read is measured, never assumed."""
from __future__ import annotations

import ctypes
import ctypes.util
import mmap
import os
from typing import Optional


def residency(path: str) -> Optional[float]:
    """Fraction of ``path``'s pages resident in the page cache, or ``None``
    when mincore is not usable."""
    try:
        libc = ctypes.CDLL(ctypes.util.find_library("c") or "libc.so.6",
                           use_errno=True)
        libc.mincore
    except (OSError, AttributeError):
        return None
    try:
        size = os.path.getsize(path)
        if size <= 0:
            return None
        npages = (size + mmap.PAGESIZE - 1) // mmap.PAGESIZE
        fd = os.open(path, os.O_RDONLY)
        try:
            m = mmap.mmap(fd, size, flags=mmap.MAP_PRIVATE,
                          prot=mmap.PROT_READ | mmap.PROT_WRITE)
        finally:
            os.close(fd)
        try:
            vec = (ctypes.c_ubyte * npages)()
            addr = ctypes.addressof(ctypes.c_char.from_buffer(m))
            if libc.mincore(ctypes.c_void_p(addr), ctypes.c_size_t(size),
                            vec) != 0:
                return None
            return sum(b & 1 for b in vec) / npages
        finally:
            del vec
            m.close()
    except (OSError, ValueError):
        return None


def cold(path: str) -> bool:
    """Evict ``path`` from the page cache and verify it: True only when the
    advice was taken and mincore finds (almost) nothing resident."""
    fd = os.open(path, os.O_RDONLY)
    try:
        os.fsync(fd)                     # dirty pages cannot be dropped
        os.posix_fadvise(fd, 0, 0, os.POSIX_FADV_DONTNEED)
    finally:
        os.close(fd)
    frac = residency(path)
    return frac is not None and frac <= 0.02
