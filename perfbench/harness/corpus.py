"""The plain reference of the input path: a NumPy read of a step's window
straight from the token file (copied from ``chip_smoke.py``)."""
from __future__ import annotations

import json

import numpy as np

HEADER_BYTES = 4096     # the token file's one JSON header page


def read_header(path: str) -> dict:
    with open(path, "rb") as f:
        head = f.read(HEADER_BYTES)
    return json.loads(head.rstrip(b"\x00").decode())


def reference_window(path: str, step: int, batch: int, seq: int):
    """NumPy read of step ``step``'s window: ``(inputs, labels)`` as stored,
    for a file of ``batch * (seq + 1)`` tokens per step."""
    head = read_header(path)
    dtype = np.dtype(head["dtype"])
    n = batch * (seq + 1)
    rows = np.fromfile(path, dtype=dtype, count=n,
                       offset=HEADER_BYTES + step * n * dtype.itemsize)
    if rows.size != n:
        raise ValueError(f"{path}: step {step} runs past the end of the file")
    rows = rows.reshape(batch, seq + 1)
    return rows[:, :seq], rows[:, 1:]


def mismatched_tokens(got, want) -> int:
    """Tokens of ``got`` whose bits differ from ``want`` (all of them when the
    shapes or item sizes differ)."""
    got = np.asarray(got)
    if got.shape != want.shape or got.dtype.itemsize != want.itemsize:
        return int(want.size)
    return int(np.count_nonzero(got.view(want.dtype) != want))


def write_corpus(path: str, num_tokens: int, vocab: int, seed: int) -> None:
    """A flat uint32 token file of ``num_tokens`` tokens drawn from ``seed``,
    in the token file format (one JSON header page, then the tokens)."""
    tokens = np.random.default_rng(seed).integers(
        0, vocab, size=num_tokens, dtype=np.uint32)
    head = json.dumps({"magic": "CKIO-TOKENS-v1", "dtype": "<u4",
                       "shape": [num_tokens]}).encode()
    with open(path, "wb") as f:
        f.write(head.ljust(HEADER_BYTES, b"\x00"))
        tokens.tofile(f)
