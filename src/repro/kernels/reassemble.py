"""Pallas TPU kernels: CkIO phase-2 data permutation, on device.

The paper's second phase permutes reader-striped data to consumer order in
host DRAM. On TPU the right place for that permutation is on-device: the
staged session buffer is DMA'd to HBM **once**, in whatever order the bytes
arrived, and these kernels reassemble batch-major training arrays at HBM
bandwidth. Three kernels cover the ingest pipeline:

``reassemble_pallas``
    Uniform block gather ``out[i] = src[idx[i]]`` over the leading axis
    (``src`` may be 2-D ``(NB, T)`` token blocks or N-D row blocks). The
    splinter->destination map is a scalar-prefetch operand parametrizing the
    *source* BlockSpec index map, so each output block is one aligned
    HBM->VMEM->HBM copy — a pure-bandwidth kernel with no compute, exactly
    the roofline shape of the paper's "data permutation" cost centre (§V-B).
    Used to restore file order from an arrival-ordered staging when splinter
    boundaries are block-uniform.

``reassemble_window_pallas``
    Fused batch-major reassembly of an LM step window: a file-order token
    buffer (at any token offset ``window_tok_off``) becomes ``(inputs,
    labels)`` of shape ``(B, S)`` in one kernel — the label shift-by-one
    rides the same copy, and remainder windows (``valid_limit``) are padded
    with ``pad_id`` on device. The window is viewed as ``(B, S+1)`` rows
    starting at its own offset, and each grid step moves one block of
    ``_ROWS`` whole rows.

``reassemble_tokens_pallas``
    General token-level gather for staged layouts whose splinter boundaries
    do *not* align to uniform blocks: per output row a precomputed
    ``(B, S+1)`` index row gathers from the full staged buffer (``-1`` =
    pad). The staged buffer is resident in VMEM whole, so this path is
    bounded by VMEM (fine for per-host step windows), and it moves one token
    per loop iteration; the block kernels above are preferred whenever the
    layout permits.

TPU tiling shapes every block here: the last two block dims must be
multiples of ``(8, 128)`` or span the whole array, so rows move in blocks
of ``_ROWS`` (batches are padded up to it and the padding sliced off), and
1-D token rows are given a unit middle axis.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_ROWS = 8      # sublanes of one TPU vreg
_LANES = 128   # lanes of one TPU vreg


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def _gather_kernel(idx_ref, src_ref, out_ref):
    del idx_ref  # consumed by the index map
    out_ref[...] = src_ref[...]


def reassemble_pallas(
    src: jax.Array,           # (NB, ...) — uniform blocks over axis 0
    idx: jax.Array,           # (NBo,) int32, values in [0, NB)
    *,
    interpret: bool = False,
) -> jax.Array:
    """Block gather ``out[i] = src[idx[i]]`` over the leading axis."""
    if src.ndim < 2:
        raise ValueError(f"src must have >= 2 dims (got shape {src.shape})")
    if src.ndim == 2:
        # A (1, T) block of an (NB, T) array breaks the sublane tiling; as
        # (NB, 1, T) each block's last two dims span the whole array.
        return reassemble_pallas(src[:, None, :], idx,
                                 interpret=interpret)[:, 0, :]
    rest = src.shape[1:]
    NBo = idx.shape[0]
    zeros = (0,) * len(rest)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(NBo,),
        in_specs=[
            pl.BlockSpec((None,) + rest,
                         lambda i, idx_ref: (idx_ref[i],) + zeros),
        ],
        out_specs=pl.BlockSpec((None,) + rest, lambda i, idx_ref: (i,) + zeros),
    )
    return pl.pallas_call(
        _gather_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((NBo,) + rest, src.dtype),
        interpret=interpret,
    )(idx, src)


def reassemble_window_pallas(
    linear: jax.Array,        # (L,) file-order tokens (session coordinates)
    *,
    global_batch: int,
    seq_len: int,
    window_tok_off: int = 0,
    valid_limit: int | None = None,
    pad_id: int = 0,
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """File-order token buffer -> batch-major ``(inputs, labels)``, fused.

    Output row ``b`` covers flat positions ``window_tok_off + b*(S+1) + j``;
    ``labels`` are the same row shifted by one token. Positions at or beyond
    ``valid_limit`` (absolute, in ``linear`` coordinates — remainder final
    windows) read as ``pad_id``. The window's offset is applied where the
    buffer is viewed as ``(B, S+1)`` rows, so every row is one source row
    whatever the offset.
    """
    B, S = global_batch, seq_len
    S1 = S + 1
    Bp = _round_up(B, _ROWS)
    w0 = window_tok_off
    full_limit = w0 + B * S1
    if valid_limit is None:
        valid_limit = full_limit
    mask_tail = valid_limit < full_limit

    need = w0 + Bp * S1
    L = linear.shape[0]
    if L < need:
        linear = jnp.pad(linear, (0, need - L), constant_values=pad_id)
    rows = linear[w0:need].reshape(Bp, S1)

    def kern(a_ref, inp_ref, lab_ref):
        seg = a_ref[...]                                       # (_ROWS, S1)
        inp, lab = seg[:, :S], seg[:, 1:]
        if mask_tail:
            pad = jnp.asarray(pad_id, dtype=seg.dtype)
            row = jax.lax.broadcasted_iota(jnp.int32, (_ROWS, S), 0)
            col = jax.lax.broadcasted_iota(jnp.int32, (_ROWS, S), 1)
            pos = w0 + (pl.program_id(0) * _ROWS + row) * S1 + col
            inp = jnp.where(pos < valid_limit, inp, pad)
            lab = jnp.where(pos + 1 < valid_limit, lab, pad)
        inp_ref[...] = inp
        lab_ref[...] = lab

    out = jax.ShapeDtypeStruct((Bp, S), linear.dtype)
    out_spec = pl.BlockSpec((_ROWS, S), lambda b: (b, 0))
    inputs, labels = pl.pallas_call(
        kern,
        grid=(Bp // _ROWS,),
        in_specs=[pl.BlockSpec((_ROWS, S1), lambda b: (b, 0))],
        out_specs=[out_spec, out_spec],
        out_shape=[out, out],
        interpret=interpret,
    )(rows)
    return inputs[:B], labels[:B]


def reassemble_tokens_pallas(
    staged: jax.Array,        # (L,) staged tokens, arbitrary layout
    row_idx: jax.Array,       # (B, S+1) int32 staged positions; -1 = pad
    *,
    pad_id: int = 0,
    interpret: bool = False,
) -> Tuple[jax.Array, jax.Array]:
    """Token-level gather: row ``b`` of the window is ``staged[row_idx[b]]``.

    ``row_idx[b, j]`` is the staged position of window flat token
    ``b*(S+1)+j`` (``j`` in ``[0, S+1)`` — the last column only feeds the
    label shift); negative entries pad. The staged buffer sits whole in
    VMEM as ``(L/128, 128)`` lanes; each grid step gathers one ``(8, 128)``
    tile of the flattened index, reading its positions from SMEM and
    rotating each source lane into place. Tokens travel as 32-bit lanes
    (narrower integer tokens are widened and narrowed back).
    """
    B, S2 = row_idx.shape
    S = S2 - 1
    L = staged.shape[0]
    dtype = staged.dtype
    if dtype.itemsize == 4:
        carrier = jax.lax.bitcast_convert_type(staged, jnp.int32)
    elif jnp.issubdtype(dtype, jnp.integer):
        carrier = staged.astype(jnp.int32)
    else:
        raise ValueError(f"token dtype {dtype} is not 32-bit or integer")
    R = -(-L // _LANES)
    table = jnp.pad(carrier, (0, R * _LANES - L)).reshape(R, _LANES)
    tile = _ROWS * _LANES
    flat = jnp.clip(row_idx, 0, L - 1).reshape(-1)
    N = flat.shape[0]
    Np = _round_up(N, tile)
    flat = jnp.pad(flat, (0, Np - N))

    def kern(idx_ref, tab_ref, out_ref):
        lane = jax.lax.broadcasted_iota(jnp.int32, (1, _LANES), 1)

        def fill_row(r, carry):
            def one(k, acc):
                p = idx_ref[r * _LANES + k]
                src = tab_ref[pl.ds(p // _LANES, 1), :]        # (1, 128)
                moved = pltpu.roll(src, (k - p % _LANES) % _LANES, 1)
                return jnp.where(lane == k, moved, acc)

            out_ref[pl.ds(r, 1), :] = jax.lax.fori_loop(
                0, _LANES, one, jnp.zeros((1, _LANES), jnp.int32))
            return carry

        jax.lax.fori_loop(0, _ROWS, fill_row, 0)

    got = pl.pallas_call(
        kern,
        grid=(Np // tile,),
        in_specs=[
            pl.BlockSpec((tile,), lambda i: (i,), memory_space=pltpu.SMEM),
            pl.BlockSpec((R, _LANES), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((_ROWS, _LANES), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((Np // _LANES, _LANES), jnp.int32),
        interpret=interpret,
    )(flat, table)
    got = got.reshape(-1)[:N].reshape(B, S2)
    rows = (jax.lax.bitcast_convert_type(got, dtype) if dtype.itemsize == 4
            else got.astype(dtype))
    pad = jnp.asarray(pad_id, dtype=dtype)
    inputs = jnp.where(row_idx[:, :S] >= 0, rows[:, :S], pad)
    labels = jnp.where(row_idx[:, 1:] >= 0, rows[:, 1:], pad)
    return inputs, labels
