"""The reassembly kernels compile for a TPU v5e chip that is described, not
attached: the TPU compiler refuses here what the chip would refuse (block
tiling, unaligned slices, VMEM and SMEM limits), at no chip time.

Shapes are the per-chip step window (8 x 4,097 tokens) and ``train_4k``'s
whole window (256 x 4,097). The topology is described inside a fixture, so
that only the test process that runs this file loads the TPU library.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops
from repro.kernels.reassemble import (
    reassemble_pallas,
    reassemble_tokens_pallas,
    reassemble_window_pallas,
)

SEQ = 4096
S1 = SEQ + 1
WINDOWS = {"per_chip": 8, "train_4k": 256}


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.experimental.compilation_cache import compilation_cache

    # A compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache off around them.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, *shapes, sharding):
    args = [jax.ShapeDtypeStruct(s, jnp.int32, sharding=sharding)
            for s in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    return text


@pytest.mark.parametrize("window", WINDOWS)
@pytest.mark.parametrize("kind", ["aligned", "unaligned", "remainder"])
def test_window_kernel_compiles(window, kind, one_chip):
    B = WINDOWS[window]
    off = {"aligned": 0, "unaligned": 5, "remainder": 0}[kind]
    n = B * S1 - (100 if kind == "remainder" else 0)

    def fn(lin):
        return reassemble_window_pallas(
            lin, global_batch=B, seq_len=SEQ, window_tok_off=off,
            valid_limit=off + n)

    _compile(fn, (off + n,), sharding=one_chip)


@pytest.mark.parametrize("window", WINDOWS)
def test_block_gather_compiles(window, one_chip):
    B = WINDOWS[window]
    _compile(reassemble_pallas, (B, S1), (B,), sharding=one_chip)


@pytest.mark.parametrize("window", WINDOWS)
def test_token_gather_compiles(window, one_chip):
    B = WINDOWS[window]

    def fn(staged, row_idx):
        return reassemble_tokens_pallas(staged, row_idx, pad_id=0)

    _compile(fn, (B * S1,), (B, S1), sharding=one_chip)


def test_streamed_ingest_compiles(one_chip, monkeypatch):
    # ops picks interpret mode from the backend, which is the CPU here;
    # the chip's branch is the compiled kernel.
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    jax.clear_caches()
    B, chunks = WINDOWS["per_chip"], 8
    per = B * S1 // chunks

    def fn(*parts):
        return ops.ingest_chunks_window(
            list(parts), global_batch=B, seq_len=SEQ,
            valid_limit=B * S1, use_pallas=True)

    _compile(fn, *[(per,)] * (chunks - 1), (B * S1 - per * (chunks - 1),),
             sharding=one_chip)
    jax.clear_caches()
