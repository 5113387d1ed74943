"""The reassembly kernels compile for a TPU v5e chip that is described, not
attached: the TPU compiler refuses here what the chip would refuse (block
tiling, unaligned slices, VMEM and SMEM limits), at no chip time.

Shapes are the per-chip step window (8 x 4,097 tokens), ``train_4k``'s
whole window (256 x 4,097) and the Moonlight cell's window (4 x 8,193). The
Moonlight cell's grouped matmul (``jax.lax.ragged_dot`` over the 8 held
experts, which the TPU compiler lowers to a kernel of its own) compiles at
the cell's shapes, forward and backward. The topology is described inside a
fixture, so that only the test process that runs this file loads the TPU
library.
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

WINDOWS = {"per_chip": (8, 4096), "train_4k": (256, 4096),
           "moonlight": (4, 8192)}


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
    args = [s if isinstance(s, jax.ShapeDtypeStruct)
            else jax.ShapeDtypeStruct(s, jnp.int32, sharding=sharding)
            for s in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    return text


@pytest.mark.parametrize("window", WINDOWS)
@pytest.mark.parametrize("kind", ["aligned", "unaligned", "remainder"])
def test_window_kernel_compiles(window, kind, one_chip):
    B, seq = WINDOWS[window]
    off = {"aligned": 0, "unaligned": 5, "remainder": 0}[kind]
    n = B * (seq + 1) - (100 if kind == "remainder" else 0)

    def fn(lin):
        return reassemble_window_pallas(
            lin, global_batch=B, seq_len=seq, window_tok_off=off,
            valid_limit=off + n)

    _compile(fn, (off + n,), sharding=one_chip)


@pytest.mark.parametrize("window", WINDOWS)
def test_block_gather_compiles(window, one_chip):
    B, seq = WINDOWS[window]
    _compile(reassemble_pallas, (B, seq + 1), (B,), sharding=one_chip)


@pytest.mark.parametrize("window", WINDOWS)
def test_token_gather_compiles(window, one_chip):
    B, seq = WINDOWS[window]

    def fn(staged, row_idx):
        return reassemble_tokens_pallas(staged, row_idx, pad_id=0)

    _compile(fn, (B * (seq + 1),), (B, seq + 1), sharding=one_chip)


def test_streamed_ingest_compiles(one_chip, monkeypatch):
    # ops picks interpret mode from the backend, which is the CPU here;
    # the chip's branch is the compiled kernel.
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    jax.clear_caches()
    (B, seq), chunks = WINDOWS["per_chip"], 8
    S1 = seq + 1
    per = B * S1 // chunks

    def fn(*parts):
        return ops.ingest_chunks_window(
            list(parts), global_batch=B, seq_len=seq,
            valid_limit=B * S1, use_pallas=True)

    _compile(fn, *[(per,)] * (chunks - 1), (B * S1 - per * (chunks - 1),),
             sharding=one_chip)
    jax.clear_caches()


def test_grouped_matmul_compiles_at_the_moonlight_cell(one_chip):
    """The 8 held experts' SwiGLU over one microbatch's routed rows
    (8,192 tokens x 6 choices in the worst case), forward and backward."""
    from repro.models.moe import expert_ffn

    held, d, f, rows = 8, 2048, 1408, 8192 * 6

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def loss(xs, gate, up, down, sizes):
        w = {"gate": gate, "up": up, "down": down}
        return jnp.sum(expert_ffn(xs, sizes, w, jnp.bfloat16)
                       .astype(jnp.float32))

    text = _compile(jax.grad(loss, argnums=(0, 1, 2, 3)),
                    spec((rows, d), jnp.bfloat16),
                    spec((held, d, f), jnp.float32),
                    spec((held, d, f), jnp.float32),
                    spec((held, f, d), jnp.float32),
                    spec((held,), jnp.int32), sharding=one_chip)
    assert "ragged-dot" in text
