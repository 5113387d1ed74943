"""Per-architecture smoke tests (reduced same-family configs) + decode
consistency against the teacher-forced forward."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import SHAPES_BY_NAME
from repro.configs.registry import get_config, list_archs, smoke_config
from repro.models import build_model

KEY = jax.random.PRNGKey(0)
B, S = 2, 32


def _batch(cfg, with_labels=True):
    ks = jax.random.split(KEY, 2)
    if cfg.is_encdec:
        b = {
            "embeds": jax.random.normal(ks[0], (B, cfg.encoder_seq, cfg.d_model),
                                        jnp.bfloat16) * 0.02,
            "tokens": jax.random.randint(ks[1], (B, S), 0, cfg.vocab_size),
        }
    elif cfg.input_mode == "embeddings":
        b = {"embeds": jax.random.normal(ks[0], (B, S, cfg.d_model),
                                         jnp.bfloat16) * 0.02}
        if cfg.mrope_sections:
            b["positions"] = jnp.broadcast_to(
                jnp.arange(S, dtype=jnp.int32)[None, :, None], (B, S, 3)
            )
    else:
        b = {"tokens": jax.random.randint(ks[0], (B, S), 0, cfg.vocab_size)}
    if with_labels:
        b["labels"] = jax.random.randint(ks[1], (B, S), 0, cfg.vocab_size)
    return b


@pytest.mark.parametrize("arch", list_archs())
def test_arch_train_step_smoke(arch):
    cfg = smoke_config(get_config(arch))
    model = build_model(cfg)
    params = model.init(KEY)
    loss, metrics = jax.jit(model.loss)(params, _batch(cfg))
    assert np.isfinite(float(loss)), f"{arch}: non-finite loss"
    # ~ln(vocab) at init
    assert 0.5 * np.log(cfg.vocab_size) < float(loss) < 3 * np.log(cfg.vocab_size)
    # gradients flow and are finite
    grads = jax.grad(lambda p: model.loss(p, _batch(cfg))[0])(params)
    gleaves = jax.tree.leaves(grads)
    assert all(np.isfinite(np.asarray(g, np.float32)).all() for g in gleaves)
    assert any(float(jnp.abs(g.astype(jnp.float32)).max()) > 0 for g in gleaves)


@pytest.mark.parametrize("arch", list_archs())
def test_arch_prefill_and_decode_smoke(arch):
    cfg = smoke_config(get_config(arch))
    model = build_model(cfg)
    params = model.init(KEY)
    batch = _batch(cfg, with_labels=False)
    logits = jax.jit(model.prefill_logits)(params, batch)
    assert logits.shape == (B, 1, cfg.vocab_size)
    frames = batch.get("embeds") if cfg.is_encdec else None
    state = model.init_decode_state(params, B, 16, frames=frames)
    lg, state2 = jax.jit(model.decode)(params, state,
                                       {"tokens": jnp.ones((B, 1), jnp.int32)})
    assert lg.shape == (B, 1, cfg.vocab_size)
    assert np.isfinite(np.asarray(lg, np.float32)).all()
    assert int(state2.pos) == 1


@pytest.mark.parametrize(
    "arch",
    ["codeqwen1.5-7b", "falcon-mamba-7b", "recurrentgemma-2b", "gemma3-27b",
     "moonlight-16b-a3b"],
)
def test_decode_matches_teacher_forcing(arch):
    """Token-by-token decode must reproduce the full-sequence forward —
    validates ring KV caches (global + windowed), the MLA latent cache,
    mamba and RG-LRU decode states against their train-time scans."""
    cfg = smoke_config(get_config(arch)).replace(dtype="float32")
    model = build_model(cfg)
    params = model.init(KEY)
    T = 12
    tokens = jax.random.randint(KEY, (B, T), 0, cfg.vocab_size)
    full = model._m.forward_logits(params, cfg, {"tokens": tokens},
                                   last_only=False)
    state = model.init_decode_state(params, B, 2 * T)
    dec_logits = []
    decode = jax.jit(model.decode)
    for t in range(T):
        lg, state = decode(params, state, {"tokens": tokens[:, t:t + 1]})
        dec_logits.append(lg[:, 0])
    dec = jnp.stack(dec_logits, axis=1)
    np.testing.assert_allclose(
        np.asarray(dec, np.float32), np.asarray(full, np.float32),
        atol=2e-3, rtol=2e-3,
    )


def test_moe_capacity_and_padding():
    """Padded experts must receive no routing weight."""
    from repro.models.moe import moe_apply, moe_init

    d, E_real, pad, ff = 16, 6, 2, 8
    params = moe_init(KEY, d, E_real, ff, 0, jnp.float32, expert_pad=pad)
    x = jax.random.normal(KEY, (2, 8, d))
    out, aux = moe_apply(params, x, top_k=2, capacity_factor=2.0,
                         dtype=jnp.float32, num_real_experts=E_real)
    assert out.shape == x.shape
    assert np.isfinite(float(aux))
    # zero out real experts' weights -> output must be exactly zero even
    # though padded experts have nonzero weights (proves they're masked)
    z = dict(params)
    for k in ("gate", "up", "down"):
        z[k] = params[k].at[:E_real].set(0.0)
    out_z, _ = moe_apply(z, x, top_k=2, capacity_factor=2.0,
                         dtype=jnp.float32, num_real_experts=E_real)
    np.testing.assert_allclose(np.asarray(out_z), 0.0, atol=1e-6)


def test_sliding_window_attention_masks_past():
    """A token beyond the window must not influence attention output."""
    from repro.models.attention import attention_train, attn_init
    from repro.models.layers import rope_angles

    d, H, hd, S, W = 16, 2, 8, 16, 4
    params = attn_init(KEY, d, H, H, hd, jnp.float32)
    x = jax.random.normal(KEY, (1, S, d))
    pos = jnp.arange(S)[None]
    cos, sin = rope_angles(pos, hd, 1e4)
    y1 = attention_train(params, x, cos, sin, dtype=jnp.float32, eps=1e-6,
                         window=W)
    x2 = x.at[0, 0].set(99.0)      # outside the window of position >= W
    y2 = attention_train(params, x2, cos, sin, dtype=jnp.float32, eps=1e-6,
                         window=W)
    np.testing.assert_allclose(np.asarray(y1[0, W:]), np.asarray(y2[0, W:]),
                               atol=1e-5)
    assert not np.allclose(np.asarray(y1[0, 0]), np.asarray(y2[0, 0]))


def test_input_specs_cover_all_cells():
    from repro.configs.registry import cells

    n = 0
    for arch, shape in cells():
        model = build_model(get_config(arch))
        specs = model.input_specs(shape)
        assert specs, (arch, shape.name)
        for leaf in jax.tree.leaves(specs):
            assert isinstance(leaf, jax.ShapeDtypeStruct)
        if shape.kind == "decode":
            st = model.decode_state_specs(shape)
            assert jax.tree.leaves(st)
        n += 1
    assert n == 35   # 11 archs x 4 shapes - 9 long_500k skips
