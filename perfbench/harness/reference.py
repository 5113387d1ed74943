"""Plain reference of one chip's share of the phi4-mini train step.

Forward pass, mean cross-entropy, gradients and AdamW in straightforward
``jax.numpy``: float32 throughout, every matmul at HIGHEST precision, one
sequence at a time with each layer rematerialised, so that it fits one chip
beside nothing else. It imports nothing of the program and is given nothing
the program made: its weights come from ``harness.state`` and its batches
from a NumPy read of the token file.

The architecture is the one the program runs: pre-norm decoder layers,
RMSNorm applied as ``x * rsqrt(mean(x^2) + eps) * (1 + scale)``, grouped-query
causal attention with rotary embeddings on every head dimension
(rotate-half, positions 0..S-1), a SwiGLU MLP, a final RMSNorm and a head
tied to the embedding. AdamW: the gradient clipped to a global norm,
bias-corrected moments, decoupled weight decay on every leaf, and a linear
warm-up into a cosine decay.

``operand_dtype`` rounds every matmul operand to a lower precision first
(per-tensor scaled, accumulation still float32): the control that a check
must fail."""
from __future__ import annotations

import math
from typing import Callable, Dict, List, Optional, Sequence

Q_BLOCK = 512        # query rows per attention block


def _round(x, dtype: Optional[str]):
    """``x`` with its values rounded to ``dtype`` (scaled so that its largest
    magnitude fills the format); the gradient passes straight through."""
    import jax
    import jax.numpy as jnp

    if dtype is None:
        return x
    dt = jnp.dtype(dtype)
    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0, float(jnp.finfo(dt).max) / amax, 1.0)
    q = (x * scale).astype(dt).astype(jnp.float32) / scale
    return x + jax.lax.stop_gradient(q - x)


def build(c: dict, operand_dtype: Optional[str] = None):
    """The mean loss of one sequence, ``row_loss(params, tokens, labels)``,
    for configuration ``c`` (its file's keys)."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    eps = c["rms_norm_eps"]
    theta = c["rope_theta"]
    hd = c["head_dim"]
    n_kv = c["num_key_value_heads"]
    groups = c["num_attention_heads"] // n_kv

    def mm(spec, a, b):
        return jnp.einsum(spec, _round(a, operand_dtype),
                          _round(b, operand_dtype),
                          precision=lax.Precision.HIGHEST,
                          preferred_element_type=jnp.float32)

    def rms(x, scale):
        var = jnp.mean(x * x, axis=-1, keepdims=True)
        return x * lax.rsqrt(var + eps) * (1.0 + scale)

    def rope(x):                                   # (S, N, hd)
        half = hd // 2
        inv = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) / half)
        ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * inv
        cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
        x1, x2 = x[..., :half], x[..., half:]
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)

    def attention(h, p):
        S = h.shape[0]
        q = rope(mm("sd,dhk->shk", h, p["wq"]))
        k = rope(mm("sd,dhk->shk", h, p["wk"]))
        v = mm("sd,dhk->shk", h, p["wv"])
        q = q.reshape(S, n_kv, groups, hd)
        kpos = jnp.arange(S)[None, :]

        nb = max(1, S // Q_BLOCK)
        size = S // nb

        @jax.checkpoint
        def block(args):
            qb, q0 = args
            scores = mm("qkgd,skd->kgqs", qb, k) * hd ** -0.5
            qpos = q0 + jnp.arange(size)[:, None]
            scores = jnp.where(kpos <= qpos, scores, -jnp.inf)
            probs = jax.nn.softmax(scores, axis=-1)
            return mm("kgqs,skd->qkgd", probs, v)

        out = lax.map(block, (q.reshape(nb, size, n_kv, groups, hd),
                              jnp.arange(nb) * size))
        return mm("shk,hkd->sd", out.reshape(S, -1, hd), p["wo"])

    def mlp(h, p):
        g = mm("sd,df->sf", h, p["gate"])
        u = mm("sd,df->sf", h, p["up"])
        return mm("sf,fd->sd", jax.nn.silu(g) * u, p["down"])

    @jax.checkpoint
    def layer(x, lp):
        x = x + attention(rms(x, lp["norm1"]["scale"]), lp["mixer"])
        return x + mlp(rms(x, lp["norm2"]["scale"]), lp["ffn"]), None

    def row_loss(params, tokens, labels):
        table = params["embed"]["table"]
        x, _ = lax.scan(layer, table[tokens], params["blocks"]["l0"])
        x = rms(x, params["final_norm"]["scale"])
        logits = mm("sd,vd->sv", x, table)
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
        return jnp.mean(lse - gold)

    return row_loss


def _lr(opt: dict, step: int) -> float:
    """Linear warm-up to ``peak_lr`` over ``warmup_steps``, then a cosine
    decay to ``min_lr_ratio`` of it at ``decay_steps``."""
    s, w = float(step), opt["warmup_steps"]
    if s < w:
        return opt["peak_lr"] * s / max(w, 1)
    t = min(max((s - w) / max(opt["decay_steps"] - w, 1), 0.0), 1.0)
    r = opt["min_lr_ratio"]
    return opt["peak_lr"] * (r + (1 - r) * 0.5 * (1 + math.cos(math.pi * t)))


class Reference:
    """Runs ``steps`` AdamW steps of the reference and keeps the readings a
    check compares: each step's loss, each leaf's norm of the first gradient
    as the optimizer takes it (after clipping), of the first raw gradient,
    and of the parameters' change over the steps."""

    def __init__(self, c: dict, opt: dict, operand_dtype: Optional[str] = None):
        import jax
        import jax.numpy as jnp

        self.opt = opt
        row_loss = build(c, operand_dtype)
        vg = jax.value_and_grad(row_loss)

        def accumulate(acc, loss_sum, params, tokens, labels):
            loss, g = vg(params, tokens, labels)
            return jax.tree.map(jnp.add, acc, g), loss_sum + loss

        self._accumulate = jax.jit(accumulate, donate_argnums=(0, 1))

        def update(params, mu, nu, grads, lr, t):
            b1, b2 = opt["b1"], opt["b2"]
            leaves = jax.tree.leaves(grads)
            gnorm = jnp.sqrt(sum(jnp.sum(g * g) for g in leaves))
            clip = jnp.minimum(1.0, opt["grad_clip"] / jnp.maximum(gnorm, 1e-9))
            g = jax.tree.map(lambda x: x * clip, grads)
            mu = jax.tree.map(lambda m, x: b1 * m + (1 - b1) * x, mu, g)
            nu = jax.tree.map(lambda v, x: b2 * v + (1 - b2) * x * x, nu, g)

            def step(p, m, v):
                mhat = m / (1 - b1 ** t)
                vhat = v / (1 - b2 ** t)
                return p - lr * (mhat / (jnp.sqrt(vhat) + opt["eps"])
                                 + opt["weight_decay"] * p)

            return jax.tree.map(step, params, mu, nu), mu, nu, g

        self._update = jax.jit(update, donate_argnums=(0, 1, 2, 3))
        self._norms = jax.jit(leaf_norms)
        self._diff_norms = jax.jit(
            lambda a, b: leaf_norms(jax.tree.map(jnp.subtract, a, b)))

    def run(self, make_params: Callable, batches: Sequence) -> Dict[str, list]:
        """``make_params()`` gives the initial parameters on the device;
        ``batches`` are ``(tokens, labels)`` NumPy arrays, one per step."""
        import jax
        import jax.numpy as jnp

        params = make_params()
        mu = jax.tree.map(jnp.zeros_like, params)
        nu = jax.tree.map(jnp.zeros_like, params)
        out: Dict[str, list] = {"loss": []}
        for t, (tokens, labels) in enumerate(batches, start=1):
            acc = jax.tree.map(jnp.zeros_like, params)
            loss_sum = jnp.zeros((), jnp.float32)
            for row in range(tokens.shape[0]):
                acc, loss_sum = self._accumulate(
                    acc, loss_sum, params, jnp.asarray(tokens[row], jnp.int32),
                    jnp.asarray(labels[row], jnp.int32))
            rows = tokens.shape[0]
            grads = jax.tree.map(lambda a: a / rows, acc)
            del acc
            out["loss"].append(float(loss_sum) / rows)
            if t == 1:
                out["grad_raw"] = [float(v) for v in self._norms(grads)]
            params, mu, nu, g = self._update(
                params, mu, nu, grads, _lr(self.opt, t), float(t))
            if t == 1:
                out["grad"] = [float(v) for v in self._norms(g)]
            del g
        del mu, nu
        p0 = make_params()
        out["change"] = [float(v) for v in self._diff_norms(params, p0)]
        del params, p0
        return out


def leaf_norms(tree) -> List:
    """Each leaf's Euclidean norm, in float32, in the tree's leaf order."""
    import jax
    import jax.numpy as jnp

    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
                      for x in jax.tree.leaves(tree)])


def leaf_names(tree) -> List[str]:
    import jax

    return [jax.tree_util.keystr(p)
            for p, _ in jax.tree_util.tree_flatten_with_path(tree)[0]]
