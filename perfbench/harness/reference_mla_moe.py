"""Plain reference of one chip's share of the Moonlight train step: the
benchmark's own copy, written from the layer equations of DeepSeek-V3
(arXiv:2412.19437 §2.1) and importing nothing of the program.

Forward pass, loss, gradients, the experts' loads, AdamW and the routers'
bias update in straightforward ``jax.numpy``: float32 throughout, every
matmul at HIGHEST precision, one sequence at a time with each layer
rematerialised and attention computed in blocks of query rows, so that it
fits one chip beside nothing else. Its weights come from
``harness.state_moe`` and its batches from a NumPy read of the token file.

For ``h`` the normed input of a layer (RMSNorm applied as ``x * rsqrt(
mean(x^2) + eps) * (1 + scale)``):

- latent attention: ``q = h W_q`` (heads of qk_nope + qk_rope);
  ``[c, k_pe] = h W_kva``; ``c = RMSNorm(c)``; ``[k_nope, v] = c W_kvb``;
  rotary embedding of interleaved pairs on ``q_pe`` and on the one ``k_pe``
  all heads share; causal softmax of ``(q_nope.k_nope + q_pe.k_pe) /
  sqrt(qk_nope + qk_rope)``; ``concat_h(probs v) W_o``;
- routing: ``s = sigmoid(h W_r)`` over every expert, the top k chosen on
  ``s + b`` (``b`` the correction bias, no gradient), gates ``s_chosen /
  sum s_chosen x routed_scaling_factor``;
- MoE output: every held expert's SwiGLU on every token times its gate (0
  where the token did not choose it), plus the shared experts' SwiGLU;
- the sequence-wise balance loss, weight ``seq_aux_alpha``;
- layer 0 dense; a final RMSNorm and an untied head; mean cross-entropy.

AdamW as ``harness.reference`` runs it, on every leaf but the correction
biases; after each step each bias moves by ``bias_update_speed x
sign(mean load - load)``, the loads counted over the step's tokens.

``operand_dtype`` rounds every matmul operand to a lower precision first,
the control that a check must fail."""
from __future__ import annotations

from typing import Callable, Dict, Optional, Sequence

from harness.reference import Q_BLOCK, _lr, _round, leaf_norms

BIAS = "router_bias"


def _layers(c: dict, operand_dtype: Optional[str] = None) -> dict:
    """The model's pieces for one sequence: ``rms``, ``attention``,
    ``scores`` (the router's sigmoid scores), ``moe``, ``dense_layer`` and
    ``moe_layer``."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    eps, theta = c["rms_norm_eps"], c["rope_theta"]
    H, rank = c["num_attention_heads"], c["kv_lora_rank"]
    nope, rope_d, vd = (c["qk_nope_head_dim"], c["qk_rope_head_dim"],
                        c["v_head_dim"])
    k, E = c["num_experts_per_tok"], c["router_experts"]
    first, scaling = c["first_expert"], c["routed_scaling_factor"]

    def mm(spec, a, b):
        return jnp.einsum(spec, _round(a, operand_dtype),
                          _round(b, operand_dtype),
                          precision=lax.Precision.HIGHEST,
                          preferred_element_type=jnp.float32)

    def rms(x, scale):
        var = jnp.mean(x * x, axis=-1, keepdims=True)
        return x * lax.rsqrt(var + eps) * (1.0 + scale)

    def rope(x):                                   # (S, N, r)
        half = x.shape[-1] // 2
        inv = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) / half)
        ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * inv
        cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
        x1, x2 = x[..., 0::2], x[..., 1::2]
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)

    def attention(h, p):
        S = h.shape[0]
        q = mm("sd,dhk->shk", h, p["wq"])
        q_nope, q_pe = q[..., :nope], rope(q[..., nope:])
        kva = mm("sd,dr->sr", h, p["wkv_a"])
        lat = rms(kva[:, :rank], p["kv_norm"]["scale"])
        k_pe = rope(kva[:, None, rank:])[:, 0]
        kv = mm("sr,rhk->shk", lat, p["wkv_b"])
        k_nope, v = kv[..., :nope], kv[..., nope:]
        kpos = jnp.arange(S)[None, :]
        nb = max(1, S // Q_BLOCK)
        size = S // nb

        @jax.checkpoint
        def block(args):
            qn, qp, q0 = args
            scores = (mm("qhn,shn->hqs", qn, k_nope)
                      + mm("qhr,sr->hqs", qp, k_pe)) / jnp.sqrt(
                          float(nope + rope_d))
            qpos = q0 + jnp.arange(size)[:, None]
            scores = jnp.where(kpos <= qpos, scores, -jnp.inf)
            return mm("hqs,shv->qhv", jax.nn.softmax(scores, axis=-1), v)

        out = lax.map(block, (q_nope.reshape(nb, size, H, nope),
                              q_pe.reshape(nb, size, H, rope_d),
                              jnp.arange(nb) * size))
        return mm("shv,hvd->sd", out.reshape(S, H, vd), p["wo"])

    def swiglu(h, gate, up, down):
        return mm("sf,fd->sd", jax.nn.silu(mm("sd,df->sf", h, gate))
                  * mm("sd,df->sf", h, up), down)

    def scores_of(h, p):
        return jax.nn.sigmoid(mm("sd,de->se", h, p["router"]))

    def moe(h, p):
        S = h.shape[0]
        scores = scores_of(h, p)
        _, idx = lax.top_k(scores + lax.stop_gradient(p[BIAS]), k)
        chosen = jnp.take_along_axis(scores, idx, axis=-1)
        w = chosen / (jnp.sum(chosen, -1, keepdims=True) + 1e-20) * scaling
        picked = jax.nn.one_hot(idx, E, dtype=jnp.float32)      # (S, k, E)
        gates = jnp.sum(picked * w[..., None], axis=1)          # (S, E)
        held = p["gate"].shape[0]
        g = mm("sd,edf->sef", h, p["gate"])
        u = mm("sd,edf->sef", h, p["up"])
        act = jax.nn.silu(g) * u * gates[:, first:first + held, None]
        out = mm("sef,efd->sd", act, p["down"])
        out = out + swiglu(h, p["shared_gate"], p["shared_up"],
                           p["shared_down"])
        load = jnp.sum(picked, axis=(0, 1))
        f = load * E / (k * S)
        share = jnp.mean(scores / jnp.sum(scores, -1, keepdims=True), axis=0)
        return out, jnp.sum(f * share), load

    @jax.checkpoint
    def dense_layer(x, lp):
        x = x + attention(rms(x, lp["norm1"]["scale"]), lp["mixer"])
        f = lp["ffn"]
        return x + swiglu(rms(x, lp["norm2"]["scale"]), f["gate"], f["up"],
                          f["down"])

    @jax.checkpoint
    def moe_layer(x, lp):
        x = x + attention(rms(x, lp["norm1"]["scale"]), lp["mixer"])
        out, aux, load = moe(rms(x, lp["norm2"]["scale"]), lp["ffn"])
        return x + out, (aux, load)

    return {"rms": rms, "attention": attention, "scores": scores_of,
            "moe": moe, "dense_layer": dense_layer, "moe_layer": moe_layer,
            "mm": mm}


def build(c: dict, operand_dtype: Optional[str] = None):
    """``row_loss(params, tokens, labels) -> (loss, loads)`` of one
    sequence, the loads (MoE layers, experts)."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    f = _layers(c, operand_dtype)

    def row_loss(params, tokens, labels):
        x = params["embed"]["table"][tokens]
        for lp in params["lead"]:
            x = f["dense_layer"](x, lp)
        x, (aux, load) = lax.scan(f["moe_layer"], x, params["blocks"]["l0"])
        x = f["rms"](x, params["final_norm"]["scale"])
        logits = f["mm"]("sd,dv->sv", x, params["lm_head"]["w"])
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
        return jnp.mean(lse - gold) + c["seq_aux_alpha"] * jnp.sum(aux), load

    return row_loss


BALANCE_ITERS = 200


def balance_router_bias(c: dict):
    """``f(params, tokens) -> params`` whose routers' correction biases
    spread one sequence's tokens evenly over the experts, as the bias rule
    holds them in a job past its start: layer by layer, on that layer's
    scores, the rule ``b_i += eta x sign(mean load - load_i)`` with a step
    that shrinks from 0.02 by 3 % an iteration."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    f = _layers(c)
    k, E = c["num_experts_per_tok"], c["router_experts"]

    def balanced(scores):
        def one(t, b):
            _, idx = lax.top_k(scores + b, k)
            load = jnp.zeros((E,), jnp.float32).at[idx.reshape(-1)].add(1.0)
            return b + 0.02 * 0.97 ** t * jnp.sign(jnp.mean(load) - load)

        return lax.fori_loop(0, BALANCE_ITERS, one, jnp.zeros((E,), jnp.float32))

    def run(params, tokens):
        x = params["embed"]["table"][tokens]
        for lp in params["lead"]:
            x = f["dense_layer"](x, lp)
        blocks = params["blocks"]["l0"]
        biases = []
        for i in range(blocks["ffn"][BIAS].shape[0]):
            lp = jax.tree.map(lambda a: a[i], blocks)
            x = x + f["attention"](f["rms"](x, lp["norm1"]["scale"]),
                                   lp["mixer"])
            h = f["rms"](x, lp["norm2"]["scale"])
            b = balanced(f["scores"](h, lp["ffn"]))
            biases.append(b)
            lp["ffn"] = dict(lp["ffn"], **{BIAS: b})
            x = x + f["moe"](h, lp["ffn"])[0]
        ffn = dict(blocks["ffn"], **{BIAS: jnp.stack(biases)})
        return dict(params, blocks={"l0": dict(blocks, ffn=ffn)})

    return run


def _is_bias(path) -> bool:
    return getattr(path[-1], "key", None) == BIAS


class Reference:
    """Runs ``steps`` steps of the reference and keeps the readings a check
    compares: each step's loss, each leaf's norm of the first gradient as
    the optimizer takes it (after clipping) and raw, of the parameters'
    change over the steps, and the first step's loads (MoE layers x
    experts, flattened)."""

    def __init__(self, c: dict, opt: dict, operand_dtype: Optional[str] = None):
        import jax
        import jax.numpy as jnp

        self.opt = opt
        vg = jax.value_and_grad(build(c, operand_dtype), has_aux=True)
        gamma = c["bias_update_speed"]

        def accumulate(acc, loss_sum, load_sum, params, tokens, labels):
            (loss, load), g = vg(params, tokens, labels)
            return (jax.tree.map(jnp.add, acc, g), loss_sum + loss,
                    load_sum + load)

        self._accumulate = jax.jit(accumulate, donate_argnums=(0, 1, 2))

        def mean(acc, rows):
            return jax.tree.map(lambda a: a / rows, acc)

        self._mean = jax.jit(mean, donate_argnums=(0,))

        def update(params, mu, nu, grads, load, lr, t):
            b1, b2 = opt["b1"], opt["b2"]
            leaves = jax.tree.leaves(grads)
            gnorm = jnp.sqrt(sum(jnp.sum(g * g) for g in leaves))
            clip = jnp.minimum(1.0, opt["grad_clip"] / jnp.maximum(gnorm, 1e-9))
            g = jax.tree.map(lambda x: x * clip, grads)

            def moment(path, m, x, fn):
                return m if _is_bias(path) else fn(m, x)

            mu = jax.tree_util.tree_map_with_path(
                lambda pa, m, x: moment(pa, m, x,
                                        lambda m, x: b1 * m + (1 - b1) * x),
                mu, g)
            nu = jax.tree_util.tree_map_with_path(
                lambda pa, v, x: moment(pa, v, x,
                                        lambda v, x: b2 * v + (1 - b2) * x * x),
                nu, g)

            def step(path, p, m, v):
                if _is_bias(path):
                    return p + gamma * jnp.sign(
                        jnp.mean(load, -1, keepdims=True) - load)
                mhat = m / (1 - b1 ** t)
                vhat = v / (1 - b2 ** t)
                return p - lr * (mhat / (jnp.sqrt(vhat) + opt["eps"])
                                 + opt["weight_decay"] * p)

            return (jax.tree_util.tree_map_with_path(step, params, mu, nu),
                    mu, nu, g)

        self._update = jax.jit(update, donate_argnums=(0, 1, 2, 3))
        self._norms = jax.jit(leaf_norms)
        self._diff_norms = jax.jit(
            lambda a, b: leaf_norms(jax.tree.map(jnp.subtract, a, b)))
        self._E, self._layers = (c["router_experts"],
                                 c["num_hidden_layers"]
                                 - c["first_k_dense_replace"])

    def run(self, make_params: Callable, batches: Sequence) -> Dict[str, list]:
        """``make_params()`` gives the initial parameters on the device;
        ``batches`` are ``(tokens, labels)`` NumPy arrays, one per step."""
        import jax
        import jax.numpy as jnp

        params = make_params()
        # The moments wait on the host while the gradients are taken, so
        # that the chip holds the parameters and two gradient trees beside
        # one sequence's activations, and no more.
        mu = jax.device_get(jax.tree.map(jnp.zeros_like, params))
        nu = jax.device_get(jax.tree.map(jnp.zeros_like, params))
        out: Dict[str, list] = {"loss": []}
        for t, (tokens, labels) in enumerate(batches, start=1):
            acc = jax.tree.map(jnp.zeros_like, params)
            loss_sum = jnp.zeros((), jnp.float32)
            load = jnp.zeros((self._layers, self._E), jnp.float32)
            for row in range(tokens.shape[0]):
                acc, loss_sum, load = self._accumulate(
                    acc, loss_sum, load, params,
                    jnp.asarray(tokens[row], jnp.int32),
                    jnp.asarray(labels[row], jnp.int32))
            rows = tokens.shape[0]
            grads = self._mean(acc, float(rows))
            del acc
            out["loss"].append(float(loss_sum) / rows)
            if t == 1:
                out["grad_raw"] = [float(v) for v in self._norms(grads)]
                out["load"] = [float(v) for v in jnp.ravel(load)]
            params, mu, nu, g = self._update(
                params, jax.device_put(mu), jax.device_put(nu), grads, load,
                _lr(self.opt, t), float(t))
            if t == 1:
                out["grad"] = [float(v) for v in self._norms(g)]
            del g
            mu, nu = jax.device_get((mu, nu))
        del mu, nu
        p0 = make_params()
        out["change"] = [float(v) for v in self._diff_norms(params, p0)]
        del params, p0
        return out
