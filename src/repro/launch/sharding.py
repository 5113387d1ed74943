"""Per-architecture sharding rules (DP/TP/EP/ZeRO-1 over the production mesh).

Conventions:
  * ``model`` axis: tensor/expert parallelism — vocab, heads, d_ff, experts,
    d_inner, lru_width.
  * ``data`` (+ ``pod``) axes: batch data parallelism; ZeRO-1 additionally
    shards optimizer moments over ``data`` on each param's largest
    still-unsharded divisible dim.
  * dims are sharded over an axis only when divisible OR at least 2× the
    axis size (GSPMD pads; the padding waste is called out per arch in
    EXPERIMENTS.md §Roofline — phi3's 40/10 heads, qwen2-moe's 60 experts,
    whisper's 51865 vocab).
  * kv-head dims smaller than the axis (qwen2-vl kv=2, phi3 kv=10,
    recurrentgemma kv=1) stay replicated.

Specs are built from *abstract* trees (eval_shape) — no allocation — and
keyed off leaf path names, mirroring how MaxText-style logical axis rules
work but without a separate annotation pass.
"""
from __future__ import annotations

from typing import Any, Optional, Tuple

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.launch.mesh import mesh_axis_sizes

BATCH_AXES = ("pod", "data")


def _batch_axes(mesh) -> Tuple[str, ...]:
    return tuple(a for a in BATCH_AXES if a in mesh.axis_names)


def _batch_size(mesh) -> int:
    sizes = mesh_axis_sizes(mesh)
    n = 1
    for a in _batch_axes(mesh):
        n *= sizes[a]
    return n


def _maybe(dim: int, axis: str, axis_size: int) -> Optional[str]:
    """Shard ``dim`` over ``axis`` only when divisible — jit argument
    shardings must divide evenly (unlike intermediate constraints)."""
    if dim >= axis_size and dim % axis_size == 0:
        return axis
    return None


def param_leaf_spec(path: str, shape: Tuple[int, ...], mesh) -> P:
    m = mesh_axis_sizes(mesh).get("model", 1)
    stacked = any(
        f"['{k}']" in path for k in ("blocks", "enc_blocks", "dec_blocks")
    )
    nd = len(shape) - (1 if stacked else 0)
    trail = shape[len(shape) - nd:]
    name = path.rsplit("['", 1)[-1].rstrip("']")

    def spec(*axes) -> P:
        axes = tuple(axes)
        assert len(axes) == nd, (path, shape, axes)
        return P(*((None,) + axes)) if stacked else P(*axes)

    # embeddings / unembedding
    if name == "table":
        v = _maybe(trail[0], "model", m)
        if v:
            return spec(v, None)
        # odd vocab (whisper 51865): replicate — sharding d_model instead
        # breaks the SPMD gather partitioner on the 3-axis multi-pod mesh
        return spec(None, None)
    if path.endswith("['lm_head']['w']"):
        return spec(None, _maybe(trail[1], "model", m))
    if name in ("enc_pos", "dec_pos"):
        return spec(None, None)

    # attention — shard heads when divisible, else fall back to head_dim
    # (phi3's 40/10 heads, qwen2-vl's kv=2, recurrentgemma's kv=1)
    if name in ("wq", "wk", "wv") and nd == 3:
        h = _maybe(trail[1], "model", m)
        if h:
            return spec(None, h, None)
        return spec(None, None, _maybe(trail[2], "model", m))
    if name == "wo" and nd == 3:
        h = _maybe(trail[0], "model", m)
        if h:
            return spec(h, None, None)
        return spec(None, _maybe(trail[1], "model", m), None)
    if name in ("bq", "bk", "bv"):
        h = _maybe(trail[0], "model", m)
        if h:
            return spec(h, None)
        return spec(None, _maybe(trail[1], "model", m))

    # MLA: the latent projection is split into c and k_pe, keep it whole;
    # the latent's up-projection is per head
    if name == "wkv_a":
        return spec(None, None)
    if name == "wkv_b":
        return spec(None, _maybe(trail[1], "model", m), None)

    # MoE experts (3-D) before dense GLU (2-D)
    if name in ("gate", "up", "down") and nd == 3:
        return spec(_maybe(trail[0], "model", m), None, None)
    if name in ("gate", "up", "shared_gate", "shared_up", "fc1") and nd == 2:
        return spec(None, _maybe(trail[1], "model", m))
    if name in ("down", "shared_down", "fc2") and nd == 2:
        return spec(_maybe(trail[0], "model", m), None)
    if name == "fc1_b":
        return spec(_maybe(trail[0], "model", m))
    if name == "router":
        return spec(None, None)
    if name == "router_bias":
        return spec(None)

    # mamba
    if name == "in_proj":
        return spec(None, _maybe(trail[1], "model", m))
    if name == "x_proj":
        return spec(_maybe(trail[0], "model", m), None)
    if name == "dt_proj":
        return spec(None, _maybe(trail[1], "model", m))
    if name in ("dt_bias", "D", "conv_b"):
        return spec(_maybe(trail[0], "model", m))
    if name == "A_log":
        return spec(_maybe(trail[0], "model", m), None)
    if name == "conv_w":
        return spec(None, _maybe(trail[1], "model", m))
    if name == "out_proj":
        return spec(_maybe(trail[0], "model", m), None)

    # rg-lru
    if name in ("wx", "wy"):
        return spec(None, _maybe(trail[1], "model", m))
    if name in ("w_r", "w_i"):
        return spec(None, _maybe(trail[1], "model", m))
    if name in ("b_r", "b_i", "lam"):
        return spec(_maybe(trail[0], "model", m))
    if name == "wo" and nd == 2:   # rg-lru out projection (w, d)
        return spec(_maybe(trail[0], "model", m), None)

    # norms, scalars, everything small: replicate
    return spec(*([None] * nd))


def param_specs(abstract_params: Any, mesh) -> Any:
    def one(path, leaf):
        return param_leaf_spec(jax.tree_util.keystr(path), leaf.shape, mesh)

    return jax.tree_util.tree_map_with_path(one, abstract_params)


def zero1_specs(abstract_params: Any, p_specs: Any, mesh) -> Any:
    """Moment sharding: param spec + 'data' on the largest free divisible dim."""
    d = mesh_axis_sizes(mesh).get("data", 1)

    def one(leaf, spec: P) -> P:
        parts = list(spec) + [None] * (len(leaf.shape) - len(spec))
        best, best_size = None, 0
        for i, (dim, s) in enumerate(zip(leaf.shape, parts)):
            if s is None and dim % d == 0 and dim > best_size and dim >= d:
                best, best_size = i, dim
            elif s == "data":
                return P(*parts)
        if best is not None:
            parts[best] = "data"
        return P(*parts)

    return jax.tree.map(one, abstract_params, p_specs,
                        is_leaf=lambda x: isinstance(x, P))


def opt_state_specs(abstract_params: Any, p_specs: Any, mesh,
                    master_weights: bool = False) -> Any:
    z = zero1_specs(abstract_params, p_specs, mesh)
    out = {"mu": z, "nu": z, "step": P()}
    if master_weights:
        out["master"] = z
    return out


def batch_specs(abstract_batch: Any, mesh) -> Any:
    baxes = _batch_axes(mesh)
    bsize = _batch_size(mesh)

    def one(leaf):
        if leaf.shape and leaf.shape[0] % bsize == 0 and leaf.shape[0] > 0:
            return P(baxes, *([None] * (len(leaf.shape) - 1)))
        return P(*([None] * len(leaf.shape)))

    return jax.tree.map(one, abstract_batch)


def decode_state_specs(abstract_state: Any, mesh, cfg=None) -> Any:
    """KV caches: batch over data axes, kv-heads over model when divisible;
    SSM/LRU states: batch over data, channel dim over model."""
    m = mesh_axis_sizes(mesh).get("model", 1)
    baxes = _batch_axes(mesh)
    bsize = _batch_size(mesh)

    def one(path, leaf):
        path_s = jax.tree_util.keystr(path)
        shape = leaf.shape
        # stacked-over-blocks states have a leading nb dim inside 'blocks'
        stacked = "blocks" in path_s or "self_caches" in path_s or "cross_kv" in path_s
        lead = (None,) if stacked else ()
        nd = len(shape) - len(lead)
        tshape = shape[len(lead):]
        if nd == 0:
            return P(*lead)
        parts = [None] * nd
        if tshape[0] % bsize == 0 and tshape[0] >= bsize:
            parts[0] = baxes
        if nd == 4:                      # (B, C, K, hd) kv cache
            kvh = _maybe(tshape[2], "model", m)
            if kvh:
                parts[2] = kvh
            else:                        # MQA-ish: shard head_dim instead
                parts[3] = _maybe(tshape[3], "model", m)
        elif nd == 3:                    # (B, di, n) ssm or (B, cw-1, di) conv
            if tshape[1] % m == 0 and tshape[1] >= 2 * m:
                parts[1] = "model"       # (B, di, n)
            elif tshape[2] % m == 0 and tshape[2] >= 2 * m:
                parts[2] = "model"       # (B, cw-1, di)
        elif nd == 2 and tshape[1] % m == 0 and tshape[1] >= 2 * m:
            parts[1] = "model"           # (B, w) lru state
        return P(*(lead + tuple(parts)))

    return jax.tree_util.tree_map_with_path(one, abstract_state)


def logits_spec(mesh, batch_size: int = 0, vocab: int = 0) -> P:
    b = _batch_axes(mesh)
    if len(b) == 1:
        b = b[0]                       # canonical bare-axis form ("data",) -> "data"
    if batch_size and batch_size % _batch_size(mesh) != 0:
        b = None                       # e.g. long_500k batch=1
    m = mesh_axis_sizes(mesh).get("model", 1)
    v = "model" if (not vocab or vocab % m == 0) else None  # whisper vocab 51865
    return P(b, None, v)


def to_named(spec_tree: Any, mesh) -> Any:
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s), spec_tree,
        is_leaf=lambda x: isinstance(x, P),
    )
