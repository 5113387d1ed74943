"""Moonlight-16B-A3B's layers at smoke size on the CPU: latent attention, a
leading dense layer, sigmoid routing with a correction bias, the held
experts' dropless share and the bias update, each against the plain
reference (``mla_moe_reference.py``)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mla_moe_reference as ref
from repro.configs.base import DENSE, MOE
from repro.configs.registry import get_config, smoke_config
from repro.models import build_model
from repro.models import moe as moe_mod
from repro.train import OptConfig, adamw_update, init_opt_state, make_train_step
from repro.train.train_step import make_loss_and_grads, update_router_bias

ARCH = "moonlight-16b-a3b"
KEY = jax.random.PRNGKey(0)
B, S = 2, 32


def _cfg(**kw):
    return smoke_config(get_config(ARCH)).replace(dtype="float32", **kw)


def _params(cfg, seed=0):
    """The program's initial tree with the norm scales and the correction
    bias drawn too (both start at zero), so that every leaf matters."""
    params = build_model(cfg).init(jax.random.PRNGKey(seed))
    paths, treedef = jax.tree_util.tree_flatten_with_path(params)
    keys = jax.random.split(jax.random.PRNGKey(seed + 1), len(paths))
    out = []
    for k, (path, leaf) in zip(keys, paths):
        name = jax.tree_util.keystr(path)
        if "scale" in name or "router_bias" in name:
            leaf = leaf + 0.1 * jax.random.normal(k, leaf.shape)
        out.append(leaf)
    return jax.tree.unflatten(treedef, out)


def _batch(cfg, seed=3):
    t = jax.random.randint(jax.random.PRNGKey(seed), (B, S + 1), 0,
                           cfg.vocab_size)
    return {"tokens": t[:, :-1], "labels": t[:, 1:]}


def _leaves(tree):
    return {jax.tree_util.keystr(p): np.asarray(v, np.float64)
            for p, v in jax.tree_util.tree_flatten_with_path(tree)[0]}


def _held(params, held, first):
    """The tree a layer holding experts [first, first + held) keeps."""
    def cut(path, leaf):
        name = jax.tree_util.keystr(path)
        if "blocks" in name and any(f"['{k}']" in name
                                    for k in ("gate", "up", "down")):
            return leaf[:, first:first + held]
        return leaf
    return jax.tree_util.tree_map_with_path(cut, params)


@pytest.mark.parametrize("held,first", [(0, 0), (4, 4), (2, 3)])
def test_loss_grads_and_loads_match_reference(held, first):
    cfg = _cfg(experts_held=held, first_expert=first)
    params = _held(_params(_cfg()), held or cfg.num_experts, first)
    batch = _batch(cfg)
    model = build_model(cfg)
    loss, grads, metrics = jax.jit(make_loss_and_grads(model, 2))(params,
                                                                 batch)
    (ref_loss, ref_loads), ref_grads = jax.value_and_grad(
        ref.loss_and_loads, has_aux=True)(params, cfg, batch["tokens"],
                                          batch["labels"])
    np.testing.assert_allclose(float(loss), float(ref_loss), rtol=2e-6)
    got, want = _leaves(grads), _leaves(ref_grads)
    assert got.keys() == want.keys()
    for name in want:
        scale = max(np.abs(want[name]).max(), 1e-30)
        np.testing.assert_allclose(got[name], want[name], rtol=1e-4,
                                   atol=1e-5 * scale, err_msg=name)
    np.testing.assert_array_equal(
        np.asarray(metrics["moe"]["load"]["blocks"]["l0"]),
        np.asarray(ref_loads["blocks"]["l0"]))
    # the held experts computed exactly the rows routed to them
    load = np.asarray(ref_loads["blocks"]["l0"])
    n = held or cfg.num_experts
    assert int(metrics["moe"]["rows"]) == load[:, first:first + n].sum()
    assert int(metrics["moe"]["groups"]) == 2 * load.shape[0]


def test_eight_shares_add_up_to_the_uncut_layer():
    """Expert parallelism over 8 chips: each share routes over every expert
    and computes its own experts' part; the 8 parts, with the shared
    experts counted once, give the whole layer."""
    cfg = _cfg()
    params = _params(cfg)
    layer = jax.tree.map(lambda a: a[0], params["blocks"]["l0"]["ffn"])
    h = jax.random.normal(KEY, (B, S, cfg.d_model))
    whole, _, _ = ref.moe(h, layer, cfg)
    shared = ref.swiglu(h, layer["shared_gate"], layer["shared_up"],
                        layer["shared_down"])
    per = cfg.num_experts // 8
    parts = []
    for s in range(8):
        share = dict(layer, gate=layer["gate"][s * per:(s + 1) * per],
                     up=layer["up"][s * per:(s + 1) * per],
                     down=layer["down"][s * per:(s + 1) * per])
        out, _, _ = moe_mod.moe_apply_dropless(
            share, h, top_k=cfg.top_k, scaling=cfg.routed_scaling,
            first=s * per, dtype=jnp.float32)
        parts.append(out)
    total = sum(parts) - 7 * shared
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole),
                               rtol=1e-4, atol=1e-5)


def test_no_token_dropped_when_all_route_to_one_held_expert():
    cfg = _cfg(experts_held=2, first_expert=0)
    layer = jax.tree.map(lambda a: a[0],
                         _held(_params(_cfg()), 2, 0)["blocks"]["l0"]["ffn"])
    layer["router_bias"] = jnp.zeros_like(layer["router_bias"]).at[0].set(10.0)
    h = jax.random.normal(KEY, (B, S, cfg.d_model))
    out, _, stats = moe_mod.moe_apply_dropless(
        layer, h, top_k=cfg.top_k, scaling=cfg.routed_scaling, first=0,
        dtype=jnp.float32)
    want, _, load = ref.moe(h, layer, cfg)
    assert float(load[0]) == B * S                    # every token chose 0
    assert int(stats["rows_max"]) == B * S            # and none was dropped
    assert int(stats["rows"]) == int(load[0] + load[1])
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), rtol=1e-4,
                               atol=1e-5)


def test_bias_update_rule():
    params = {"blocks": {"l0": {"ffn": {"router_bias": jnp.zeros((2, 4))}}},
              "tail": []}
    load = {"blocks": {"l0": jnp.asarray([[1.0, 2.0, 3.0, 2.0],
                                          [4.0, 4.0, 4.0, 4.0]])}}
    new = update_router_bias(params, load, 0.5)
    np.testing.assert_array_equal(
        np.asarray(new["blocks"]["l0"]["ffn"]["router_bias"]),
        [[0.5, 0.0, -0.5, 0.0], [0.0, 0.0, 0.0, 0.0]])


def test_adamw_leaves_the_bias_alone():
    params = {"w": jnp.ones((3,)), "ffn": {"router_bias": jnp.ones((3,))}}
    grads = jax.tree.map(jnp.ones_like, params)
    new, state, _ = adamw_update(grads, init_opt_state(params), params,
                                 OptConfig(warmup_steps=1, weight_decay=0.1))
    np.testing.assert_array_equal(np.asarray(new["ffn"]["router_bias"]), 1.0)
    np.testing.assert_array_equal(np.asarray(state["mu"]["ffn"]["router_bias"]),
                                  0.0)
    assert float(jnp.abs(new["w"] - 1.0).max()) > 0


def test_train_step_moves_the_bias_by_the_step_loads():
    cfg = _cfg()
    params, batch = _params(cfg), _batch(cfg)
    step = jax.jit(make_train_step(build_model(cfg), OptConfig(),
                                   num_microbatches=2))
    new, opt, metrics = step(params, init_opt_state(params), batch)
    _, ref_loads = ref.loss_and_loads(params, cfg, batch["tokens"],
                                      batch["labels"])
    load = np.asarray(ref_loads["blocks"]["l0"])
    b0 = np.asarray(params["blocks"]["l0"]["ffn"]["router_bias"])
    want = b0 + cfg.bias_update_rate * np.sign(
        load.mean(-1, keepdims=True) - load)
    np.testing.assert_allclose(
        np.asarray(new["blocks"]["l0"]["ffn"]["router_bias"]), want,
        rtol=0, atol=1e-7)
    assert not np.asarray(opt["mu"]["blocks"]["l0"]["ffn"]["router_bias"]).any()
    np.testing.assert_array_equal(
        np.asarray(metrics["moe"]["load"]["blocks"]["l0"]), load)


def test_dense_layer_runs_before_the_scanned_blocks():
    cfg = get_config(ARCH)
    assert cfg.layer_schedule()[0].ffn == DENSE
    assert all(s.ffn == MOE for s in cfg.layer_schedule()[1:])
    assert cfg.scan_split()[1:] == (26, ())
    params = build_model(cfg).abstract_params()
    assert params["lead"][0]["ffn"]["gate"].shape == (2048, 11264)
    assert params["blocks"]["l0"]["ffn"]["gate"].shape == (26, 64, 2048, 1408)
    assert "lead" not in build_model(get_config("phi4-mini-3.8b")
                                     ).abstract_params()
    # the lead layer's output feeds the blocks: perturbing it moves the loss
    small = _cfg()
    params, batch = _params(small), _batch(small)
    loss = build_model(small).loss
    bumped = jax.tree.map(lambda a: a, params)
    bumped["lead"][0]["ffn"]["down"] = params["lead"][0]["ffn"]["down"] * 2
    assert float(loss(params, batch)[0]) != float(loss(bumped, batch)[0])


def test_published_sizes_and_parameter_counts():
    cfg = get_config(ARCH)
    n = cfg.param_counts()["total"]
    assert 15.9e9 < n < 16.1e9, n            # "16B"
    cut = cfg.replace(num_layers=5, experts_held=8, vocab_size=20480)
    assert abs(cut.param_counts()["total"] - 568.5e6) < 0.1e6


def test_mla_decode_matches_the_full_forward():
    cfg = _cfg()
    model = build_model(cfg)
    params = _params(cfg)
    T = 10
    tokens = jax.random.randint(KEY, (B, T), 0, cfg.vocab_size)
    full = model._m.forward_logits(params, cfg, {"tokens": tokens},
                                   last_only=False)
    state = model.init_decode_state(params, B, 16)
    assert state.lead[0].c.shape == (B, 16, cfg.kv_lora_rank)
    decode = jax.jit(model.decode)
    out = []
    for t in range(T):
        lg, state = decode(params, state, {"tokens": tokens[:, t:t + 1]})
        out.append(lg[:, 0])
    np.testing.assert_allclose(np.asarray(jnp.stack(out, 1)),
                               np.asarray(full), rtol=2e-4, atol=2e-4)


def test_launch_train_smoke_saves_and_restores_the_bias(tmp_path, capsys):
    """``launch/train.py --arch moonlight-16b-a3b --smoke`` through the CkIO
    pipeline; its checkpoint brings the moved correction bias back, and a
    resumed run starts from it."""
    from repro.launch import train as launch
    from repro.train import AsyncCheckpointer, restore_tree

    argv = ["--arch", ARCH, "--smoke", "--steps", "4", "--global-batch", "2",
            "--seq", "32", "--microbatches", "2", "--num-readers", "2",
            "--data", str(tmp_path / "corpus.bin"),
            "--ckpt-dir", str(tmp_path / "ckpt"), "--ckpt-every", "2"]
    cfg = smoke_config(get_config(ARCH))
    out = launch.run(cfg, launch.parse_args(argv))
    assert out["steps"] == 4 and np.isfinite(out["final_loss"])

    ck = AsyncCheckpointer(str(tmp_path / "ckpt"))
    try:
        params = build_model(cfg).init(KEY)
        like = {"params": params, "opt": init_opt_state(params)}
        state, step = restore_tree(ck.latest(), like)
    finally:
        ck.shutdown()
    assert step == 4
    bias = np.asarray(state["params"]["blocks"]["l0"]["ffn"]["router_bias"])
    moves = bias / cfg.bias_update_rate
    assert np.abs(moves).max() > 0                     # moved from zero...
    np.testing.assert_allclose(moves, np.round(moves), atol=1e-3)  # by the rule
    assert not np.asarray(state["opt"]["mu"]["blocks"]["l0"]["ffn"]
                          ["router_bias"]).any()

    capsys.readouterr()
    argv[argv.index("--steps") + 1] = "6"
    out = launch.run(cfg, launch.parse_args(argv + ["--resume"]))
    assert "resumed from step 4" in capsys.readouterr().out
    assert out["steps"] == 2


def test_expert_rows_are_counted_while_traced(tmp_path):
    from repro.core import tracing
    from repro.train import count_step

    cfg = _cfg()
    params, batch = _params(cfg), _batch(cfg)
    step = jax.jit(make_train_step(build_model(cfg), OptConfig(),
                                   num_microbatches=2))
    _, _, metrics = step(params, init_opt_state(params), batch)
    count_step(metrics)                       # no trace: nothing recorded
    assert "moe.routed_rows" not in tracing.snapshot().counters
    tracing.reset()
    jax.profiler.start_trace(str(tmp_path))
    try:
        count_step(metrics)
    finally:
        jax.profiler.stop_trace()
    got = tracing.snapshot().counters
    tracing.reset()
    m = metrics["moe"]
    assert got["moe.routed_rows"] == int(m["rows"]) == 2 * B * S * cfg.top_k
    assert got["moe.expert_rows_max"] == int(m["rows_max"])
    assert got["moe.expert_row_groups"] == 2 * 2    # 2 layers x 2 microbatches
