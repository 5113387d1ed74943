"""Optimizer, gradient compression, checkpointing, fault supervisor."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import get_config, smoke_config
from repro.models import build_model
from repro.train import (
    AsyncCheckpointer,
    FaultInjected,
    OptConfig,
    StepSupervisor,
    adamw_update,
    grad_compress,
    init_opt_state,
    lr_at,
    make_train_step,
    restore_tree,
    save_checkpoint,
)

KEY = jax.random.PRNGKey(0)


def test_lr_schedule():
    cfg = OptConfig(peak_lr=1.0, warmup_steps=10, decay_steps=110,
                    min_lr_ratio=0.1)
    assert float(lr_at(cfg, jnp.asarray(0))) == 0.0
    assert float(lr_at(cfg, jnp.asarray(10))) == pytest.approx(1.0)
    assert float(lr_at(cfg, jnp.asarray(110))) == pytest.approx(0.1, abs=1e-3)


def test_adamw_reduces_quadratic():
    params = {"w": jnp.asarray([3.0, -2.0])}
    state = init_opt_state(params)
    cfg = OptConfig(peak_lr=0.1, warmup_steps=1, decay_steps=200,
                    weight_decay=0.0)
    for _ in range(150):
        g = {"w": 2 * params["w"]}
        params, state, _ = adamw_update(g, state, params, cfg)
    assert float(jnp.abs(params["w"]).max()) < 0.15


def test_master_weights_beat_bf16_drift():
    """With bf16 params, master weights must accumulate small updates that
    plain bf16 params would lose to rounding."""
    p0 = jnp.full((8,), 100.0, jnp.bfloat16)
    cfg = OptConfig(peak_lr=1e-3, warmup_steps=1, decay_steps=10**6,
                    weight_decay=0.0, grad_clip=0)
    pm, sm = {"w": p0}, init_opt_state({"w": p0}, master_weights=True)
    pn, sn = {"w": p0}, init_opt_state({"w": p0})
    for _ in range(50):
        g = {"w": jnp.ones((8,), jnp.float32)}
        pm, sm, _ = adamw_update(g, sm, pm, cfg)
        pn, sn, _ = adamw_update(g, sn, pn, cfg)
    drift_master = float(jnp.abs(sm["master"]["w"] - 100.0).mean())
    assert drift_master > 0.04          # master accumulated ~50 * 1e-3
    assert np.isfinite(np.asarray(pm["w"], np.float32)).all()


def test_int8_error_feedback_bounded():
    g = {"a": jax.random.normal(KEY, (256,)) * 0.1}
    ef = grad_compress.init_ef_state(g)
    total_applied = jnp.zeros((256,))
    for i in range(20):
        q, deq, ef = grad_compress.ef_compress(g, ef)
        total_applied = total_applied + deq["a"]
    # error feedback: accumulated applied updates track accumulated true grads
    err = float(jnp.abs(total_applied - 20 * g["a"]).max())
    scale = float(jnp.abs(g["a"]).max())
    assert err < scale, f"EF residual unbounded: {err} vs {scale}"


def test_compressed_bytes_accounting():
    g = {"a": jnp.zeros((100,)), "b": jnp.zeros((10, 10))}
    assert grad_compress.compressed_bytes(g, "fp32") == 800
    assert grad_compress.compressed_bytes(g, "bf16") == 400
    assert grad_compress.compressed_bytes(g, "int8") == 208


def test_microbatching_matches_full_batch():
    cfg = smoke_config(get_config("phi4-mini-3.8b")).replace(dtype="float32",
                                                             remat_policy="none")
    model = build_model(cfg)
    params = model.init(KEY)
    tokens = jax.random.randint(KEY, (4, 33), 0, cfg.vocab_size)
    batch = {"tokens": tokens[:, :-1], "labels": tokens[:, 1:]}
    from repro.train.train_step import make_loss_and_grads

    loss1, g1, _ = make_loss_and_grads(model, 1)(params, batch)
    for nmb in (2, 4):
        lossn, gn, _ = make_loss_and_grads(model, nmb)(params, batch)
        assert float(loss1) == pytest.approx(float(lossn), rel=1e-5)
        for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(gn)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-5, rtol=1e-4)


def test_checkpoint_roundtrip_and_ckio_restore(tmp_path):
    tree = {
        "a": jnp.arange(1000, dtype=jnp.float32).reshape(10, 100),
        "nested": {"b": jnp.ones((7,), jnp.bfloat16),
                   "c": jnp.asarray(3, jnp.int32)},
    }
    path = str(tmp_path / "t.ckpt")
    save_checkpoint(path, tree, step=42)
    for use_ckio in (False, True):
        restored, step = restore_tree(path, tree, use_ckio=use_ckio)
        assert step == 42
        for x, y in zip(jax.tree.leaves(tree), jax.tree.leaves(restored)):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_elastic_restore_sharded(tmp_path):
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.train import restore_sharded

    mesh = jax.make_mesh((1,), ("data",))
    tree = {"w": jnp.arange(64, dtype=jnp.float32).reshape(8, 8)}
    path = str(tmp_path / "e.ckpt")
    save_checkpoint(path, tree, step=1)
    shardings = {"w": NamedSharding(mesh, P("data", None))}
    restored, step = restore_sharded(path, tree, shardings)
    assert step == 1
    np.testing.assert_array_equal(np.asarray(restored["w"]),
                                  np.asarray(tree["w"]))
    assert restored["w"].sharding == shardings["w"]


LEAF_CASES = {
    "float32": lambda: jnp.linspace(-3, 3, 96, dtype=jnp.float32).reshape(8, 12),
    "bfloat16": lambda: jnp.linspace(-2, 2, 33).astype(jnp.bfloat16),
    "int32": lambda: jnp.arange(-40, 40, 3, dtype=jnp.int32).reshape(3, 9),
    "scalar": lambda: jnp.asarray(-7.25, jnp.float32),
    "zero_size": lambda: jnp.zeros((0, 5), jnp.float32),
}


def assert_same_bits(saved, restored):
    for x, y in zip(jax.tree.leaves(saved), jax.tree.leaves(restored)):
        x = np.asarray(x)
        assert (y.dtype, y.shape) == (x.dtype, x.shape)
        assert np.asarray(y).tobytes() == x.tobytes()


def arena_of(leaf):
    """The buffer a CkIO-restored leaf is a view of: the object under the
    memoryview at the end of its ``.base`` chain."""
    base = leaf
    while not isinstance(base, memoryview):
        base = base.base
    return base.obj


@pytest.mark.parametrize("use_ckio", [False, True], ids=["plain", "ckio"])
@pytest.mark.parametrize("case", list(LEAF_CASES))
def test_restored_leaf_is_bit_identical(tmp_path, case, use_ckio):
    # "leaf" sorts after "head", so the case's leaf is the last in the file
    # (a zero-size last leaf must not cut the file short).
    tree = {"head": jnp.arange(37, dtype=jnp.float32),
            "leaf": LEAF_CASES[case]()}
    path = str(tmp_path / "c.ckpt")
    save_checkpoint(path, tree, step=5)
    restored, step = restore_tree(path, tree, use_ckio=use_ckio,
                                  num_readers=2, num_pes=2)
    assert step == 5
    assert_same_bits(tree, restored)


def test_view_leaves_outlive_their_session_and_the_next_restore(tmp_path):
    import gc

    def state(seed):
        k = jax.random.split(jax.random.PRNGKey(seed), 2)
        return {"a": jax.random.normal(k[0], (256, 64)),
                "b": jax.random.randint(k[1], (4096,), 0, 1 << 30)}

    first, second = state(1), state(2)
    save_checkpoint(str(tmp_path / "1.ckpt"), first, step=1)
    save_checkpoint(str(tmp_path / "2.ckpt"), second, step=2)
    kept, _ = restore_tree(str(tmp_path / "1.ckpt"), first,
                           num_readers=2, num_pes=2)
    gc.collect()                    # the session and its readers are gone
    assert_same_bits(first, kept)
    # A second restore of the same size in the same process: were the first
    # arena released or recycled under the kept leaves, they would change.
    again, _ = restore_tree(str(tmp_path / "2.ckpt"), second,
                            num_readers=2, num_pes=2)
    assert_same_bits(second, again)
    assert_same_bits(first, kept)
    assert not np.shares_memory(kept["a"], again["a"])
    del again
    gc.collect()
    assert_same_bits(first, kept)


def test_ckio_leaves_are_read_only_views_of_one_arena(tmp_path):
    tree = {name: make() for name, make in LEAF_CASES.items()}
    path = str(tmp_path / "v.ckpt")
    save_checkpoint(path, tree, step=3)
    restored, _ = restore_tree(path, tree, num_readers=2, num_pes=2)
    leaves = jax.tree.leaves(restored)
    assert not any(leaf.flags.writeable for leaf in leaves)
    with pytest.raises(ValueError):
        restored["float32"][0, 0] = 1.0
    arena = arena_of(leaves[0])
    assert all(arena_of(leaf) is arena for leaf in leaves)
    assert all(np.shares_memory(leaf, arena) for leaf in leaves if leaf.size)
    # The plain path gives each leaf a writable array of its own.
    plain, _ = restore_tree(path, tree, use_ckio=False)
    assert all(leaf.flags.writeable for leaf in jax.tree.leaves(plain))


def test_restore_sharded_of_view_leaves_on_one_device_mesh(tmp_path):
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.train import restore_sharded

    mesh = jax.make_mesh((1,), ("data",))
    tree = {name: make() for name, make in LEAF_CASES.items()}
    path = str(tmp_path / "s.ckpt")
    save_checkpoint(path, tree, step=9)
    shardings = {name: NamedSharding(mesh, P()) for name in tree}
    shardings["float32"] = NamedSharding(mesh, P("data", None))
    restored, step = restore_sharded(path, tree, shardings,
                                     num_readers=2, num_pes=2)
    assert step == 9
    assert_same_bits(tree, restored)
    for name, leaf in restored.items():
        assert leaf.sharding == shardings[name]


def test_supervisor_recovers_from_faults(tmp_path):
    cfg = smoke_config(get_config("qwen2-vl-2b"))
    model = build_model(cfg)
    params = model.init(KEY)
    opt = init_opt_state(params)
    step_jit = jax.jit(make_train_step(model, OptConfig(peak_lr=1e-3,
                                                        warmup_steps=1,
                                                        decay_steps=50)))

    def step_fn(state, batch):
        p, o, m = step_jit(state["params"], state["opt"], batch)
        return {"params": p, "opt": o}, m

    def batch_for(s):
        k = jax.random.PRNGKey(s)
        t = jax.random.randint(k, (2, 17), 0, cfg.vocab_size)
        return {"tokens": t[:, :-1], "labels": t[:, 1:]}

    ck = AsyncCheckpointer(str(tmp_path / "ckpts"), keep=2)
    boom = {"left": 2}

    def fault_hook(step):
        if step == 5 and boom["left"] > 0:
            boom["left"] -= 1
            raise FaultInjected("node died")

    sup = StepSupervisor(step_fn, ck, ckpt_every=3, max_retries=3)
    state = sup.run({"params": params, "opt": opt}, batch_for, 8,
                    fault_hook=fault_hook)
    assert sup.stats.failures == 2
    assert sup.stats.restores == 2
    assert int(jax.device_get(state["opt"]["step"])) >= 8
    ck.shutdown()


def test_supervisor_gives_up_after_max_retries(tmp_path):
    ck = AsyncCheckpointer(str(tmp_path / "c2"), keep=1)

    def step_fn(state, batch):
        return state, {}

    def always_fail(step):
        raise FaultInjected("persistent failure")

    sup = StepSupervisor(step_fn, ck, ckpt_every=1, max_retries=2)
    with pytest.raises(RuntimeError, match="retries exhausted"):
        sup.run({"x": jnp.zeros(())}, lambda s: None, 3,
                fault_hook=always_fail)
    ck.shutdown()


def test_checkpoint_alignment_edge_cases(tmp_path):
    """Regression: (a) a final leaf ending exactly on the 128-byte alignment
    boundary must not be clobbered by tail padding; (b) a misaligned final
    leaf must still be fully readable through a CkIO session (no EOF)."""
    aligned = {"w": jnp.arange(64, dtype=jnp.float32)}        # 256 B = 2*128
    odd = {"w": jnp.arange(64, dtype=jnp.float32),
           "c": jnp.asarray(7, jnp.int32)}                     # 4 B tail
    for i, tree in enumerate((aligned, odd)):
        path = str(tmp_path / f"edge{i}.ckpt")
        save_checkpoint(path, tree, step=i)
        for use_ckio in (False, True):
            restored, step = restore_tree(path, tree, use_ckio=use_ckio)
            assert step == i
            for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(restored)):
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
