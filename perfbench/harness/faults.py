"""Faults planted under a run, for the checks that must catch them: each
wraps one of the program's functions (``make_train_step``,
``CkIOPipeline.get_batch_device``, ``restore_sharded``) and breaks what it
produces."""
from __future__ import annotations


def state_unchanged(make_train_step):
    """A step that computes its loss and returns its state unchanged."""
    def make(model, opt_cfg, **kw):
        import jax
        import jax.numpy as jnp

        inner = make_train_step(model, opt_cfg, **kw)

        def step(params, opt, batch):
            _, _, metrics = inner(params, opt, batch)
            return (jax.tree.map(jnp.copy, params),
                    jax.tree.map(jnp.copy, opt), metrics)

        return step

    return make


def half_batch(make_train_step):
    """A step that leaves out half of the batch's rows and takes the mean
    over the rest."""
    def make(model, opt_cfg, *, num_microbatches=1, **kw):
        inner = make_train_step(model, opt_cfg,
                                num_microbatches=max(1, num_microbatches // 2),
                                **kw)

        def step(params, opt, batch):
            return inner(params, opt, {k: v[: v.shape[0] // 2]
                                       for k, v in batch.items()})

        return step

    return make


def token_altered(get_batch_device):
    """A batch whose first input token is changed where it is produced."""
    def fetch(self, step, *args, **kw):
        x, y = get_batch_device(self, step, *args, **kw)
        return x.at[0, 0].set(x[0, 0] + 1), y

    return fetch


def leaf_altered(restore_sharded):
    """A resume whose first restored leaf has one word changed."""
    def restore(path, like, shardings, **kw):
        import jax

        tree, step = restore_sharded(path, like, shardings, **kw)
        leaves, treedef = jax.tree.flatten(tree)
        leaves[0] = leaves[0].at[(0,) * leaves[0].ndim].add(1)
        return jax.tree.unflatten(treedef, leaves), step

    return restore


def leaf_left_out(restore_sharded):
    """A resume that leaves its last leaf at zero instead of restoring it."""
    def restore(path, like, shardings, **kw):
        import jax
        import jax.numpy as jnp

        tree, step = restore_sharded(path, like, shardings, **kw)
        leaves, treedef = jax.tree.flatten(tree)
        leaves[-1] = jnp.zeros_like(leaves[-1])
        return jax.tree.unflatten(treedef, leaves), step

    return restore


def rounded(restore_sharded, dtype: str = "bfloat16"):
    """The control: every float leaf restored at the next precision down
    (rounded to ``dtype`` and back)."""
    def restore(path, like, shardings, **kw):
        import jax
        import jax.numpy as jnp

        tree, step = restore_sharded(path, like, shardings, **kw)
        return jax.tree.map(
            lambda x: x.astype(dtype).astype(x.dtype)
            if jnp.issubdtype(x.dtype, jnp.floating) else x, tree), step

    return restore
