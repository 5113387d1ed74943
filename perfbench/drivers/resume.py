"""Warm checkpoint resume through the program's own path.

Set-up makes a full train state (f32 parameters, both Adam moments and the
step) on the device from the seed, compiles the fingerprint on it, and
writes it with the program's ``train/checkpoint.save_checkpoint`` into one
packed file under the checkout; the write leaves the file in the page cache
(checked with mincore, and read once where it is not). The window then calls
``train/checkpoint.restore_sharded`` back to back, each resume timed from
the call until every leaf is resident on its device; the state a resume
restored is deleted before the next one starts. The traced run makes the
two calls that ``restore_sharded`` composes (``restore_tree``, then one
``device_put`` per leaf) one after the other, so that each carries a span.

After the window every resume's leaves are checked by fingerprint, and the
last resume's leaves word by word, against the state made again from the
seed one leaf at a time on the device.

Traffic parameters (``traffic/<mix>.json``): ``state_step`` (the step stored
in the state), ``num_readers`` (null: the program's own choice),
``num_pes``, ``trace_resumes``."""
from __future__ import annotations

import os
import time

from harness import checks, device, fingerprint, pagecache, program, state
from harness.compiles import CompileCounter
from harness.main import Outcome


def free(tree) -> None:
    import jax

    for leaf in jax.tree.leaves(tree):
        if isinstance(leaf, jax.Array) and not leaf.is_deleted():
            leaf.delete()


def leaf_paths(tree):
    import jax

    return [tuple(k.key for k in p)
            for p, _ in jax.tree_util.tree_flatten_with_path(tree)[0]]


class Resumer:
    """The checkpoint of one seed's state, and the program's calls that
    bring it back onto the devices."""

    def __init__(self, config: dict, traffic: dict, devices, workdir: str):
        import jax
        from jax.sharding import SingleDeviceSharding

        from repro import train as program_train
        from repro.models import build_model

        self.tr = traffic
        model = build_model(program.model_config(config))
        params = model.abstract_params()
        self.like = {"params": params,
                     "opt": jax.eval_shape(program_train.init_opt_state,
                                           params)}
        self.one = SingleDeviceSharding(devices[0])
        self.shardings = jax.tree.map(lambda _: self.one, self.like)
        self.leaves = state.train_state_leaves(config, traffic["state_step"])
        self.path = os.path.join(workdir, "state.ckpt")
        self.kw = {"num_readers": traffic["num_readers"],
                   "num_pes": traffic["num_pes"]}
        self.fingerprint = jax.jit(fingerprint.tree_fingerprint)
        self.nbytes = 0

    def write(self, seed: int) -> None:
        """The seed's state, made on the device (where the fingerprint is
        compiled on it) and written to the checkpoint file."""
        import numpy as np

        from repro.train import checkpoint

        # Placed as a resume places it, so that the fingerprint compiled on
        # it serves the restored states too.
        made = state.maker(self.leaves, out_shardings=self.one)(
            state.base_key(seed))
        program.check_tree(made, self.like)
        np.asarray(self.fingerprint(made))
        manifest = checkpoint.save_checkpoint(self.path, made,
                                              self.tr["state_step"])
        free(made)
        self.nbytes = sum(e["nbytes"] for e in manifest["leaves"])

    def warm(self) -> float:
        """Fraction of the file resident in the page cache (mincore), after
        one sequential read where the write left less than all of it."""
        resident = pagecache.residency(self.path)
        if resident is None or resident < 1.0:
            with open(self.path, "rb", buffering=0) as f:
                while f.read(64 << 20):
                    pass
            resident = pagecache.residency(self.path)
        return -1.0 if resident is None else resident

    def resume(self):
        """``restore_sharded``, until every leaf is resident."""
        import jax

        from repro.train import checkpoint

        restored, _ = checkpoint.restore_sharded(self.path, self.like,
                                                 self.shardings, **self.kw)
        return jax.block_until_ready(restored)

    def resume_in_spans(self, spans):
        """The two calls ``restore_sharded`` composes, each in a span."""
        import jax

        from repro.train import checkpoint

        with spans.span("restore_tree"):
            tree, _ = checkpoint.restore_tree(self.path, self.like, **self.kw)
        with spans.span("device_put"):
            flat, treedef = jax.tree_util.tree_flatten(tree)
            placed = [jax.device_put(t, s) for t, s in
                      zip(flat, treedef.flatten_up_to(self.shardings))]
            jax.block_until_ready(placed)
        return jax.tree_util.tree_unflatten(treedef, placed)

    def compare(self, seed: int, fingerprints, last, limits: dict):
        """Every resume's fingerprints and the last resume's words against
        the state made again from the seed, one leaf at a time."""
        import jax
        import numpy as np

        index = {l.path: i for i, l in enumerate(self.leaves)}
        got = jax.tree.leaves(last)
        key = state.base_key(seed)
        bad_fp = np.zeros(len(fingerprints), dtype=np.int64)
        words = 0
        for j, path in enumerate(leaf_paths(self.like)):
            i = index[path]
            check = jax.jit(lambda k, g, i=i: (
                lambda ref: (fingerprint.leaf_fingerprint(ref),
                             fingerprint.differing_words(g, ref)))(
                state.make_leaf(k, i, self.leaves[i])))
            fp_ref, diff = check(key, got[j])
            fp_ref = np.asarray(fp_ref)
            words += int(diff)
            for r, fps in enumerate(fingerprints):
                bad_fp[r] += int(not np.array_equal(fps[j], fp_ref))
        return [
            checks.Check("leaf_mismatch_words", float(words),
                         limits["leaf_mismatch_words"]),
            checks.Check("fingerprint_mismatches", float(bad_fp.sum()),
                         limits["fingerprint_mismatches"]),
        ], bad_fp


def run(ctx) -> Outcome:
    import numpy as np

    res = Resumer(ctx.cell.config, ctx.cell.traffic, ctx.devices, ctx.workdir)
    res.write(ctx.seed)
    ctx.log(f"checkpoint: {res.nbytes} bytes of leaves, "
            f"{res.warm()!r} of the file in the page cache (-1: unknown)")
    last = None
    counter = CompileCounter()
    fingerprints, times = [], []
    counter.start()
    if ctx.trace:
        ctx.start_trace()
        for _ in range(ctx.cell.traffic["trace_resumes"]):
            free(last)
            last = res.resume_in_spans(ctx.spans)
            fingerprints.append(np.asarray(res.fingerprint(last)))
        ctx.stop_trace()
        end_to_end = {}
    else:
        t0 = time.perf_counter()
        setup_s = t0 - ctx.started
        while True:
            free(last)
            t = time.perf_counter()
            last = res.resume()
            times.append(time.perf_counter() - t)
            fingerprints.append(np.asarray(res.fingerprint(last)))
            if time.perf_counter() - t0 >= ctx.seconds:
                break
        end_to_end = {"resume_s": sum(times) / len(times),
                      "setup_s": setup_s}
        ctx.log(f"window: {len(times)} resumes, each {times!r} s")
    compiles = counter.stop()
    peak = device.memory_peak_bytes(ctx.devices)
    compared, bad = res.compare(ctx.seed, fingerprints, last,
                                ctx.cell.limits)
    free(last)
    return Outcome(
        end_to_end=end_to_end, checks=compared,
        attempted=len(fingerprints), failed=int((bad > 0).sum()),
        memory_peak_bytes=peak, window_compiles=compiles,
        counters={"checkpoint_bytes": res.nbytes,
                  "resumes": len(fingerprints)})
