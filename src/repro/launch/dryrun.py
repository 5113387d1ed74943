import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

# Multi-pod dry-run: lower + compile every (arch × shape) on the production
# meshes, extract roofline inputs (FLOPs, bytes, per-device collective bytes,
# memory analysis), persist JSONL.
#
# The two lines above MUST run before any jax import — jax locks the device
# count at first init. Everything else (smoke tests, benches) sees 1 device.
#
# Usage:
#   python -m repro.launch.dryrun --arch gemma3-27b --shape train_4k --mesh multipod
#   python -m repro.launch.dryrun --all --mesh both --out benchmarks/results/dryrun.jsonl

import argparse
import json
import re
import time
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import SHAPES_BY_NAME, ShapeConfig
from repro.configs.registry import LONG_CONTEXT_ARCHS, cells, get_config
from repro.launch.mesh import make_production_mesh, mesh_axis_sizes
from repro.launch import sharding as shd
from repro.models import build_model
from repro.train.optimizer import OptConfig, init_opt_state
from repro.train.train_step import make_train_step

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16,
}

_COLLECTIVES = (
    "all-reduce", "all-gather", "reduce-scatter", "all-to-all",
    "collective-permute",
)
_SHAPE_RE = re.compile(r"\b(pred|[a-z]?f8e4m3fn|f8e5m2|bf16|f16|f32|f64|s8|s16|s32|s64|u8|u16|u32|u64|c64|c128)\[([0-9,]*)\]")


def _shape_bytes(type_str: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(type_str):
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES.get(dt, 4)
    return total


def collective_bytes_from_hlo(hlo_text: str) -> Dict[str, int]:
    """Sum result bytes of every collective op in a (post-SPMD, per-device)
    HLO module, keyed by op kind. Result bytes ~ payload per device; ring
    algorithms move up to 2x this per all-reduce — a modeling choice noted in
    EXPERIMENTS.md §Roofline."""
    out: Dict[str, int] = {k: 0 for k in _COLLECTIVES}
    out["count"] = 0
    for line in hlo_text.splitlines():
        s = line.strip()
        if "= " not in s:
            continue
        for op in _COLLECTIVES:
            marker = f" {op}("
            # exclude -start/-done duplicates (count the -start only)
            if f" {op}-done(" in s:
                continue
            if marker in s or f" {op}-start(" in s:
                lhs = s.split(marker)[0] if marker in s else s.split(f" {op}-start(")[0]
                # result type(s) appear after '=' on the lhs
                rhs_types = lhs.split("= ", 1)[-1]
                out[op] += _shape_bytes(rhs_types)
                out["count"] += 1
                break
    return out


def _batch_abstract(model, shape: ShapeConfig, mesh):
    specs = model.input_specs(shape)
    p = shd.batch_specs(specs, mesh)
    named = shd.to_named(p, mesh)
    return jax.tree.map(
        lambda sds, s: jax.ShapeDtypeStruct(sds.shape, sds.dtype, sharding=s),
        specs, named,
    )


def _with_sharding(abstract: Any, spec_tree: Any, mesh) -> Any:
    named = shd.to_named(spec_tree, mesh)
    return jax.tree.map(
        lambda sds, s: jax.ShapeDtypeStruct(sds.shape, sds.dtype, sharding=s),
        abstract, named,
    )


def pick_num_microbatches(shape: ShapeConfig, mesh, requested: Optional[int]) -> int:
    if shape.kind != "train":
        return 1
    if requested:
        return requested
    sizes = mesh_axis_sizes(mesh)
    dp = sizes.get("data", 1) * sizes.get("pod", 1)
    return max(1, min(16, shape.global_batch // dp))


def lower_cell(
    arch: str,
    shape: ShapeConfig,
    mesh,
    *,
    num_microbatches: Optional[int] = None,
    remat: Optional[str] = None,
    accum_dtype: str = "float32",
    compression: Optional[str] = None,
    param_dtype: Optional[str] = None,
    master_weights: bool = False,
    unroll: bool = False,
    num_layers_override: Optional[int] = None,
    overrides: Optional[Dict[str, Any]] = None,
    extra_tag: str = "",
):
    cfg = get_config(arch)
    if remat:
        cfg = cfg.replace(remat_policy=remat)
    if param_dtype:
        cfg = cfg.replace(param_dtype=param_dtype)
    if overrides:
        cfg = cfg.replace(**overrides)
    if unroll:
        # exact-cost analysis pass: scan bodies are counted once by XLA's
        # cost analysis, so unroll layers and skip microbatching (flop and
        # collective totals are microbatch-invariant; memory comes from the
        # scanned pass)
        cfg = cfg.replace(scan_layers=False)
        num_microbatches = 1
    if num_layers_override:
        cfg = cfg.replace(num_layers=num_layers_override)
    model = build_model(cfg)
    p_abs = model.abstract_params()
    p_specs = shd.param_specs(p_abs, mesh)
    p_in = _with_sharding(p_abs, p_specs, mesh)
    batch_in = _batch_abstract(model, shape, mesh)

    with jax.sharding.set_mesh(mesh):
        if shape.kind == "train":
            nmb = pick_num_microbatches(shape, mesh, num_microbatches)
            opt_abs = jax.eval_shape(
                lambda p: init_opt_state(p, master_weights=master_weights), p_abs
            )
            o_specs = shd.opt_state_specs(p_abs, p_specs, mesh,
                                          master_weights=master_weights)
            o_in = _with_sharding(opt_abs, o_specs, mesh)
            step = make_train_step(
                model, OptConfig(), num_microbatches=nmb,
                accum_dtype=jnp.dtype(accum_dtype), compression=compression,
            )
            jitted = jax.jit(
                step,
                in_shardings=(shd.to_named(p_specs, mesh),
                              shd.to_named(o_specs, mesh),
                              None),
                out_shardings=(shd.to_named(p_specs, mesh),
                               shd.to_named(o_specs, mesh),
                               None),
                donate_argnums=(0, 1),
            )
            lowered = jitted.lower(p_in, o_in, batch_in)
        elif shape.kind == "prefill":
            jitted = jax.jit(
                lambda p, b: model.prefill_logits(p, b),
                in_shardings=(shd.to_named(p_specs, mesh), None),
                out_shardings=shd.to_named(
                    shd.logits_spec(mesh, shape.global_batch, cfg.vocab_size), mesh),
            )
            lowered = jitted.lower(p_in, batch_in)
        else:  # decode
            st_abs = model.decode_state_specs(shape)
            st_specs = shd.decode_state_specs(st_abs, mesh, cfg)
            st_in = _with_sharding(st_abs, st_specs, mesh)
            jitted = jax.jit(
                lambda p, s, b: model.decode(p, s, b),
                in_shardings=(shd.to_named(p_specs, mesh),
                              shd.to_named(st_specs, mesh),
                              None),
                out_shardings=(shd.to_named(
                                   shd.logits_spec(mesh, shape.global_batch,
                                                   cfg.vocab_size),
                                   mesh),
                               shd.to_named(st_specs, mesh)),
                donate_argnums=(1,),
            )
            lowered = jitted.lower(p_in, st_in, batch_in)
    return cfg, lowered


def _lower_and_measure(arch, shape, mesh, *, compile_: bool, **kw) -> Dict[str, Any]:
    t0 = time.time()
    cfg, lowered = lower_cell(arch, shape, mesh, **kw)
    out: Dict[str, Any] = {"t_lower_s": round(time.time() - t0, 2)}
    try:
        ca = lowered.cost_analysis() or {}
        out["hlo_flops"] = float(ca.get("flops", -1.0))
        out["hlo_bytes"] = float(ca.get("bytes accessed", -1.0))
    except Exception as e:  # pragma: no cover
        out["cost_analysis_error"] = repr(e)
    if compile_:
        t0 = time.time()
        compiled = lowered.compile()
        out["t_compile_s"] = round(time.time() - t0, 2)
        try:
            ma = compiled.memory_analysis()
            if ma is not None:
                for attr in (
                    "argument_size_in_bytes", "output_size_in_bytes",
                    "temp_size_in_bytes", "generated_code_size_in_bytes",
                ):
                    v = getattr(ma, attr, None)
                    if v is not None:
                        out[attr] = int(v)
        except Exception as e:  # pragma: no cover
            out["memory_analysis_error"] = repr(e)
        try:
            cca = compiled.cost_analysis() or {}
            # post-fusion, per-device program (SPMD module)
            if "flops" in cca:
                out["compiled_flops"] = float(cca["flops"])
            if "bytes accessed" in cca:
                out["compiled_bytes"] = float(cca["bytes accessed"])
        except Exception:
            pass
        text = compiled.as_text()
        out["hlo_text_bytes"] = len(text)
        out["collectives"] = collective_bytes_from_hlo(text)
    return out


def run_cell(
    arch: str,
    shape_name: str,
    multi_pod: bool,
    *,
    compile_: bool = True,
    analyze: bool = True,
    **lower_kw,
) -> Dict[str, Any]:
    """Three-pass cell analysis.

    A) exact global FLOPs/bytes: unrolled full model, lower only (XLA cost
       analysis counts scan bodies once, so scans must be unrolled; compile
       not needed for HLO-level cost analysis).
    B) per-device collective bytes: unrolled *reduced-depth* compiles at
       nb=2 and nb=4 blocks, extrapolated linearly to the full depth —
       exact because every block is structurally identical and optimizer/
       gradient collectives are linear in block count too.
    C) memory + compile-success proof: the production configuration
       (scanned, microbatched) compiled at full depth.
    """
    shape = SHAPES_BY_NAME[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = int(mesh.devices.size)
    cfg = get_config(arch)
    pat = len(cfg.block_pattern)
    tail = cfg.num_layers % pat
    nb_full = cfg.num_layers // pat

    rec: Dict[str, Any] = {
        "arch": arch,
        "shape": shape_name,
        "kind": shape.kind,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "chips": chips,
        "tag": lower_kw.pop("extra_tag", ""),
    }

    # -- pass C: production compile (memory + proof) --------------------------
    prod = _lower_and_measure(arch, shape, mesh, compile_=compile_, **lower_kw)
    for k in ("t_lower_s", "t_compile_s", "argument_size_in_bytes",
              "output_size_in_bytes", "temp_size_in_bytes",
              "generated_code_size_in_bytes", "memory_analysis_error"):
        if k in prod:
            rec[k] = prod[k]
    rec["scanned_collectives"] = prod.get("collectives")

    if analyze:
        # -- pass A: exact flops/bytes --------------------------------------
        ex = _lower_and_measure(
            arch, shape, mesh, compile_=False, unroll=True, **lower_kw
        )
        rec["hlo_flops"] = ex.get("hlo_flops")
        rec["hlo_bytes"] = ex.get("hlo_bytes")
        rec["t_lower_unrolled_s"] = ex.get("t_lower_s")

        # -- pass B: collective + post-fusion byte extrapolation ----------------
        if compile_ and nb_full > 4:
            m2 = _lower_and_measure(
                arch, shape, mesh, compile_=True, unroll=True,
                num_layers_override=2 * pat + tail, **lower_kw
            )
            m4 = _lower_and_measure(
                arch, shape, mesh, compile_=True, unroll=True,
                num_layers_override=4 * pat + tail, **lower_kw
            )
            c2, c4 = m2["collectives"], m4["collectives"]
            coll = {}
            for k in c4:
                slope = (c4[k] - c2[k]) / 2.0
                coll[k] = int(c4[k] + slope * (nb_full - 4))
            rec["collectives"] = coll
            rec["collectives_method"] = "extrapolated(nb=2,4)"
            for key, name in (("compiled_bytes", "device_bytes"),
                              ("compiled_flops", "device_flops")):
                if key in m2 and key in m4:
                    slope = (m4[key] - m2[key]) / 2.0
                    rec[name] = float(m4[key] + slope * (nb_full - 4))
        elif compile_:
            full = _lower_and_measure(
                arch, shape, mesh, compile_=True, unroll=True, **lower_kw
            )
            rec["collectives"] = full["collectives"]
            rec["collectives_method"] = "exact(unrolled)"
            if "compiled_bytes" in full:
                rec["device_bytes"] = full["compiled_bytes"]
            if "compiled_flops" in full:
                rec["device_flops"] = full["compiled_flops"]
    else:
        rec["hlo_flops"] = prod.get("hlo_flops")
        rec["hlo_bytes"] = prod.get("hlo_bytes")
        rec["collectives"] = prod.get("collectives")
        rec["collectives_method"] = "scanned(undercounted)"

    pc = cfg.param_counts()
    rec["params_total"] = pc["total"]
    rec["params_active"] = pc["active"]
    tokens = shape.global_batch * (shape.seq_len if shape.kind != "decode" else 1)
    factor = 6 if shape.kind == "train" else 2
    rec["model_flops"] = factor * pc["active"] * tokens
    rec["tokens_per_step"] = tokens
    return rec


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", choices=["pod", "multipod", "both"], default="both")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--no-compile", action="store_true")
    ap.add_argument("--microbatches", type=int, default=None)
    ap.add_argument("--remat", default=None)
    ap.add_argument("--accum-dtype", default="float32")
    ap.add_argument("--param-dtype", default=None)
    ap.add_argument("--master-weights", action="store_true")
    ap.add_argument("--compression", default=None)
    ap.add_argument("--no-analyze", action="store_true",
                    help="skip exact-flop + collective-extrapolation passes")
    ap.add_argument("--override", action="append", default=[],
                    help="ModelConfig field override, e.g. xent_mode=onehot")
    ap.add_argument("--tag", default="")
    ap.add_argument("--out", default="benchmarks/results/dryrun.jsonl")
    args = ap.parse_args()

    if args.all:
        todo = list(cells())
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        todo = [(args.arch, SHAPES_BY_NAME[args.shape])]
    meshes = {"pod": [False], "multipod": [True], "both": [False, True]}[args.mesh]
    overrides = {}
    for ov in args.override:
        k, v = ov.split("=", 1)
        try:
            import ast
            overrides[k] = ast.literal_eval(v)
        except (ValueError, SyntaxError):
            overrides[k] = v

    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    for arch, shape in todo:
        for mp in meshes:
            print(f"=== {arch} × {shape.name} × {'2x16x16' if mp else '16x16'} ===",
                  flush=True)
            try:
                rec = run_cell(
                    arch, shape.name, mp,
                    compile_=not args.no_compile,
                    analyze=not args.no_analyze,
                    num_microbatches=args.microbatches,
                    remat=args.remat,
                    accum_dtype=args.accum_dtype,
                    param_dtype=args.param_dtype,
                    master_weights=args.master_weights,
                    compression=args.compression,
                    overrides=overrides,
                    extra_tag=args.tag,
                )
            except Exception as e:
                rec = {
                    "arch": arch, "shape": shape.name,
                    "mesh": "2x16x16" if mp else "16x16",
                    "error": repr(e)[:500], "tag": args.tag,
                }
                print(f"  FAILED: {rec['error']}", flush=True)
            with open(args.out, "a") as f:
                f.write(json.dumps(rec) + "\n")
            if "error" not in rec:
                coll = rec.get("collectives") or {}
                csum = sum(v for k, v in coll.items() if k != "count")
                print(
                    f"  ok: lower {rec.get('t_lower_s')}s compile "
                    f"{rec.get('t_compile_s', '-')}s "
                    f"flops={rec.get('hlo_flops') or -1:.3e} coll={csum:.3e}B",
                    flush=True,
                )


if __name__ == "__main__":
    main()
