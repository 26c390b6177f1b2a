import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this driver builds the *production* step function (the same
jitted train_step / prefill / decode_step the trainer and server run),
lowers it against ShapeDtypeStruct inputs (no allocation), compiles it for
the mesh, and records:

  * memory_analysis()  — per-device argument/output/temp bytes (fits?)
  * cost_analysis()    — per-device HLO FLOPs + bytes accessed
  * collective stats   — parsed from the optimized HLO (hlo_analysis)
  * MODEL_FLOPS        — 6*N*D (train) / 2*N*D (inference), N_active for MoE

Results land in experiments/dryrun/<mesh>/<arch>__<shape>.json; roofline.py
renders EXPERIMENTS.md tables from them. A cell failing to compile is a
bug in the framework's sharding — the suite is green only when all 40
cells pass on the single-pod (16,16) mesh AND the 2x16x16 multi-pod mesh.

NOTE: the two XLA_FLAGS lines above MUST precede any jax import (jax locks
the device count at first init). Nothing else in the repo sets this flag —
smoke tests and benchmarks see the host's real single device.
"""

import argparse
import dataclasses
import json
import time
import traceback
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro import configs
from repro.configs.base import ArchConfig, ShapeCell, applicable_shapes
from repro.dist import sharding as shd
from repro.launch import hlo_analysis as H
from repro.launch.mesh import make_production_mesh
from repro.models import api
from repro.optim import adamw, linear_warmup_cosine
from repro.train import trainer


def count_params(cfg: ArchConfig) -> tuple[float, float]:
    """(N_total, N_active) from the *unpadded* (tp=1) parameter tree."""
    import math

    model = api.build_model(cfg, tp=1, max_seq=8)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    n_total = float(
        sum(math.prod(l.shape) for l in jax.tree.leaves(shapes))
    )
    n_active = n_total
    if cfg.moe is not None:
        e, k = cfg.moe.num_experts, cfg.moe.top_k
        per_expert = 3 * cfg.d_model * cfg.moe.d_ff_expert
        n_active -= cfg.n_layers * (e - k) * per_expert
    return n_total, n_active


def model_flops(cfg: ArchConfig, cell: ShapeCell) -> float:
    n_total, n_active = count_params(cfg)
    tokens = cell.global_batch * (
        cell.seq_len if cell.kind in ("train", "prefill") else 1
    )
    per_token = 6.0 * n_active if cell.kind == "train" else 2.0 * n_active
    return per_token * tokens


def _bf16_params(shapes: Any) -> Any:
    return jax.tree.map(
        lambda l: jax.ShapeDtypeStruct(
            l.shape, jnp.bfloat16 if l.dtype == jnp.float32 else l.dtype
        ),
        shapes,
    )


def lower_cell(
    cfg: ArchConfig,
    cell: ShapeCell,
    mesh: jax.sharding.Mesh,
    *,
    serve_quant_bits: Optional[int] = None,
):
    """Build + lower + compile one cell. Returns (compiled, lowered)."""
    tp = mesh.shape["model"] if cfg.use_tp else 1
    model = api.build_model(cfg, tp=tp, max_seq=cell.seq_len)
    specs = api.input_specs(cfg, cell, tp=tp)
    p_shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))

    with mesh, shd.activation_context(cfg, mesh):
        if cell.kind == "train":
            opt = adamw(linear_warmup_cosine(3e-4, 200, 10_000))
            state_shapes = {
                "params": p_shapes,
                "opt": jax.eval_shape(opt.init, p_shapes),
                "step": jax.ShapeDtypeStruct((), jnp.int32),
            }
            jitted, s_shard, b_shard = trainer.make_sharded_train_step(
                model.loss, opt, cfg, mesh, state_shapes, specs["batch"],
                n_micro=cfg.train_microbatches,
            )
            lowered = jitted.lower(state_shapes, specs["batch"])
        elif cell.kind == "prefill":
            serve_params = _bf16_params(p_shapes)
            if serve_quant_bits:
                from repro.serve.engine import quantize_for_serving

                serve_params = jax.eval_shape(
                    lambda p: quantize_for_serving(p, serve_quant_bits),
                    p_shapes,
                )
            p_specs = shd.param_specs(serve_params, cfg, mesh)
            args = (
                (specs["tokens"], specs["frames"])
                if cfg.is_enc_dec else (specs["tokens"],)
            )
            arg_specs = shd.batch_specs(list(args), cfg, mesh)
            in_sh = (
                shd.named(p_specs, mesh),
                *[jax.sharding.NamedSharding(mesh, s) for s in arg_specs],
            )
            lowered = jax.jit(
                model.prefill, in_shardings=in_sh
            ).lower(serve_params, *args)
        else:  # decode
            serve_params = _bf16_params(p_shapes)
            if serve_quant_bits:
                from repro.serve.engine import quantize_for_serving

                serve_params = jax.eval_shape(
                    lambda p: quantize_for_serving(p, serve_quant_bits),
                    p_shapes,
                )
            p_specs = shd.param_specs(serve_params, cfg, mesh)
            c_specs = shd.cache_specs(specs["cache"], cfg, mesh)
            tok_specs = shd.batch_specs(
                {"token": specs["token"], "pos": specs["pos"]}, cfg, mesh
            )
            in_sh = (
                shd.named(p_specs, mesh),
                shd.named(c_specs, mesh),
                jax.sharding.NamedSharding(mesh, tok_specs["token"]),
                jax.sharding.NamedSharding(mesh, tok_specs["pos"]),
            )
            out_sh = (None, shd.named(c_specs, mesh))
            lowered = jax.jit(
                model.decode_step,
                in_shardings=in_sh,
                out_shardings=out_sh,
                donate_argnums=(1,),
            ).lower(
                serve_params, specs["cache"], specs["token"], specs["pos"]
            )
        compiled = lowered.compile()
    return compiled, lowered


_CONVERT_RE = None


def _bf16_emulation_bytes(text: str) -> int:
    """Bytes of large f32 buffers produced by bf16->f32 `convert`s.

    The CPU backend emulates bf16 dots in f32 and hoists the conversion
    of loop-carried bf16 stacks (KV caches, residual saves) out of the
    loop, materializing an f32 twin of the whole stack. On TPU bf16 is
    native and these buffers do not exist; we quantify them so the
    fits-in-HBM check can be read both raw (CPU artifact included) and
    adjusted (TPU-realistic).
    """
    import re as _re

    total = 0
    pat = _re.compile(
        r"= f32\[([\d,]+)\][^ ]* (?:convert|fusion)\("
    )
    seen = set()
    for line in text.splitlines():
        if "convert" not in line:
            continue
        m = pat.search(line)
        if not m:
            continue
        dims = [int(x) for x in m.group(1).split(",")]
        n = 4
        for d in dims:
            n *= d
        if n >= 1 << 28 and m.group(1) not in seen:
            seen.add(m.group(1))
            total += n
    return total


def analyze(compiled, cfg: ArchConfig, cell: ShapeCell, mesh) -> dict:
    from repro.launch.hlo_count import weighted_cost

    n_dev = mesh.size

    ma = compiled.memory_analysis()
    ca = compiled.cost_analysis()
    wc = weighted_cost(compiled.as_text())  # loop-aware (hlo_count.py)
    mf = model_flops(cfg, cell)
    terms = H.roofline_terms(
        per_device_flops=wc.flops,
        per_device_bytes=wc.bytes_accessed,
        per_device_collective_bytes=wc.collective_bytes,
        model_flops_total=mf,
        n_devices=n_dev,
        per_device_arg_bytes=float(ma.argument_size_in_bytes),
    )
    return {
        "arch": cfg.name,
        "shape": cell.name,
        "kind": cell.kind,
        "mesh": dict(zip(mesh.axis_names, [mesh.shape[a] for a in
                                           mesh.axis_names])),
        "n_devices": n_dev,
        "memory": {
            "argument_bytes": int(ma.argument_size_in_bytes),
            "output_bytes": int(ma.output_size_in_bytes),
            "temp_bytes": int(ma.temp_size_in_bytes),
            "alias_bytes": int(ma.alias_size_in_bytes),
            "total_per_device_bytes": int(
                ma.argument_size_in_bytes + ma.output_size_in_bytes
                + ma.temp_size_in_bytes - ma.alias_size_in_bytes
            ),
            # CPU-backend bf16-emulation f32 twins (absent on TPU):
            "bf16_emulation_bytes": int(
                _bf16_emulation_bytes(compiled.as_text())
            ),
        },
        "cost": {
            "per_device_flops": wc.flops,
            "per_device_bytes_accessed": wc.bytes_accessed,
            "xla_cost_analysis_flops_unscaled": float(
                ca.get("flops", 0.0)
            ),
            "loops": wc.loops,
            "top_bytes": [list(t) for t in wc.top_bytes],
            "top_flops": [list(t) for t in wc.top_flops],
        },
        "collectives": {
            "bytes_by_op": wc.collective_by_op,
            "count_by_op": wc.collective_counts,
            "total_bytes": wc.collective_bytes,
        },
        "roofline": terms,
    }


def run_cell(
    arch: str,
    shape: str,
    multi_pod: bool,
    out_dir: str,
    *,
    spe_bits: Optional[int] = None,
    spe_sparse: bool = False,
    serve_quant_bits: Optional[int] = None,
    tag: str = "",
    overrides: Optional[dict] = None,
) -> dict:
    cfg = configs.get(arch)
    if spe_bits is not None or spe_sparse:
        cfg = dataclasses.replace(
            cfg, spe_bits=spe_bits, spe_sparse=spe_sparse
        )
    if overrides:
        cfg = dataclasses.replace(cfg, **overrides)
    cell = configs.SHAPES[shape]
    mesh = make_production_mesh(multi_pod=multi_pod)
    t0 = time.monotonic()
    compiled, lowered = lower_cell(
        cfg, cell, mesh, serve_quant_bits=serve_quant_bits
    )
    dt = time.monotonic() - t0
    rec = analyze(compiled, cfg, cell, mesh)
    rec["compile_s"] = dt
    rec["serve_quant_bits"] = serve_quant_bits
    rec["spe_bits"] = spe_bits
    rec["spe_sparse"] = spe_sparse
    mesh_name = "multipod_2x16x16" if multi_pod else "singlepod_16x16"
    d = os.path.join(out_dir, mesh_name)
    os.makedirs(d, exist_ok=True)
    stem = f"{cfg.name.replace('/', '_')}__{shape}{tag}"
    with open(os.path.join(d, stem + ".json"), "w") as f:
        json.dump(rec, f, indent=1)
    # persist the optimized HLO so analyzer improvements can re-analyze
    # without recompiling (`--reanalyze`)
    import gzip

    with gzip.open(os.path.join(d, stem + ".hlo.gz"), "wt") as f:
        f.write(compiled.as_text())
    adj = (
        rec["memory"]["total_per_device_bytes"]
        - rec["memory"]["bf16_emulation_bytes"]
    )
    print(
        f"[dryrun] {cfg.name:24s} {shape:12s} {mesh_name:18s} "
        f"compile={dt:6.1f}s mem/dev={rec['memory']['total_per_device_bytes']/2**30:6.2f}GiB "
        f"(tpu-adj {adj/2**30:6.2f}) "
        f"dominant={rec['roofline']['dominant']:10s} "
        f"frac={rec['roofline']['roofline_fraction']:.3f}"
    )
    return rec


def reanalyze(out_dir: str) -> None:
    """Re-run the HLO analysis over stored .hlo.gz artifacts (no
    compilation) and refresh the roofline/collective fields in place."""
    import glob
    import gzip

    from repro.launch.hlo_count import weighted_cost

    n = 0
    for fn in sorted(glob.glob(os.path.join(out_dir, "*", "*.hlo.gz"))):
        jf = fn[: -len(".hlo.gz")] + ".json"
        if not os.path.exists(jf):
            continue
        with open(jf) as f:
            rec = json.load(f)
        with gzip.open(fn, "rt") as f:
            wc = weighted_cost(f.read())
        mf = rec["roofline"]["model_flops_total"]
        rec["cost"].update({
            "per_device_flops": wc.flops,
            "per_device_bytes_accessed": wc.bytes_accessed,
            "loops": wc.loops,
            "top_bytes": [list(t) for t in wc.top_bytes],
            "top_flops": [list(t) for t in wc.top_flops],
        })
        rec["collectives"] = {
            "bytes_by_op": wc.collective_by_op,
            "count_by_op": wc.collective_counts,
            "total_bytes": wc.collective_bytes,
        }
        rec["roofline"] = H.roofline_terms(
            per_device_flops=wc.flops,
            per_device_bytes=wc.bytes_accessed,
            per_device_collective_bytes=wc.collective_bytes,
            model_flops_total=mf,
            n_devices=rec["n_devices"],
            per_device_arg_bytes=float(rec["memory"]["argument_bytes"]),
        )
        with open(jf, "w") as f:
            json.dump(rec, f, indent=1)
        n += 1
    print(f"[dryrun] reanalyzed {n} cells")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", type=str, default=None,
                    help="arch id (e.g. qwen3-8b); default: all")
    ap.add_argument("--shape", type=str, default=None,
                    help="shape cell; default: all applicable")
    ap.add_argument("--mesh", choices=["single", "multi", "both"],
                    default="both")
    ap.add_argument("--out", type=str, default="experiments/dryrun")
    ap.add_argument("--spe-bits", type=int, default=None)
    ap.add_argument("--spe-sparse", action="store_true")
    ap.add_argument("--serve-quant-bits", type=int, default=None)
    ap.add_argument("--kv-quant-bits", type=int, default=None)
    ap.add_argument("--moe-shard", type=str, default=None)
    ap.add_argument("--loss-chunk", type=int, default=None)
    ap.add_argument("--microbatches", type=int, default=None)
    ap.add_argument("--tag", type=str, default="")
    ap.add_argument("--list", action="store_true")
    ap.add_argument("--reanalyze", action="store_true",
                    help="re-analyze stored .hlo.gz without compiling")
    args = ap.parse_args()
    if args.reanalyze:
        reanalyze(args.out)
        return

    overrides = {}
    if args.kv_quant_bits is not None:
        overrides["kv_quant_bits"] = args.kv_quant_bits
    if args.moe_shard is not None:
        overrides["moe_shard"] = args.moe_shard
    if args.loss_chunk is not None:
        overrides["loss_chunk"] = args.loss_chunk
    if args.microbatches is not None:
        overrides["train_microbatches"] = args.microbatches

    archs = (
        [args.arch] if args.arch else list(configs.CLI_IDS.keys())
    )
    meshes = {
        "single": [False], "multi": [True], "both": [False, True],
    }[args.mesh]

    todo = []
    for a in archs:
        cfg = configs.get(a)
        cells = (
            [configs.SHAPES[args.shape]] if args.shape
            else applicable_shapes(cfg)
        )
        for c in cells:
            for mp in meshes:
                todo.append((a, c.name, mp))
    if args.list:
        for a, s, mp in todo:
            print(a, s, "multi" if mp else "single")
        print(f"{len(todo)} cells")
        return

    failures = []
    for a, s, mp in todo:
        try:
            run_cell(
                a, s, mp, args.out,
                spe_bits=args.spe_bits, spe_sparse=args.spe_sparse,
                serve_quant_bits=args.serve_quant_bits, tag=args.tag,
                overrides=overrides,
            )
        except Exception as e:  # noqa: BLE001 — report all failures at end
            failures.append((a, s, mp, repr(e)))
            print(f"[dryrun] FAIL {a} {s} {'multi' if mp else 'single'}: {e}")
            traceback.print_exc()
    print(f"\n[dryrun] {len(todo) - len(failures)}/{len(todo)} cells passed")
    if failures:
        for f in failures:
            print("  FAIL:", *f)
        raise SystemExit(1)


if __name__ == "__main__":
    main()
