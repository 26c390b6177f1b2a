"""Training driver: --arch <id> [--reduced] over the current devices.

On the CPU container this runs REDUCED configs end-to-end (the examples
use it); on a TPU slice the same driver runs the full configs over
`make_production_mesh()`. The step function, sharding rules, data
pipeline, checkpointing and fault tolerance are identical in both modes —
only the mesh differs.

`--multi-pod PxD[xM]` switches to compressed multi-pod data parallelism
(`trainer.make_multipod_train_step`): a ("pod", "data", "model") mesh
where the in-pod axes run the sharded pjit step with XLA collectives
and the pod axis reduces gradients through `dist.compression` —
`--scheme gather` (default; (8/n)x egress, best below 8 pods) or
`--scheme two_stage` (n-independent ~4x), `--no-compress` for the f32
ablation baseline. The error-feedback buffers ride in the checkpointed
state, so kill-and-resume reproduces the uninterrupted run bitwise.

Examples:
  PYTHONPATH=src python -m repro.launch.train --arch qwen3-8b --reduced \\
      --steps 50 --batch 8 --seq 64 --ckpt /tmp/ck
  PYTHONPATH=src python -m repro.launch.train --arch va-cnn --steps 300
  XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
  PYTHONPATH=src python -m repro.launch.train --arch qwen3-8b --reduced \\
      --multi-pod 2x2x2 --scheme two_stage --steps 40 --batch 8 \\
      --ckpt /tmp/ck_mp
"""

from __future__ import annotations

import argparse
import dataclasses
import logging
import time

import jax
import jax.numpy as jnp

from repro import configs, obs
from repro.data import iegm, lm
from repro.dist import sharding as shd
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_multipod_mesh, make_smoke_mesh
from repro.models import api
from repro.optim import adamw, linear_warmup_cosine
from repro.train import fault, trainer


def _lm_cfg(args):
    cfg = configs.reduced(args.arch) if args.reduced else configs.get(
        args.arch
    )
    if args.spe_bits or args.spe_sparse:
        cfg = dataclasses.replace(
            cfg, spe_bits=args.spe_bits, spe_sparse=args.spe_sparse
        )
    return cfg


def _lm_batch_at(stream, cfg, args):
    """step -> batch, adding the deterministic enc-dec frames the
    whisper-family loss consumes."""
    def batch_at(step):
        b = stream.batch_at(step)
        if cfg.is_enc_dec:
            fkey = jax.random.fold_in(jax.random.PRNGKey(7), step)
            b["frames"] = jax.random.normal(
                fkey, (args.batch, cfg.enc_seq, cfg.d_model),
                jnp.float32,
            )
        return b

    return batch_at


def train_lm_multipod(args) -> dict:
    """Compressed multi-pod DP: in-pod sharded pjit x pod-axis
    quantized reduction, checkpoint-restartable (error buffers
    included)."""
    cfg = _lm_cfg(args)
    mesh = make_multipod_mesh(args.multi_pod)
    n_pod = mesh.shape["pod"]
    if args.batch % n_pod:
        raise SystemExit(
            f"--batch {args.batch} must divide by {n_pod} pods"
        )
    compress = not args.no_compress
    model = api.build_model(cfg, tp=1, max_seq=args.seq)
    key = jax.random.PRNGKey(args.seed)
    params = model.init(key)
    n_params = sum(p.size for p in jax.tree.leaves(params))
    logging.info(
        "arch=%s params=%.3fM mesh=%s scheme=%s compress=%s",
        cfg.name, n_params / 1e6, dict(mesh.shape),
        args.scheme, compress,
    )

    opt = adamw(
        linear_warmup_cosine(args.lr, args.warmup, args.steps),
        weight_decay=0.01,
    )
    state = trainer.init_state(params, opt)
    state["err"] = trainer.init_dp_err(
        params, mesh, scheme=args.scheme, compress=compress
    )
    step_fn, s_shard = trainer.make_multipod_train_step(
        model.loss, opt, cfg, mesh, jax.eval_shape(lambda: state),
        scheme=args.scheme, compress=compress, clip_norm=1.0,
        n_micro=args.grad_accum,
    )

    stream = lm.TokenStream(
        batch=args.batch, seq_len=args.seq, vocab=cfg.vocab, seed=args.seed
    )
    state, history = fault.run_training(
        step_fn, state, _lm_batch_at(stream, cfg, args),
        num_steps=args.steps,
        ckpt_dir=args.ckpt,
        ckpt_every=args.ckpt_every,
        watchdog=fault.StragglerWatchdog(),
        log_every=args.log_every,
        restore_shardings=s_shard,
    )
    first, last = history[0]["loss"], history[-1]["loss"]
    print(
        f"[train] {cfg.name} multi-pod {args.multi_pod} "
        f"scheme={args.scheme if compress else 'f32'}: "
        f"loss {first:.4f} -> {last:.4f} ({len(history)} steps)"
    )
    return {"history": history, "state": state}


def train_lm(args) -> dict:
    cfg = _lm_cfg(args)
    model = api.build_model(cfg, tp=1, max_seq=args.seq)
    key = jax.random.PRNGKey(args.seed)
    params = model.init(key)
    n_params = sum(p.size for p in jax.tree.leaves(params))
    logging.info("arch=%s params=%.3fM", cfg.name, n_params / 1e6)

    opt = adamw(
        linear_warmup_cosine(args.lr, args.warmup, args.steps),
        weight_decay=0.01,
    )
    state = trainer.init_state(params, opt)
    step_fn = obs.get().probe.track("train.step", jax.jit(
        trainer.make_train_step(
            model.loss, opt, clip_norm=1.0, n_micro=args.grad_accum
        ),
        donate_argnums=(0,),
    ), donate=(0,))

    stream = lm.TokenStream(
        batch=args.batch, seq_len=args.seq, vocab=cfg.vocab, seed=args.seed
    )

    watchdog = fault.StragglerWatchdog()
    state, history = fault.run_training(
        step_fn, state, _lm_batch_at(stream, cfg, args),
        num_steps=args.steps,
        ckpt_dir=args.ckpt,
        ckpt_every=args.ckpt_every,
        watchdog=watchdog,
        log_every=args.log_every,
    )
    first, last = history[0]["loss"], history[-1]["loss"]
    print(f"[train] {cfg.name}: loss {first:.4f} -> {last:.4f} "
          f"({len(history)} steps)")
    return {"history": history, "state": state}


def train_va(args) -> dict:
    from repro.configs import va_cnn
    from repro.core import vadetect

    cfg = va_cnn.CONFIG
    key = jax.random.PRNGKey(args.seed)
    params = vadetect.init(key, cfg)
    opt = adamw(linear_warmup_cosine(args.lr, args.warmup, args.steps))
    state = trainer.init_state(params, opt)
    step_fn = obs.get().probe.track("train.step", jax.jit(
        trainer.make_train_step(
            lambda p, b: vadetect.loss_fn(p, b, cfg), opt, clip_norm=1.0
        ),
        donate_argnums=(0,),
    ), donate=(0,))
    stream = iegm.IEGMStream(batch=args.batch, seed=args.seed)
    state, history = fault.run_training(
        step_fn, state, stream.batch_at,
        num_steps=args.steps, ckpt_dir=args.ckpt,
        ckpt_every=args.ckpt_every, log_every=args.log_every,
    )
    accs = [h["accuracy"] for h in history[-20:]]
    print(f"[train] va-cnn: loss {history[0]['loss']:.4f} -> "
          f"{history[-1]['loss']:.4f}; acc(last20) "
          f"{sum(accs)/len(accs):.4f}")
    return {"history": history, "state": state}


def main() -> None:
    logging.basicConfig(level=logging.INFO)
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--warmup", type=int, default=20)
    ap.add_argument("--grad-accum", type=int, default=1)
    ap.add_argument("--ckpt", type=str, default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--spe-bits", type=int, default=None)
    ap.add_argument("--spe-sparse", action="store_true")
    ap.add_argument(
        "--multi-pod", type=str, default=None, metavar="PxD[xM]",
        help="compressed multi-pod DP over a (pod, data, model) mesh, "
             "e.g. 2x2x2 (needs P*D*M devices)",
    )
    ap.add_argument(
        "--scheme", choices=("gather", "two_stage"), default="gather",
        help="cross-pod wire layout: gather=(8/n)x egress (n<8 pods), "
             "two_stage=n-independent ~4x (n>=8)",
    )
    ap.add_argument(
        "--no-compress", action="store_true",
        help="f32 cross-pod reduction (ablation baseline)",
    )
    ap.add_argument(
        "--trace-out", default=None, metavar="PREFIX",
        help="enable telemetry; on exit write PREFIX.jsonl (event log) "
             "and PREFIX.json (Chrome/Perfetto trace)",
    )
    args = ap.parse_args()
    enable_compile_cache()
    if args.trace_out:
        # before any step compilation so jit cells register with the probe
        obs.configure(enabled=True)
    if args.multi_pod:
        if args.arch == "va-cnn":
            raise SystemExit(
                "--multi-pod currently drives the LM trainer; va-cnn "
                "fits on one pod (use the plain path)"
            )
        train_lm_multipod(args)
    elif args.arch == "va-cnn":
        train_va(args)
    else:
        train_lm(args)
    if args.trace_out:
        jsonl, chrome = obs.get().finish(args.trace_out)
        print(f"[obs] trace written: {jsonl} + {chrome} "
              f"(recompiles: {obs.get().probe.cache_sizes()})")


if __name__ == "__main__":
    main()
