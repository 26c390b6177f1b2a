"""Cross-pod gradient reduction: scheme x pod-count sweep + convergence.

Measures, on the *trainer's actual gradient tree* (a reduced LM config's
parameter tree), the three cross-pod reduction paths from
`repro.dist.compression` over forced multi-device host "pod" meshes:

  * sweep (n_pods in {2, 4, 8} x {gather, two_stage, uncompressed}) —
    two views of bytes-on-wire: collective bytes parsed from the
    optimized HLO with the loop-aware analyzer
    (`launch.hlo_count.weighted_cost`, the dry-run's accounting), and
    the modeled per-device ring egress:
      - f32 ring all-reduce:  2*(n-1)/n * 4B * |leaf|
      - int8 full-leaf gather: (n-1) * (|leaf| + 4B)      -> (8/n)x
      - int8 two-stage (reduce-scatter + all-gather):
        2*(n-1)/n * |leaf_padded| + 8B*(n-1)              -> ~4x, any n
    plus wall-clock per jitted call (host-CPU collectives: structural
    sanity, not DCN numbers).
  * convergence — short compressed-DP training runs of the reduced
    config (`trainer.make_dp_step_compressed` over the full forced pod
    mesh) per scheme, recording the loss curve: the wire-ratio vs
    loss-curve tradeoff in one table.

Asserted here (and therefore in `scripts/ci.sh`, which runs this):
  * two-stage egress ratio vs f32 is ~4x AND pod-count-independent
    (spread < 10% across n = 2/4/8);
  * gather decays like 8/n (>3.5x at n=2, <1.3x at n=8);
  * every scheme's loss curve decreases, compressed finals within
    tolerance of the f32 baseline.

Emits BENCH_dist.json, including a `telemetry` section in the shared
`repro.obs.telemetry_section` schema — {schema_version, enabled,
counters, gauges, histograms (count/sum/min/max/mean/p50/p90/p99/p999
per name, e.g. `train.step_latency_s`), recompiles (per compiled cell:
the per-scheme reduction jits and convergence train steps),
peak_device_memory_bytes} — identical across BENCH_stream/BENCH_decode/
BENCH_dist. Device count comes from
XLA_FLAGS=--xla_force_host_platform_device_count (forced to 8 here
unless already set; must precede any jax import).

    PYTHONPATH=src python benchmarks/dist_compression.py [--smoke]
"""

import os

if "--xla_force_host_platform_device_count" not in os.environ.get(
    "XLA_FLAGS", ""
):
    os.environ["XLA_FLAGS"] = (
        "--xla_force_host_platform_device_count=8 "
        + os.environ.get("XLA_FLAGS", "")
    ).strip()

import argparse
import json
import time

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro import configs, obs, optim
from repro.analysis import audit_section
from repro.data import lm
from repro.launch.hlo_count import weighted_cost
from repro.models import api
from repro.dist import compression as C
from repro.train import trainer

SCHEMES = ("uncompressed", "gather", "two_stage")


def grad_tree(arch: str):
    """The trainer's gradient pytree: one real value_and_grad of the
    reduced config's loss (grads mirror the f32 param tree)."""
    cfg = configs.reduced(arch)
    model = api.build_model(cfg, tp=1, max_seq=32)
    params = model.init(jax.random.PRNGKey(0))
    batch = {
        "tokens": jax.random.randint(
            jax.random.PRNGKey(1), (4, 16), 0, cfg.vocab),
        "targets": jax.random.randint(
            jax.random.PRNGKey(2), (4, 16), 0, cfg.vocab),
    }
    if cfg.is_enc_dec:
        batch["frames"] = jax.random.normal(
            jax.random.PRNGKey(3), (4, cfg.enc_seq, cfg.d_model),
            jnp.float32,
        )
    grads = jax.grad(lambda p: model.loss(p, batch)[0])(params)
    return cfg, grads


def _nbytes(tree) -> int:
    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(tree))


def modeled_egress(grads, n: int) -> dict:
    """Per-device ring-collective egress bytes for one reduction of the
    tree under each scheme (docstring formulas)."""
    sizes = [x.size for x in jax.tree.leaves(grads)]
    pad = lambda s: -(-s // n) * n  # noqa: E731
    unc = sum(2 * (n - 1) / n * 4 * s for s in sizes)
    gather = sum((n - 1) * (s + 4) for s in sizes)
    two = sum(2 * (n - 1) / n * pad(s) + 8 * (n - 1) for s in sizes)
    return {
        "uncompressed": unc,
        "gather": gather,
        "two_stage": two,
        "ratio_gather": unc / gather,
        "ratio_two_stage": unc / two,
    }


def _pod_mesh(n: int):
    return jax.make_mesh(
        (n,), ("pod",), devices=jax.devices()[:n],
        axis_types=(jax.sharding.AxisType.Auto,),
    )


def _reduction_fn(scheme: str, mesh, grads):
    """Jitted shard_map of one reduction call; returns (fn, args)."""
    rep = jax.tree.map(lambda _: P(), grads)
    if scheme == "uncompressed":
        fn = jax.jit(jax.shard_map(
            lambda g: C.uncompressed_psum_mean(g, "pod"),
            mesh=mesh, in_specs=(rep,), out_specs=rep, check_vma=False,
        ))
        return fn, (grads,)
    if scheme == "gather":
        err = jax.tree.map(jnp.zeros_like, grads)
        fn = jax.jit(jax.shard_map(
            lambda g, e: C.compressed_psum_mean(g, e, "pod"),
            mesh=mesh, in_specs=(rep, rep), out_specs=(rep, rep),
            check_vma=False,
        ))
        return fn, (grads, err)
    if scheme == "two_stage":
        n = mesh.shape["pod"]
        err1 = jax.tree.map(jnp.zeros_like, grads)
        err2 = jax.tree.map(
            lambda g: jnp.zeros(C.two_stage_shard_len(g.size, n)), grads
        )
        fn = jax.jit(jax.shard_map(
            lambda g, a, b: C.two_stage_psum_mean(g, a, b, "pod"),
            mesh=mesh, in_specs=(rep, rep, rep),
            out_specs=(rep, rep, rep), check_vma=False,
        ))
        return fn, (grads, err1, err2)
    raise ValueError(scheme)


def _time_call(fn, *args, reps: int = 10) -> float:
    jax.block_until_ready(fn(*args))  # warm/compile
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps


def sweep(grads, pod_counts) -> list[dict]:
    cells = []
    for n in pod_counts:
        mesh = _pod_mesh(n)
        eg = modeled_egress(grads, n)
        for scheme in SCHEMES:
            fn, args = _reduction_fn(scheme, mesh, grads)
            fn = obs.get().probe.track(
                f"dist.reduce.{scheme}.n{n}", fn
            )
            wc = weighted_cost(fn.lower(*args).compile().as_text())
            cells.append({
                "n_pods": n,
                "scheme": scheme,
                "modeled_egress_bytes_per_device": eg[scheme],
                "modeled_ratio_vs_f32":
                    eg["uncompressed"] / eg[scheme],
                "hlo_collective_bytes": wc.collective_bytes,
                "hlo_collective_by_op": wc.collective_by_op,
                "wall_s_per_call": _time_call(fn, *args),
            })
            print(
                f"[dist_compression] n={n} {scheme:>12}: "
                f"egress/device={eg[scheme]/2**20:7.2f}MiB "
                f"({eg['uncompressed']/eg[scheme]:4.2f}x vs f32)  "
                f"hlo={wc.collective_bytes/2**20:7.2f}MiB  "
                f"wall={cells[-1]['wall_s_per_call']*1e3:6.2f}ms"
            )
    return cells


def convergence(arch: str, steps: int) -> dict:
    """Wire-ratio vs loss-curve: train the reduced config with each
    reduction scheme over the full forced pod mesh."""
    n = jax.device_count()
    mesh = _pod_mesh(n)
    cfg = configs.reduced(arch)
    model = api.build_model(cfg, tp=1, max_seq=32)
    curves = {}
    for mode in ("f32", "gather", "two_stage"):
        compress = mode != "f32"
        scheme = mode if compress else "gather"
        params = model.init(jax.random.PRNGKey(0))
        opt = optim.adamw(3e-3)
        state = trainer.init_state(params, opt)
        state["err"] = trainer.init_dp_err(
            params, mesh, scheme=scheme, compress=compress
        )
        # Seat the initial state on the pod mesh with the step's output
        # sharding (replicated): otherwise the first call traces for
        # uncommitted single-device inputs and the second call retraces
        # for NamedSharding outputs — a silent 2x compile the recompile
        # telemetry (and the check() gate below) would flag.
        repl = jax.sharding.NamedSharding(mesh, P())
        for k in ("params", "opt", "step"):
            state[k] = jax.device_put(state[k], repl)
        step = obs.get().probe.track(
            f"train.dp_step.{mode}",
            jax.jit(trainer.make_dp_step_compressed(
                model.loss, opt, mesh, scheme=scheme, compress=compress
            )),
        )
        stream = lm.TokenStream(
            batch=8, seq_len=16, vocab=cfg.vocab, seed=0
        )
        tel = obs.get()
        step_hist = tel.registry.histogram("train.step_latency_s")
        losses = []
        for i in range(steps):
            t0 = time.perf_counter()
            with tel.span("train/step", cat="train", mode=mode, step=i):
                state, m = step(state, stream.batch_at(i))
                losses.append(round(float(m["loss"]), 6))
            step_hist.observe(time.perf_counter() - t0)
        curves[mode] = losses
        print(
            f"[dist_compression] convergence {mode:>9}: "
            f"loss {losses[0]:.4f} -> {losses[-1]:.4f} ({steps} steps, "
            f"n_pods={n})"
        )
    return {
        "arch": cfg.name, "n_pods": n, "steps": steps, "batch": 8,
        "curves": curves,
        "final": {k: v[-1] for k, v in curves.items()},
    }


def check(rec: dict) -> None:
    """The acceptance gates `scripts/ci.sh` relies on."""
    two = {c["n_pods"]: c["modeled_ratio_vs_f32"]
           for c in rec["sweep"] if c["scheme"] == "two_stage"}
    gather = {c["n_pods"]: c["modeled_ratio_vs_f32"]
              for c in rec["sweep"] if c["scheme"] == "gather"}
    # two-stage: ~4x below f32, independent of pod count
    for n, r in two.items():
        assert 3.5 < r < 4.3, ("two_stage ratio", n, r)
    spread = (max(two.values()) - min(two.values())) / min(two.values())
    assert spread < 0.10, ("two_stage not n-independent", two)
    # gather: (8/n)x decay — wins at n=2, dead by n=8
    assert gather[min(gather)] > 3.5, gather
    if 8 in gather:
        assert gather[8] < 1.3, gather
    # compressed wire really is smaller where XLA can show it: at every
    # n the HLO collective bytes of both int8 schemes undercut f32
    by_key = {(c["n_pods"], c["scheme"]): c for c in rec["sweep"]}
    for (n, scheme), c in by_key.items():
        if scheme == "uncompressed":
            continue
        unc = by_key[(n, "uncompressed")]["hlo_collective_bytes"]
        if unc and c["hlo_collective_bytes"]:
            assert c["hlo_collective_bytes"] < unc, (n, scheme)
    # convergence: every curve trains; compression stays near baseline
    cv = rec["convergence"]["curves"]
    for mode, losses in cv.items():
        assert losses[-1] < losses[0] - 0.05, (mode, losses[0],
                                               losses[-1])
    f32_final = cv["f32"][-1]
    drop = cv["f32"][0] - f32_final
    for mode in ("gather", "two_stage"):
        assert abs(cv[mode][-1] - f32_final) < max(0.25 * drop, 0.05), (
            mode, cv[mode][-1], f32_final
        )
    # telemetry gates: step-latency percentiles present for the
    # convergence runs, every per-scheme jitted cell in the recompile
    # map with exactly the expected compiled-variant count (one shape
    # each — any retrace after warmup would show here)
    t = rec["telemetry"]
    assert t["schema_version"] == obs.SCHEMA_VERSION and t["enabled"]
    h = t["histograms"]["train.step_latency_s"]
    assert h["count"] > 0 and None not in (
        h["p50"], h["p99"], h["p999"]
    ), h
    for mode in ("f32", "gather", "two_stage"):
        assert t["recompiles"].get(f"train.dp_step.{mode}") == 1, (
            mode, t["recompiles"]
        )
    assert t["peak_device_memory_bytes"] > 0, t
    # static cell audit: every registered jit cell re-lowered clean —
    # avals captured, no host callbacks/transfers, no f64, donations
    # honored, collectives within any declared budget
    ca = rec["cell_audit"]
    assert ca["n_cells"] > 0
    assert ca["violations_total"] == 0, ca
    assert any(k.startswith("dist.reduce.") for k in ca["cells"]), ca
    for mode in ("f32", "gather", "two_stage"):
        assert f"train.dp_step.{mode}" in ca["cells"], ca["cells"].keys()


def run(arch: str, out_path: str, *, steps: int,
        trace_out: str | None = None) -> dict:
    n_dev = jax.device_count()
    pod_counts = [n for n in (2, 4, 8) if n <= n_dev]
    if not pod_counts:
        raise SystemExit(
            f"dist_compression needs >= 2 devices for the scheme sweep "
            f"but jax sees {n_dev}; a pre-set XLA_FLAGS without "
            f"--xla_force_host_platform_device_count=8 overrides the "
            f"default this script would apply"
        )
    # before the reduction/step jits compile, so they register with
    # the probe
    obs.configure(enabled=True)
    cfg, grads = grad_tree(arch)
    rec = {
        "benchmark": "dist_compression",
        "arch": cfg.name,
        "n_devices": n_dev,
        "grad_leaves": len(jax.tree.leaves(grads)),
        "grad_bytes": _nbytes(grads),
        "sweep": sweep(grads, pod_counts),
        "convergence": convergence(arch, steps),
        "telemetry": obs.telemetry_section(),
        # every reduction / dp-step jit cell the sweep registered,
        # re-lowered and statically audited (repro.analysis)
        "cell_audit": audit_section(),
    }
    check(rec)
    rec["checked"] = True
    with open(out_path, "w") as f:
        json.dump(rec, f, indent=1)
    print(f"[dist_compression] all gates passed -> {out_path}")
    if trace_out:
        jsonl, chrome = obs.get().finish(trace_out)
        print(f"[obs] trace written: {jsonl} + {chrome}")
    return rec


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="qwen3_8b")
    ap.add_argument("--out", default="BENCH_dist.json")
    ap.add_argument("--smoke", action="store_true",
                    help="fewer convergence steps (CI)")
    ap.add_argument("--trace-out", default=None, metavar="PREFIX",
                    help="write the telemetry trace to PREFIX.jsonl "
                         "(event log) + PREFIX.json (Chrome/Perfetto)")
    args = ap.parse_args()
    run(args.arch, args.out, steps=24 if args.smoke else 60,
        trace_out=args.trace_out)


if __name__ == "__main__":
    main()
