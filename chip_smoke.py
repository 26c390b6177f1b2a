#!/usr/bin/env python3
"""Chip smoke test: the system's main paths, once each, on a TPU.

  python chip_smoke.py                # one chip: va_train, va_stream, lm_serve
  python chip_smoke.py --four-chips   # four-chip host: the sharded paths only

One process drives every phase. Each phase prints one line of what it
did (shapes, requests, compile and wall seconds); those seconds are
smoke timings, not benchmark numbers. Any failed check exits non-zero.
The last line of a passing run is the JSON device line:

  {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

Phases (one chip):
  va_train   the paper's co-design QAT trainer (16:8 sparsity, 8 bits)
             for a few steps through `launch.train.train_va` and
             `train.fault.run_training`; finite loss, zero retries.
  va_stream  the trained weights compiled to the chip format and served
             as a 1,024-patient fleet (`stream.simulate`, twin path);
             zero drops, one compile per bucket, and the twin, Pallas
             kernel (Mosaic) and reference paths agree on one batch.
  lm_serve   qwen3-8b at its published widths, depth cut to 6 layers,
             serving 8 requests through the in-process `Frontend` in
             front of a paged `Engine` (8 slots, page 16, max_seq 512).

With --four-chips: the 6-layer qwen3-8b on a 2x2 (data x model) mesh
against one chip (first-token logits, per-device bytes), and the VA fleet
sharded over 4 devices against one chip (predictions).

Without a TPU the script exits non-zero before any phase runs. The XLA
compile cache goes where `JAX_COMPILATION_CACHE_DIR` says, else to
`<repo>/.jax_cache` (`repro.launch.compile_cache`).
"""

from __future__ import annotations

import argparse
import asyncio
import dataclasses
import gc
import json
import math
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

# LM serving shape of the smoke phases (qwen3-8b published widths)
LM_LAYERS = 6
LM_SLOTS = 8
LM_PROMPT = 128
LM_MAX_NEW = 32
LM_MAX_SEQ = 512
LM_PAGE = 16

VA_TRAIN_STEPS = 30
VA_TRAIN_BATCH = 64
VA_PATIENTS = 1024
VA_BUCKETS = (8, 32, 128, 256)

# Cross-path tolerance for the VA logits, as a fraction of the largest
# |logit| of the reference path. On a TPU, XLA runs f32 convolutions
# and matmuls at DEFAULT precision: one bf16 pass, whose operands carry
# 8 mantissa bits (relative rounding up to 2^-9 per operand). The twin
# path (XLA conv), the kernel (Mosaic dot) and the reference (gather +
# sum) round at different points through 8 chained layers, so their
# logits differ by a few such roundings of the logit scale; 2% covers
# that with room, while a wrong weight, select index or stride is off
# by the order of the logits themselves.
VA_LOGIT_RTOL = 2e-2

# Sharded-vs-one-chip tolerance for the LM's first-token logits, as a
# fraction of the largest |logit| on one chip. The model computes in
# bf16; on a model axis the row-parallel contractions are summed as
# partial products in another order, so each of the 6 layers adds a
# few bf16 roundings (2^-8 relative). A missing or doubled reduction
# is off by the order of the logits.
LM_LOGIT_RTOL = 5e-2


class SmokeFailure(AssertionError):
    pass


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def say(phase: str, msg: str) -> None:
    print(f"[smoke] {phase}: {msg}", flush=True)


def _gb(n: float) -> str:
    return f"{n / 1e9:.2f} GB"


def _bytes_in_use(device) -> int:
    """Live bytes on `device` (-1 where the backend reports none)."""
    return (device.memory_stats() or {}).get("bytes_in_use", -1)


def lm_config():
    from repro import configs

    return dataclasses.replace(configs.get("qwen3_8b"), n_layers=LM_LAYERS)


def _lm_common() -> dict:
    return dict(batch=LM_SLOTS, prompt_len=LM_PROMPT, max_new=LM_MAX_NEW,
                max_seq=LM_MAX_SEQ, page_size=LM_PAGE)


def _serve(engine, prompts, max_new: int, prompt_len: int):
    """Route `prompts` through an in-process `Frontend` over `engine`;
    returns (results, compiles inside the serving window, warm seconds,
    serve seconds). `Frontend.stop()` raises if its engine thread died."""
    from repro import obs
    from repro.serve.frontend import Frontend, FrontendConfig, InProcClient

    fe = Frontend(engine=engine, cfg=FrontendConfig())
    t0 = time.perf_counter()
    fe.warm(prompt_len)
    warm_s = time.perf_counter() - t0
    snap = obs.get().probe.snapshot()

    async def go():
        await fe.start(host=None)
        client = InProcClient(fe)
        futs = [
            await client.send_lm(uid=i, prompt=[int(t) for t in p],
                                 max_new=max_new)
            for i, p in enumerate(prompts)
        ]
        try:
            return [await asyncio.wait_for(f, 900.0) for f in futs]
        finally:
            await fe.stop()

    t0 = time.perf_counter()
    results = asyncio.run(go())
    serve_s = time.perf_counter() - t0
    return results, obs.get().probe.new_misses(snap), warm_s, serve_s


def _check_served(phase: str, results, n: int, max_new: int,
                  vocab: int) -> None:
    done = [r for r in results if r["status"] == "completed"]
    rejected = [r for r in results if r["status"] == "rejected"]
    check(not rejected, f"{phase}: {len(rejected)} rejections: "
                        f"{[r.get('reason') for r in rejected]}")
    check(len(done) == n, f"{phase}: {len(done)}/{n} completed")
    for r in done:
        toks = r["tokens"]
        check(len(toks) == max_new,
              f"{phase}: uid {r['uid']} returned {len(toks)} tokens")
        check(all(0 <= t < vocab for t in toks),
              f"{phase}: uid {r['uid']} token outside [0, {vocab})")


# -- one-chip phases --------------------------------------------------------


def phase_va_train():
    """Train the VA detector at the paper's operating point; returns the
    trained params."""
    from repro import obs
    from repro.launch.train import train_va

    steps, batch = VA_TRAIN_STEPS, VA_TRAIN_BATCH
    t0 = time.perf_counter()
    out = train_va(argparse.Namespace(
        seed=0, lr=3e-3, warmup=5, steps=steps, batch=batch, ckpt=None,
        ckpt_every=steps, log_every=0,
    ))
    wall = time.perf_counter() - t0
    hist = out["history"]
    losses = [h["loss"] for h in hist]
    retries = obs.get().registry.snapshot()["counters"].get(
        "train.retries_total", 0
    )
    check(len(hist) == steps, f"va_train: {len(hist)}/{steps} steps ran")
    check(all(math.isfinite(x) for x in losses),
          f"va_train: non-finite loss in {losses}")
    check(retries == 0, f"va_train: train.retries_total = {retries}")
    say("va_train", f"{steps} steps x batch {batch} (512-sample IEGM, "
        f"16:8 sparse, 8-bit QAT); loss {losses[0]:.4f} -> "
        f"{losses[-1]:.4f}; retries 0; first step (compile) "
        f"{hist[0]['wall_s']:.2f}s, wall {wall:.2f}s")
    return out["state"]["params"]


def phase_va_stream(params) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import va_cnn
    from repro.core import compiler, vadetect
    from repro.stream import FleetConfig, FleetRunner, simulate
    from repro.stream.runner import _twin_logits, twin_weights
    from repro.stream.sources import FleetSource

    cfg = va_cnn.CONFIG
    n_patients, buckets = VA_PATIENTS, VA_BUCKETS
    program = compiler.compile_model(params, cfg)
    fcfg = FleetConfig(n_patients=n_patients, buckets=buckets, path="twin")
    runner = FleetRunner(program, cfg, path=fcfg.path)
    t0 = time.perf_counter()
    for b in buckets:
        runner.classify(
            jnp.zeros((b, vadetect.RECORD_LEN))
        ).block_until_ready()
    compile_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    out = simulate(fcfg, runner=runner)
    wall = time.perf_counter() - t0
    m = out["metrics"]
    want = n_patients * fcfg.segments_per_patient
    check(m["dropped_total"] == 0,
          f"va_stream: {m['dropped_total']} scheduler drops")
    check(m["segments_total"] == want,
          f"va_stream: {m['segments_total']}/{want} segments classified")
    check(out["jit_cache_misses"] == len(buckets),
          f"va_stream: jit_cache_misses {out['jit_cache_misses']} != "
          f"{len(buckets)} buckets")
    say("va_stream", f"{n_patients} patients x {fcfg.segments_per_patient} "
        f"segments, buckets {list(buckets)}, path twin: "
        f"{m['segments_total']} segments in {m['batches_total']} batches, "
        f"dropped 0, jit_cache_misses {out['jit_cache_misses']}; bucket "
        f"compile {compile_s:.2f}s, simulate wall {wall:.2f}s")

    # one 256-segment batch through the three compute paths
    b = max(buckets)
    x = FleetSource(fcfg.source_config()).signals(
        np.arange(b) % n_patients, np.zeros(b, np.int64)
    )["signal"]
    weights = twin_weights(program)
    fns = {
        "twin": jax.jit(
            lambda s: _twin_logits(weights, program.layer_meta, s)
        ),
        "kernel": jax.jit(
            lambda s: compiler.execute(program, s, cfg, path="kernel")
        ),
        "reference": jax.jit(
            lambda s: compiler.execute(program, s, cfg, path="reference")
        ),
    }
    kernel_hlo = fns["kernel"].lower(x).compile().as_text()
    check("tpu_custom_call" in kernel_hlo,
          "va_stream: kernel path compiled without a Mosaic "
          "tpu_custom_call (interpret mode?)")
    logits = {k: np.asarray(f(x)) for k, f in fns.items()}
    ref = logits["reference"]
    check(all(np.isfinite(v).all() and v.shape == (b, 2)
              for v in logits.values()),
          "va_stream: non-finite or misshapen logits")
    scale = float(np.abs(ref).max())
    diffs = {k: float(np.abs(v - ref).max())
             for k, v in logits.items() if k != "reference"}
    for k, d in diffs.items():
        check(d <= VA_LOGIT_RTOL * scale,
              f"va_stream: {k} vs reference max |dlogit| {d:.3g} > "
              f"{VA_LOGIT_RTOL} x {scale:.3g}")
    agree = {k: int((v.argmax(-1) == ref.argmax(-1)).sum())
             for k, v in logits.items() if k != "reference"}
    say("va_stream", f"{b}-segment batch: kernel (nm_spmm, Mosaic "
        f"tpu_custom_call) and twin vs reference max |dlogit| "
        f"kernel {diffs['kernel']:.3g}, twin {diffs['twin']:.3g} "
        f"(limit {VA_LOGIT_RTOL} x max|logit| {scale:.3g}); argmax "
        f"agreement kernel {agree['kernel']}/{b}, twin {agree['twin']}/{b}")


def phase_lm_serve() -> None:
    import jax
    import numpy as np

    from repro.launch.serve import build_lm_engine

    cfg, common = lm_config(), _lm_common()
    t0 = time.perf_counter()
    model, params, make_engine, make_prompts = build_lm_engine(
        cfg, **common
    )
    jax.block_until_ready(params)
    init_s = time.perf_counter() - t0
    n_params = sum(x.size for x in jax.tree.leaves(params))
    prompts = make_prompts(common["batch"])

    t0 = time.perf_counter()
    last, _ = jax.jit(model.prefill)(params, prompts[0][None])
    last = np.asarray(last)
    prefill_s = time.perf_counter() - t0
    check(last.shape[0] == 1 and last.shape[-1] >= cfg.vocab,
          f"lm_serve: prefill logits shape {last.shape}")
    check(np.isfinite(last).all(), "lm_serve: non-finite prefill logits")

    results, misses, warm_s, serve_s = _serve(
        make_engine(), prompts, common["max_new"], common["prompt_len"]
    )
    _check_served("lm_serve", results, len(prompts), common["max_new"],
                  cfg.vocab)
    stats = jax.devices()[0].memory_stats() or {}
    say("lm_serve", f"{cfg.name} at published widths (d_model "
        f"{cfg.d_model}, {cfg.n_heads}H/{cfg.n_kv_heads}KV x "
        f"{cfg.head_dim}, d_ff {cfg.d_ff}, vocab {cfg.vocab}) with depth "
        f"cut to n_layers={cfg.n_layers} via dataclasses.replace; "
        f"{n_params / 1e9:.2f}B params "
        f"({str(jax.tree.leaves(params)[0].dtype)}), init {init_s:.2f}s")
    say("lm_serve", f"prefill of one {common['prompt_len']}-token prompt: "
        f"finite logits {last.shape}, {prefill_s:.2f}s incl. compile")
    say("lm_serve", f"compiles inside the serving window after "
        f"Frontend.warm(): {sum(misses.values())} {misses or ''}; "
        f"peak_bytes_in_use {stats.get('peak_bytes_in_use', 'n/a')}")
    say("lm_serve", f"{len(results)}/{len(prompts)} requests completed "
        f"(prompt {common['prompt_len']}, max_new {common['max_new']}) "
        f"through Frontend -> paged Engine ({common['batch']} slots, page "
        f"{common['page_size']}, max_seq {common['max_seq']}); 0 "
        f"rejections; warm {warm_s:.2f}s, serve {serve_s:.2f}s")


# -- four-chip paths --------------------------------------------------------


def four_chip_lm() -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.launch.serve import build_lm_engine
    from repro.serve import sharded as SH

    cfg, common = lm_config(), _lm_common()

    # one chip first; everything it holds is freed before the mesh run
    model, params, make_engine, make_prompts = build_lm_engine(
        cfg, **common
    )
    prompts = make_prompts(common["batch"])
    batch = jnp.stack(prompts)
    ref = np.asarray(jax.jit(model.prefill)(params, batch)[0])
    one_results, _, _, _ = _serve(
        make_engine(), prompts, common["max_new"], common["prompt_len"]
    )
    _check_served("four_chips lm one-chip", one_results, len(prompts),
                  common["max_new"], cfg.vocab)
    prompts_host = np.asarray(batch)
    del model, params, make_engine, make_prompts, prompts, batch
    gc.collect()
    left = _bytes_in_use(jax.devices()[0])
    check(0 <= left < 1e9, f"four_chips: {_gb(left)} still on device 0 after "
                      f"freeing the one-chip engine")

    model, params, make_engine, _ = build_lm_engine(
        cfg, **common, mesh_spec="2x2"
    )
    engine = make_engine()
    plan = engine.plan
    want = plan.param_bytes_per_device + plan.cache_bytes_per_device
    used = [_bytes_in_use(d) for d in jax.devices()[:4]]
    for i, u in enumerate(used):
        # slot/token arrays and the page table add a few MB at most
        check(abs(u - want) <= 0.02 * want + 64 * 2**20,
              f"four_chips: device {i} holds {_gb(u)}, plan_decode "
              f"says {_gb(want)}")
    say("four_chips", f"{cfg.name} n_layers={cfg.n_layers} on a 2x2 "
        f"(data x model) mesh: plan_decode {_gb(want)}/device (params "
        f"{_gb(plan.param_bytes_per_device)} + cache "
        f"{_gb(plan.cache_bytes_per_device)}); memory_stats bytes_in_use "
        f"per device [{', '.join(_gb(u) for u in used)}]")

    dense_plan = SH.plan_decode(model, params, plan.mesh,
                                batch_size=len(prompts_host))
    prefill, _ = SH.compile_decode(model, dense_plan)
    got = np.asarray(prefill(
        params, jax.device_put(jnp.asarray(prompts_host), dense_plan.prompts)
    )[0])
    scale = float(np.abs(ref).max())
    diff = float(np.abs(got - ref).max())
    check(np.isfinite(got).all(), "four_chips: non-finite sharded logits")
    check(diff <= LM_LOGIT_RTOL * scale,
          f"four_chips: sharded vs one-chip first-token max |dlogit| "
          f"{diff:.3g} > {LM_LOGIT_RTOL} x {scale:.3g}")
    same_first = int((got.argmax(-1) == ref.argmax(-1)).sum())

    results, misses, warm_s, serve_s = _serve(
        engine, [jnp.asarray(p) for p in prompts_host],
        common["max_new"], common["prompt_len"],
    )
    _check_served("four_chips lm 2x2", results, len(prompts_host),
                  common["max_new"], cfg.vocab)
    one = {r["uid"]: r["tokens"] for r in one_results}
    same_tok = sum(t == u for r in results
                   for t, u in zip(r["tokens"], one[r["uid"]]))
    say("four_chips", f"first-token logits 2x2 vs one chip: max |dlogit| "
        f"{diff:.3g} (limit {LM_LOGIT_RTOL} x max|logit| {scale:.3g}), "
        f"argmax equal {same_first}/{len(prompts_host)}; ShardedEngine "
        f"served {len(results)}/{len(prompts_host)} completed, 0 "
        f"rejections, {same_tok}/{len(prompts_host) * common['max_new']} "
        f"tokens equal to one chip; compiles after warm "
        f"{sum(misses.values())}; warm {warm_s:.2f}s, serve {serve_s:.2f}s")


def four_chip_va() -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import va_cnn
    from repro.core import compiler, vadetect
    from repro.launch.stream import make_data_mesh
    from repro.stream import FleetConfig, FleetRunner, simulate
    from repro.stream.sources import FleetSource

    cfg = va_cnn.CONFIG
    n_patients, buckets = VA_PATIENTS, VA_BUCKETS
    program = compiler.compile_model(
        vadetect.init(jax.random.PRNGKey(0), cfg), cfg
    )
    mesh = make_data_mesh(4)
    fcfg = FleetConfig(n_patients=n_patients, buckets=buckets, path="twin")
    b = max(buckets)
    x = FleetSource(fcfg.source_config()).signals(
        np.arange(b), np.zeros(b, np.int64)
    )["signal"]
    one = np.asarray(FleetRunner(program, cfg).classify(x))
    runner = FleetRunner(program, cfg, mesh=mesh)
    four = np.asarray(runner.classify(jnp.asarray(x)))
    check((one == four).all(),
          f"four_chips: VA predictions differ on "
          f"{int((one != four).sum())}/{b} segments")
    t0 = time.perf_counter()
    out = simulate(fcfg, runner=runner)
    wall = time.perf_counter() - t0
    m = out["metrics"]
    check(m["dropped_total"] == 0,
          f"four_chips: {m['dropped_total']} fleet drops on 4 devices")
    say("four_chips", f"VA fleet sharded over {runner.n_devices} devices: "
        f"{b}-segment predictions equal to one chip ({b}/{b}); "
        f"{n_patients}-patient simulate {m['segments_total']} segments, "
        f"dropped 0, jit_cache_misses {out['jit_cache_misses']}, wall "
        f"{wall:.2f}s")


# -- entry point ------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the multi-chip paths (needs 4 chips)")
    args = ap.parse_args(argv)
    try:
        from repro import obs
        from repro.launch.compile_cache import enable_compile_cache
    except ImportError as e:
        print(f"[smoke] cannot import the repro package from "
              f"{os.path.join(ROOT, 'src')}: {e}", file=sys.stderr)
        return 2
    cache_dir = enable_compile_cache()
    import jax

    devices = jax.devices()
    d0 = devices[0]
    if d0.platform != "tpu":
        print(f"[smoke] no TPU found: JAX sees {len(devices)} "
              f"{d0.platform} device(s) ({d0.device_kind}); this smoke "
              f"test runs on a TPU only", file=sys.stderr)
        return 2
    need = 4 if args.four_chips else 1
    if len(devices) < need:
        print(f"[smoke] --four-chips needs 4 devices, found "
              f"{len(devices)}", file=sys.stderr)
        return 2
    n_cached = len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0
    print(f"[smoke] device {d0.device_kind} x {len(devices)}; compile "
          f"cache {cache_dir} ({n_cached} entries at start); timings "
          f"below are smoke timings, not benchmark numbers", flush=True)
    # telemetry on: the retry counter and the compile probe read it
    obs.configure(enabled=True)
    t0 = time.perf_counter()
    try:
        if args.four_chips:
            four_chip_lm()
            four_chip_va()
        else:
            params = phase_va_train()
            phase_va_stream(params)
            phase_lm_serve()
    except SmokeFailure as e:
        print(f"[smoke] FAILED: {e}", file=sys.stderr)
        return 1
    n_cached = len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0
    print(f"[smoke] all phases passed in {time.perf_counter() - t0:.1f}s; "
          f"compile cache holds {n_cached} entries", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": d0.platform, "kind": d0.device_kind,
        "count": len(devices),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
