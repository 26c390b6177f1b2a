"""Serving driver: batched generation (LM) or VA diagnosis service.

  PYTHONPATH=src python -m repro.launch.serve --arch qwen3-8b --reduced \\
      --batch 4 --prompt-len 16 --max-new 16 [--quant-bits 8] \\
      [--temperature 0.8 --top-k 40]
  PYTHONPATH=src python -m repro.launch.serve --arch va-cnn --patients 8

Greedy by default; --temperature enables per-request folded-key
sampling (reproducible for a fixed --seed, optionally top-k-truncated)
on both the single-device and mesh-sharded paths.

Async serving frontend (`repro.serve.frontend`): --listen HOST:PORT
serves the length-prefixed JSON transport with admission control at
--admission-rate; --connect HOST:PORT drives it open-loop from another
process; --frontend-sweep runs the loopback-socket offered-load sweep
(shed-rate curve, URGENT survival, graceful-degradation verdict).

Sharded multi-device decode (`repro.serve.sharded`): pass --mesh D or
DxM to place the decode cache/params on a ("data", "model") mesh; on a
CPU container force host devices first:

  XLA_FLAGS=--xla_force_host_platform_device_count=8 PYTHONPATH=src \\
      python -m repro.launch.serve --arch qwen3-8b --reduced \\
      --batch 8 --mesh 4x2
"""

from __future__ import annotations

import argparse
import time
from typing import Optional

import jax
import jax.numpy as jnp

from repro import configs, obs
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_serving_mesh
from repro.models import api
from repro.serve import engine as E
from repro.serve import sharded as SH


def serve_lm(args) -> None:
    cfg = _lm_config(args)
    max_seq = args.prompt_len + args.max_new + 1
    model = api.build_model(cfg, tp=1, max_seq=max_seq)
    key = jax.random.PRNGKey(args.seed)
    mesh = make_serving_mesh(args.mesh) if args.mesh else None
    params = (
        SH.init_placed_params(model, key, mesh) if mesh is not None
        else model.init(key)
    )
    if args.quant_bits:
        params = E.quantize_for_serving(params, args.quant_bits)
        print(f"[serve] weights quantized to {args.quant_bits} bits")
    prompts = jax.random.randint(
        key, (args.batch, args.prompt_len), 0, cfg.vocab
    )
    sampling = dict(
        greedy=args.temperature is None,
        key=jax.random.fold_in(key, 1),  # decouple from init/prompts
        # keep an explicit 0.0 (sample_tokens' documented degenerate-
        # to-greedy case) instead of `or`-defaulting it to 1.0
        temperature=1.0 if args.temperature is None
        else args.temperature,
        top_k=args.top_k,
    )
    if args.temperature is not None:
        print(f"[serve] sampling: temperature={args.temperature} "
              f"top_k={args.top_k or 'off'} (per-request folded keys)")
    if mesh is not None:
        plan = SH.plan_decode(model, params, mesh, batch_size=args.batch)
        print(
            f"[serve] mesh {dict(zip(mesh.axis_names, mesh.devices.shape))}: "
            f"cache {plan.cache_bytes_per_device / 1e3:.1f} kB/device "
            f"(replicated would be {plan.cache_bytes_total / 1e3:.1f} kB), "
            f"params {plan.param_bytes_per_device / 1e3:.1f} kB/device"
        )
        t0 = time.monotonic()
        with obs.get().span("serve/generate", cat="serve",
                            batch=args.batch, max_new=args.max_new,
                            mesh=args.mesh):
            out = SH.sharded_generate(
                model, params, prompts, mesh=mesh, max_new=args.max_new,
                plan=plan, **sampling,
            )
            out.block_until_ready()
    else:
        t0 = time.monotonic()
        with obs.get().span("serve/generate", cat="serve",
                            batch=args.batch, max_new=args.max_new):
            out = E.generate(
                model, params, prompts, max_new=args.max_new, **sampling
            )
            out.block_until_ready()
    dt = time.monotonic() - t0
    n_tok = args.batch * args.max_new
    print(f"[serve] {cfg.name}: {out.shape} tokens in {dt:.2f}s "
          f"({n_tok / dt:.1f} tok/s incl. compile)")
    print("[serve] sample:", out[0][:12].tolist())


def serve_load_sweep(args) -> None:
    """Open-loop offered-load sweep over the slot engine (wall time):
    measure the closed-loop capacity, then drive Poisson/trace-driven
    arrival schedules at fractions of it and report tail latency from
    *intended* arrival times, the saturation knee, TTFT SLO burn, and
    the overload verdict (see `repro.obs.loadlab`)."""
    import json as _json

    from repro.obs import loadlab

    make_engine, make_prompts = _build_lm_engine(args)
    key = jax.random.PRNGKey(args.seed)
    cap = loadlab.run_serve_point(
        make_engine,
        make_prompts(max(2 * args.batch, 8)),
        rate_rps=1e5,  # everything intended at ~t=0: drain throughput
        max_new=args.max_new,
        key=jax.random.fold_in(key, 3),
    )["achieved_rps"]
    fractions = tuple(
        float(f) for f in args.load_fractions.split(",")
    )
    out = loadlab.sweep_serve(
        make_engine,
        make_prompts,
        capacity_rps=cap,
        load_fractions=fractions,
        n_requests=args.load_requests,
        max_new=args.max_new,
        seed=args.seed,
        process=args.arrival_process,
    )
    print(
        f"[serve] open-loop sweep: capacity ~{cap:.0f} req/s, "
        f"{args.arrival_process} arrivals, "
        f"{args.load_requests} requests/point"
    )
    for p in out["points"]:
        print(
            f"[serve]   {p['load_fraction']:>5.2f}x  "
            f"offered {p['offered_load']:8.1f}/s  "
            f"p50 {p['p50_s'] * 1e3:7.1f}ms  "
            f"p99 {p['p99_s'] * 1e3:7.1f}ms  "
            f"p99.9 {p['p999_s'] * 1e3:7.1f}ms"
        )
    k = out["knee"]
    if k.get("detected"):
        print(
            f"[serve] saturation knee @ {k['knee_rate']:.1f} req/s "
            f"(p99 grows {k['post_knee_growth']:.1f}x past it)"
        )
    slo = out["slo"]
    print(
        f"[serve] SLO {slo['declared']['name']} "
        f"(bound {slo['declared']['bound'] * 1e3:.1f}ms): "
        f"met sub-saturated = {slo['met_sub_saturated']}; "
        f"overload verdict = {out['overload']['verdict']}"
    )
    if args.json:
        print(_json.dumps(out, indent=1, default=float))


def _hostport(s: str) -> tuple[str, int]:
    host, _, port = s.rpartition(":")
    return host or "127.0.0.1", int(port)


def build_lm_engine(
    cfg,
    *,
    batch: int,
    prompt_len: int,
    max_new: int,
    seed: int = 0,
    max_seq: Optional[int] = None,
    page_size: Optional[int] = None,
    pages_per_device: Optional[int] = None,
    chunk_tokens: Optional[int] = None,
    mesh_spec: Optional[str] = None,
):
    """Build what the LM serving entry points share for config `cfg`:
    `(model, params, make_engine, make_prompts)`. `max_seq` defaults to
    prompt + max_new + 2; with `page_size` it is rounded up to a whole
    number of pages. With a `mesh_spec` ('D' or 'DxM') the params are
    created directly in their mesh placement, never whole on one
    device, and `make_engine` builds a `ShardedEngine` over them."""
    if max_seq is None:
        max_seq = prompt_len + max_new + 2
    if page_size:
        # paged pools need page_size | every attention capacity; round
        # the derived max_seq up instead of bouncing the run
        max_seq += (-max_seq) % page_size
    model = api.build_model(cfg, tp=1, max_seq=max_seq)
    key = jax.random.PRNGKey(seed)
    mesh = make_serving_mesh(mesh_spec) if mesh_spec else None

    paging = None
    if page_size:
        from repro.dist import sharding as shd
        from repro.serve.paging import PagingConfig, validate_page_size

        n_data = (
            shd._axis_size(shd.data_axes(cfg, mesh), mesh)
            if mesh is not None else 1
        )
        per_dev = pages_per_device
        if per_dev is None:
            # default: the dense pool's worth of pages (+1 scratch) —
            # paged then never rejects what dense would have seated
            span = validate_page_size(page_size, model.attn_capacities())
            per_dev = (batch // max(n_data, 1)) * span + 1
        paging = PagingConfig(page_size, per_dev * max(n_data, 1))

    params = (
        SH.init_placed_params(model, key, mesh) if mesh is not None
        else model.init(key)
    )

    def make_engine():
        if mesh is not None:
            return SH.ShardedEngine(
                model, params, batch_size=batch, mesh=mesh,
                paging=paging, chunk_tokens=chunk_tokens,
            )
        return E.Engine(
            model, params, batch_size=batch,
            paging=paging, chunk_tokens=chunk_tokens,
        )

    def make_prompts(n):
        toks = jax.random.randint(
            jax.random.fold_in(key, 2), (n, prompt_len), 0, cfg.vocab,
        )
        return [jnp.asarray(toks[i], jnp.int32) for i in range(n)]

    return model, params, make_engine, make_prompts


def _lm_config(args):
    return configs.reduced(args.arch) if args.reduced else configs.get(
        args.arch
    )


def _build_lm_engine(args):
    _, _, make_engine, make_prompts = build_lm_engine(
        _lm_config(args),
        batch=args.batch,
        prompt_len=args.prompt_len,
        max_new=args.max_new,
        seed=args.seed,
        page_size=args.page_size,
        pages_per_device=args.pages_per_device,
        chunk_tokens=args.chunk_tokens,
        mesh_spec=args.mesh,
    )
    return make_engine, make_prompts


def serve_listen(args) -> None:
    """Serve LM requests over the length-prefixed JSON socket
    transport (`repro.serve.frontend`), with admission control at
    --admission-rate (shed with typed rejections past it)."""
    import asyncio

    from repro.serve.frontend import Frontend, FrontendConfig

    make_engine, _ = _build_lm_engine(args)
    fe = Frontend(
        engine=make_engine(),
        cfg=FrontendConfig(admission_rate_rps=args.admission_rate),
    )
    fe.warm(args.prompt_len)
    host, port = _hostport(args.listen)

    async def amain() -> None:
        bound = await fe.start(host, port)
        print(f"[serve] frontend listening on {bound[0]}:{bound[1]} "
              f"(admission rate: "
              f"{args.admission_rate or 'unbounded'})")
        try:
            await asyncio.Event().wait()
        finally:
            await fe.stop()

    try:
        asyncio.run(amain())
    except KeyboardInterrupt:
        print("[serve] frontend stopped")


def serve_connect(args) -> None:
    """Open-loop socket client: offer --load-requests LM requests at
    --offered-rate req/s and report terminal outcomes."""
    import asyncio

    from repro.obs import loadlab
    from repro.serve.frontend import SocketClient

    host, port = _hostport(args.connect)
    key = jax.random.PRNGKey(args.seed)
    intended = loadlab.arrival_times(
        key, 0, rate_hz=args.offered_rate, n=args.load_requests,
        process=args.arrival_process,
    )

    async def amain() -> dict:
        import time

        client = await SocketClient.connect(host, port)
        futs = []
        t0 = time.perf_counter()
        for i in range(args.load_requests):
            delay = intended[i] - (time.perf_counter() - t0)
            if delay > 0:
                await asyncio.sleep(delay)
            prompt = [int(x) for x in jax.random.randint(
                jax.random.fold_in(key, 100 + i),
                (args.prompt_len,), 0, 1000,
            )]
            futs.append(await client.send_lm(
                uid=i, prompt=prompt, max_new=args.max_new
            ))
        results = [await asyncio.wait_for(f, 120.0) for f in futs]
        await client.close()
        return results

    results = asyncio.run(amain())
    done = sum(1 for r in results if r["status"] == "completed")
    reasons: dict = {}
    for r in results:
        if r["status"] == "rejected":
            reasons[r["reason"]] = reasons.get(r["reason"], 0) + 1
    print(f"[serve] {args.load_requests} offered at "
          f"{args.offered_rate:.1f} req/s -> {done} completed, "
          f"{len(results) - done} rejected {reasons or ''}")


def serve_frontend_sweep(args) -> None:
    """Loopback-socket offered-load sweep through the frontend:
    measure engine capacity closed-loop (or take --admission-rate),
    then offer --load-fractions of it over a real socket with active
    admission control — shed-rate curve, URGENT segment survival, and
    the overload verdict (see `loadlab.sweep_frontend`)."""
    import json as _json

    from repro.core import compiler, vadetect
    from repro.obs import loadlab
    from repro.serve.frontend import Frontend
    from repro.stream.runner import FleetRunner

    make_engine, make_prompts = _build_lm_engine(args)
    rate = args.admission_rate
    if rate is None:
        rate = loadlab.run_serve_point(
            make_engine,
            make_prompts(max(2 * args.batch, 8)),
            rate_rps=1e5,
            max_new=args.max_new,
            key=jax.random.PRNGKey(args.seed + 3),
        )["achieved_rps"]
        print(f"[serve] closed-loop capacity ~{rate:.0f} req/s -> "
              f"admission rate")
    runner = FleetRunner(
        compiler.compile_model(
            vadetect.init(jax.random.PRNGKey(args.seed))
        )
    )

    def make_frontend(fcfg):
        fe = Frontend(engine=make_engine(), n_patients=args.patients,
                      runner=runner, cfg=fcfg)
        fe.warm(args.prompt_len)
        return fe

    out = loadlab.sweep_frontend(
        make_frontend,
        make_prompts,
        admission_rate_rps=rate,
        load_fractions=tuple(
            float(f) for f in args.load_fractions.split(",")
        ),
        n_requests=args.load_requests,
        max_new=args.max_new,
        seed=args.seed,
        n_patients=args.patients,
        process=args.arrival_process,
    )
    for p in out["points"]:
        print(
            f"[serve]   {p['load_fraction']:>5.2f}x  "
            f"offered {p['offered_load']:8.1f}/s  "
            f"completed {p['completed']:3d}  "
            f"shed {p['shed_rate']:5.1%}  "
            f"p99 {(p['p99_s'] or float('nan')) * 1e3:7.1f}ms  "
            f"seg-deferred {p['segments']['deferred']}"
        )
    ov = out["overload"]
    print(f"[serve] frontend verdict = {ov['verdict']} "
          f"(accounting_exact={ov['accounting_exact']}, "
          f"urgent_survived={ov['urgent_survived']})")
    to = out.get("transport_overhead")
    if to:
        print(f"[serve] socket - inproc p99: "
              f"{to['socket_minus_inproc_p99_s'] * 1e3:.2f}ms at "
              f"{to['load_fraction']}x")
    if args.json:
        print(_json.dumps(out, indent=1, default=float))


def serve_va(args) -> None:
    from repro.configs import va_cnn
    from repro.core import compiler, vadetect
    from repro.data import iegm
    from repro.serve.va_service import VAService

    key = jax.random.PRNGKey(args.seed)
    params = vadetect.init(key, va_cnn.CONFIG)
    program = compiler.compile_model(params, va_cnn.CONFIG)
    svc = VAService(program, va_cnn.CONFIG)
    batch = iegm.synth_diagnosis_batch(key, args.patients)
    out = svc.diagnose_batch(batch["signal"])
    correct = sum(
        int(d.is_va) == int(batch["label"][i]) for i, d in enumerate(out)
    )
    rep = svc.report.summary()
    print(f"[serve] va-cnn: {args.patients} diagnoses, "
          f"{correct}/{args.patients} match labels (untrained weights)")
    print(f"[serve] chip model: {rep['latency_us']:.1f}us/inference, "
          f"{rep['effective_GOPS']:.1f} GOPS, "
          f"{rep['avg_power_uW']:.2f} uW")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--quant-bits", type=int, default=None)
    ap.add_argument("--temperature", type=float, default=None,
                    help="enable sampling at this temperature "
                         "(default: greedy argmax)")
    ap.add_argument("--top-k", type=int, default=0,
                    help="top-k truncation for sampling (0 = full "
                         "distribution); needs --temperature")
    ap.add_argument("--mesh", default=None,
                    help="shard decode on a device mesh: 'D' or 'DxM' "
                         "(data x model), e.g. --mesh 8 or --mesh 4x2")
    ap.add_argument("--patients", type=int, default=8)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--load-sweep", action="store_true",
                    help="run the open-loop offered-load sweep "
                         "(repro.obs.loadlab) instead of one batch")
    ap.add_argument("--frontend-sweep", action="store_true",
                    help="offered-load sweep through the async "
                         "serving frontend over a loopback socket, "
                         "with knee-aware admission control")
    ap.add_argument("--listen", default=None, metavar="HOST:PORT",
                    help="serve LM requests over the frontend's "
                         "length-prefixed JSON socket transport")
    ap.add_argument("--connect", default=None, metavar="HOST:PORT",
                    help="open-loop socket client against a --listen "
                         "frontend (sends --load-requests at "
                         "--offered-rate)")
    ap.add_argument("--offered-rate", type=float, default=50.0,
                    help="with --connect: offered load in req/s")
    ap.add_argument("--admission-rate", type=float, default=None,
                    help="admission-control rate in req/s for "
                         "--listen/--frontend-sweep (default: "
                         "unbounded for --listen; measured capacity "
                         "for --frontend-sweep)")
    ap.add_argument("--load-fractions",
                    default="0.25,0.5,0.75,1.0,2.0",
                    help="offered load as fractions of measured "
                         "capacity (comma-separated)")
    ap.add_argument("--load-requests", type=int, default=24,
                    help="requests per offered-load point")
    ap.add_argument("--arrival-process", default="poisson",
                    choices=["poisson", "trace"],
                    help="interarrival process for --load-sweep")
    ap.add_argument("--json", action="store_true",
                    help="with --load-sweep: dump the full sweep "
                         "record as JSON")
    ap.add_argument("--trace-out", default=None, metavar="PREFIX",
                    help="enable telemetry; on exit write PREFIX.jsonl "
                         "(event log) and PREFIX.json (Chrome/Perfetto "
                         "trace)")
    ap.add_argument("--page-size", type=int, default=None,
                    help="paged KV cache: positions per page (must "
                         "divide every attention window; max_seq is "
                         "rounded up to a multiple)")
    ap.add_argument("--pages-per-device", type=int, default=None,
                    help="with --page-size: physical pages per data "
                         "shard incl. 1 scratch (default: the dense "
                         "pool equivalent, batch/shard x span + 1)")
    ap.add_argument("--chunk-tokens", type=int, default=None,
                    help="chunked prefill: split prompts longer than "
                         "this into page-sized chunks interleaved "
                         "with decode ticks")
    args = ap.parse_args()
    enable_compile_cache()
    if args.pages_per_device and not args.page_size:
        ap.error("--pages-per-device requires --page-size")
    if args.top_k and args.temperature is None:
        ap.error("--top-k only applies when sampling; pass "
                 "--temperature too (e.g. --temperature 1.0)")
    if args.trace_out:
        obs.configure(enabled=True)
    if (args.frontend_sweep or args.listen) and args.arch == "va-cnn":
        ap.error("the serving frontend fronts the LM slot engine; "
                 "for the stream side use repro.launch.stream "
                 "--listen/--connect")
    if args.load_sweep:
        if args.arch == "va-cnn":
            ap.error("--load-sweep drives the LM slot engine; for the "
                     "fleet sweep use repro.launch.stream --load-sweep")
        serve_load_sweep(args)
    elif args.frontend_sweep:
        serve_frontend_sweep(args)
    elif args.listen:
        serve_listen(args)
    elif args.connect:
        serve_connect(args)
    elif args.arch == "va-cnn":
        serve_va(args)
    else:
        serve_lm(args)
    if args.trace_out:
        jsonl, chrome = obs.get().finish(args.trace_out)
        print(f"[obs] trace written: {jsonl} + {chrome}")


if __name__ == "__main__":
    main()
