"""Fleet streaming driver: continuous multi-patient VA monitoring.

  PYTHONPATH=src python -m repro.launch.stream --patients 256 \\
      --segments 8 --buckets 8,32,128,256 --devices 4

Builds a data-axis mesh over the first `--devices` host devices, trains
nothing (weights are random — the point is the serving path), compiles
the accelerator program, and drives the `repro.stream` fleet simulation:
virtual-time arrivals with jitter/dropout, deadline-aware micro-batching
with urgent-patient preemption, sharded bucketed inference, vectorized
6-segment voting. Prints the fleet metrics summary.

To exercise a multi-device mesh on a CPU host, force host devices
*before* any jax import:
  XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
      PYTHONPATH=src python -m repro.launch.stream --devices 8 ...
"""

from __future__ import annotations

import argparse
import json

import jax

from repro import obs
from repro.core import compiler, vadetect
from repro.launch.compile_cache import enable_compile_cache
from repro.stream import FleetConfig, simulate


def make_data_mesh(n_devices: int) -> jax.sharding.Mesh | None:
    """1-D data-parallel mesh over the first n host devices."""
    if n_devices <= 1:
        return None
    avail = jax.device_count()
    if n_devices > avail:
        raise SystemExit(
            f"--devices {n_devices} > available {avail}; set XLA_FLAGS="
            f"--xla_force_host_platform_device_count={n_devices}"
        )
    return jax.make_mesh(
        (n_devices,),
        ("data",),
        devices=jax.devices()[:n_devices],
        axis_types=(jax.sharding.AxisType.Auto,),
    )


def stream_load_sweep(args, program, buckets, mesh) -> None:
    """Open-loop offered-load sweep in virtual time: per-patient
    Poisson/trace segment arrivals at fractions of the modeled fleet
    capacity, latency from intended arrival, knee location, pinned
    URGENT-cohort deadline-slack SLO, overload verdict
    (see `repro.obs.loadlab`). Exactly reproducible on any host."""
    from repro.obs import loadlab
    from repro.stream import FleetRunner

    runner = FleetRunner(program, path=args.path, mesh=mesh)
    fractions = tuple(float(f) for f in args.load_fractions.split(","))
    out = loadlab.sweep_stream(
        n_patients=args.patients,
        buckets=buckets,
        load_fractions=fractions,
        segments_at_capacity=args.segments_at_capacity,
        seed=args.seed,
        urgent_fraction=args.urgent_fraction,
        process=args.arrival_process,
        runner=runner,
    )
    if args.trace_out:
        jsonl, chrome = obs.get().finish(args.trace_out)
        print(f"[obs] trace written: {jsonl} + {chrome}")
    if args.json:
        print(json.dumps(out, indent=1, default=float))
        return
    print(
        f"[stream] open-loop sweep: {args.patients} patients, "
        f"buckets={list(buckets)}, capacity "
        f"{out['capacity_segments_per_s']:.0f} seg/s, "
        f"{args.arrival_process} arrivals"
    )
    for p in out["points"]:
        print(
            f"[stream]   {p['load_fraction']:>5.2f}x  "
            f"offered {p['offered_load']:9.0f}/s  "
            f"p50 {p['p50_s'] * 1e3:7.2f}ms  "
            f"p99 {p['p99_s'] * 1e3:7.2f}ms  "
            f"p99.9 {p['p999_s'] * 1e3:7.2f}ms  "
            f"dropped={p['dropped']}"
        )
    k = out["knee"]
    if k.get("detected"):
        print(
            f"[stream] saturation knee @ {k['knee_rate']:.0f} seg/s "
            f"(p99 grows {k['post_knee_growth']:.1f}x past it)"
        )
    print(
        f"[stream] URGENT cohort ({out['urgent_patients']} patients) "
        f"overload burn rate "
        f"{out['slo']['urgent_overload'].get('burn_rate'):.2f}; "
        f"verdict = {out['overload']['verdict']}"
    )


def stream_listen(args, program, buckets, mesh) -> None:
    """Accept patient segments over the serving-frontend socket
    transport (`repro.serve.frontend`): ROUTINE segments are deferred
    (never dropped) past --stream-rate, URGENT always pass and flip
    the scheduler's preemption bitmap."""
    import asyncio

    from repro.serve.frontend import Frontend, FrontendConfig
    from repro.stream import FleetRunner

    host, _, port = args.listen.rpartition(":")
    fe = Frontend(
        n_patients=args.patients,
        runner=FleetRunner(program, path=args.path, mesh=mesh),
        cfg=FrontendConfig(
            stream_rate_rps=args.stream_rate,
            stream_buckets=buckets,
            stream_max_wait_s=args.max_wait,
        ),
    )
    fe.warm()

    async def amain() -> None:
        bound = await fe.start(host or "127.0.0.1", int(port))
        print(f"[stream] frontend listening on "
              f"{bound[0]}:{bound[1]} ({args.patients} patients, "
              f"routine rate: {args.stream_rate or 'unbounded'})")
        try:
            await asyncio.Event().wait()
        finally:
            await fe.stop()

    try:
        asyncio.run(amain())
    except KeyboardInterrupt:
        print("[stream] frontend stopped")


def stream_connect(args) -> None:
    """Open-loop socket client: offer --patients x --segments patient
    segments at --offered-rate seg/s (first --urgent-fraction of
    patients URGENT), then drain and report the ack ledger."""
    import asyncio
    import time

    from repro.obs import loadlab
    from repro.serve.frontend import SocketClient

    host, _, port = args.connect.rpartition(":")
    n_urgent = max(1, int(round(args.urgent_fraction * args.patients)))
    total = args.patients * args.segments
    intended = loadlab.arrival_times(
        jax.random.PRNGKey(args.seed), 0, rate_hz=args.offered_rate,
        n=total, process=args.arrival_process,
    )

    async def amain():
        client = await SocketClient.connect(host or "127.0.0.1",
                                            int(port))
        futs = []
        t0 = time.perf_counter()
        for i in range(total):
            delay = intended[i] - (time.perf_counter() - t0)
            if delay > 0:
                await asyncio.sleep(delay)
            p, s = i % args.patients, i // args.patients
            futs.append(await client.send_segment(
                patient=p, seq=s, urgent=p < n_urgent
            ))
        acks = [await asyncio.wait_for(f, 60.0) for f in futs]
        stats = (await client.drain()).get("stats", {})
        await client.close()
        return acks, stats

    acks, stats = asyncio.run(amain())
    by = {}
    for a in acks:
        by[a["status"]] = by.get(a["status"], 0) + 1
    print(f"[stream] {total} segments offered at "
          f"{args.offered_rate:.1f}/s ({n_urgent} urgent patients): "
          f"acks {by}")
    enq = stats.get("sched_enqueued_total", 0)
    packed = stats.get("sched_packed_total", 0)
    print(f"[stream] drained: enqueued={enq} packed={packed} "
          f"left-behind={enq - packed} "
          f"deferred={stats.get('seg_deferred', 0)}")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--patients", type=int, default=256)
    ap.add_argument("--segments", type=int, default=8,
                    help="segments per patient over the horizon")
    ap.add_argument("--buckets", default="8,32,128,256")
    ap.add_argument("--devices", type=int, default=1)
    ap.add_argument("--path", default="twin",
                    choices=["twin", "reference", "kernel", "dense"])
    ap.add_argument("--va-fraction", type=float, default=0.05)
    ap.add_argument("--jitter", type=float, default=0.05,
                    help="arrival jitter std as a fraction of 2.048s")
    ap.add_argument("--dropout", type=float, default=0.0,
                    help="per-segment telemetry-gap probability")
    ap.add_argument("--max-wait", type=float, default=0.256)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--load-sweep", action="store_true",
                    help="run the open-loop offered-load sweep "
                         "(repro.obs.loadlab, virtual time) instead "
                         "of the periodic-arrival simulation")
    ap.add_argument("--load-fractions",
                    default="0.25,0.5,0.75,1.0,1.5,2.0",
                    help="offered load as fractions of the modeled "
                         "capacity (comma-separated)")
    ap.add_argument("--segments-at-capacity", type=int, default=1024,
                    help="virtual horizon, expressed as segments "
                         "offered by the 1.0x point")
    ap.add_argument("--urgent-fraction", type=float, default=0.125,
                    help="pinned URGENT cohort fraction for the "
                         "class-survival SLO")
    ap.add_argument("--arrival-process", default="poisson",
                    choices=["poisson", "trace"],
                    help="interarrival process for --load-sweep")
    ap.add_argument("--listen", default=None, metavar="HOST:PORT",
                    help="accept patient segments over the serving "
                         "frontend's socket transport")
    ap.add_argument("--connect", default=None, metavar="HOST:PORT",
                    help="open-loop socket client against a --listen "
                         "frontend (sends patients x segments at "
                         "--offered-rate)")
    ap.add_argument("--offered-rate", type=float, default=100.0,
                    help="with --connect: offered load in segments/s")
    ap.add_argument("--stream-rate", type=float, default=None,
                    help="with --listen: ROUTINE admission rate in "
                         "segments/s (past it segments defer, never "
                         "drop; default unbounded)")
    ap.add_argument("--json", action="store_true",
                    help="dump the full result record as JSON")
    ap.add_argument("--trace-out", default=None, metavar="PREFIX",
                    help="enable telemetry; on exit write PREFIX.jsonl "
                         "(event log) and PREFIX.json (Chrome/Perfetto "
                         "trace)")
    args = ap.parse_args()
    enable_compile_cache()
    if args.trace_out:
        # before the runner compiles so its jit cell registers with the
        # probe
        obs.configure(enabled=True)

    if args.connect:
        stream_connect(args)
        return
    buckets = tuple(sorted(int(b) for b in args.buckets.split(",")))
    mesh = make_data_mesh(args.devices)
    params = vadetect.init(jax.random.PRNGKey(args.seed))
    program = compiler.compile_model(params)
    if args.listen:
        stream_listen(args, program, buckets, mesh)
        return
    if args.load_sweep:
        stream_load_sweep(args, program, buckets, mesh)
        return
    cfg = FleetConfig(
        n_patients=args.patients,
        segments_per_patient=args.segments,
        seed=args.seed,
        va_fraction=args.va_fraction,
        jitter_frac=args.jitter,
        dropout=args.dropout,
        buckets=buckets,
        max_wait_s=args.max_wait,
        path=args.path,
    )
    out = simulate(cfg, program, mesh=mesh)
    if args.trace_out:
        out["telemetry"] = obs.telemetry_section()
        jsonl, chrome = obs.get().finish(args.trace_out)
        print(f"[obs] trace written: {jsonl} + {chrome}")
    if args.json:
        print(json.dumps(out, indent=1, default=str))
        return
    m, rt, chip = out["metrics"], out["realtime"], out["chip"]
    print(
        f"[stream] {args.patients} patients x {args.segments} segments, "
        f"buckets={list(buckets)}, devices={out['config']['n_devices']}, "
        f"path={args.path}"
    )
    print(
        f"[stream] segments={m['segments_total']} "
        f"batches={m['batches_total']} pad={m['pad_fraction']:.1%} "
        f"dropped={m['dropped_total']} "
        f"jit_cache_misses={out['jit_cache_misses']}"
    )
    print(
        f"[stream] wall {m['segments_per_s_wall']:.0f} seg/s "
        f"({rt['realtime_factor']:.1f}x the {rt['required_segments_per_s']:.0f} "
        f"seg/s real-time requirement); modeled chip fleet "
        f"{chip['modeled_fleet_segments_per_s']:.0f} seg/s"
    )
    if "deadline_slack_s" in m:
        sl = m["deadline_slack_s"]
        print(
            f"[stream] deadline slack p50={sl['p50']*1e3:.1f}ms "
            f"worst-1%={sl['worst_1pct']*1e3:.1f}ms "
            f"violations={sl['violations']}"
        )
    print(
        f"[stream] diagnoses={m['diagnoses_total']} "
        f"(VA={m['va_diagnoses_total']}) urgent-packed="
        f"{m['urgent_packed_total']} chip/segment="
        f"{chip['latency_us_per_segment']:.1f}us"
    )


if __name__ == "__main__":
    main()
