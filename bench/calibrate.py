#!/usr/bin/env python3
"""Measurements that set the benchmark's fixed numbers, run on the chip
and not by the benchmark's own runs.

  python bench/calibrate.py readings --workload <cell> --seeds 1,2,3 --seconds 20
      one process, one run of the cell per seed: the compared numbers of
      the program and, on the same sampled outputs, of the control (the
      reference in the precision below the configuration's), one JSON
      line per seed. The limits (`limits` in the
      configuration's file) are set from them.

  python bench/calibrate.py sweep --workload <cell> --rates 2,3,4 --seconds 20
      an LM cell set up once, then one window per offered rate: tokens/s,
      first-token latency and the backlog left at the close, one JSON line
      per rate. The knee is the highest rate held without a backlog that
      grows; the mixes' rates are set from it.

Lines also go to `chiprun_out/calibrate/<cell>.<mode>.jsonl`.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
for p in (os.path.join(ROOT, "src"), ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import run as R  # noqa: E402


def _emit(path: str, rec: dict) -> None:
    line = json.dumps(rec)
    print(line, flush=True)
    with open(path, "a") as f:
        f.write(line + "\n")


def readings(spec, seeds, seconds, device, peak, path) -> None:
    for seed in seeds:
        t0 = time.perf_counter()
        out = R.run_cell(spec, seed, seconds, False, device=device,
                         peak=peak, t_start=t0, control=True)
        _emit(path, {
            "seed": seed, "correct": out["correct"],
            "program": {k: v["value"] for k, v in out["checks"].items()},
            "control": out["_control"],
            "metrics": {k: v["value"] for k, v in out["metrics"].items()},
        })


def sweep(spec, rates, seconds, device, peak, path) -> None:
    from bench import loadgen

    ctx = R.Context(spec.cfg, spec.mix, spec.ref, 1, seconds,
                    R.seed_key(1), peak, spec.limits, R.Tracer(None),
                    loadgen, {}, spec.chips)
    cell = spec.system.Cell(ctx)
    cell.setup()
    for i, rate in enumerate(rates):
        mix = dict(spec.mix, arrivals=dict(spec.mix["arrivals"],
                                           rate_per_s=rate))
        cell.reschedule(mix, seed=100 + i)
        cell.tracer = R.Tracer(None)
        cell.run_window(seconds)
        c, e2e = cell.counters(), cell.end_to_end()
        half = cell.backlog_at(seconds / 2)
        _emit(path, {"rate_per_s": rate, **e2e, "backlog_half": half,
                     "backlog_end": c["submitted"] - c["started"],
                     "unfinished": c["submitted"] - c["finished"],
                     "occupancy": c["active_sum"] / max(1, c["ticks"])})
        cell.drain()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("mode", choices=("readings", "sweep"))
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="1")
    ap.add_argument("--rates", default="")
    ap.add_argument("--seconds", type=float, default=20.0)
    args = ap.parse_args(argv)
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    import jax

    from bench import peaks

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    spec = R.resolve(args.workload)
    device = R.device_info(spec.chips)
    peak = peaks.peaks_for(device["kind"])
    out_dir = os.path.join(ROOT, "chiprun_out", "calibrate")
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(out_dir, f"{args.workload}.{args.mode}.jsonl")
    if args.mode == "readings":
        readings(spec, [int(s) for s in args.seeds.split(",")],
                 args.seconds, device, peak, path)
    else:
        sweep(spec, [float(r) for r in args.rates.split(",")],
              args.seconds, device, peak, path)
    return 0


if __name__ == "__main__":
    sys.exit(main())
