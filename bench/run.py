#!/usr/bin/env python3
"""Benchmark entry point: one cell of `BENCHMARK.json` on the chip.

  python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell names a configuration and a traffic mix; the harness finds them
by name: the configuration's sizes in the file `BENCHMARK.json` gives,
its reference and work counts in the module of the same name beside it,
the system that drives the program in `bench/systems/<system>.py` (the
configuration's `system` key), the mix in `bench/traffic/<mix>.json`,
and each per-layer metric's reader in `bench/metrics/<metric>.py`. The
limit of each number the check compares sits in the configuration's
file (`limits`), set from that configuration's own readings.

Set-up (process start to the first timed request) builds and warms only
this cell's shapes; then the window runs for `--seconds`; then the
program's state is freed and what the window produced is compared with
the plain reference. With `--trace 0` the result carries the cell's
end-to-end metrics; with `--trace 1` a profiler trace of a slice of the
window gives its per-layer metrics. The last stdout line is one JSON
object; the last stderr lines are the numbers compared, each beside its
limit. A run without a TPU, or on a chip the peaks table does not know,
exits non-zero and prints no result.
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import importlib.util
import json
import os
import sys
import tempfile
import time
from typing import Any, Optional

T_START = time.perf_counter()

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)

# seconds of the window the profiler records in a --trace 1 run
TRACE_SECONDS = 4.0


class SetupError(RuntimeError):
    """The run cannot start: no chip, an unknown chip, or a missing
    file. Reported on stderr with no result line."""


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or not os.path.exists(path):
        raise SetupError(f"no module at {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class CellSpec:
    """Everything one cell is made of, found by name."""

    name: str
    chips: int
    cfg: dict
    mix: dict
    ref: Any  # the configuration's reference module
    system: Any  # the module that drives the program
    end_to_end: list  # BENCHMARK.json entries this cell reports
    per_layer: list
    readers: dict  # per-layer metric name -> reader module
    limits: dict  # compared number -> limit, from the configuration


def _listed(metric: dict, cell: str, e2e_names: set) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    return metric.get("moves") in e2e_names if "moves" in metric else True


def resolve(cell: str, root: str = ROOT) -> CellSpec:
    """Load cell `cell` of `<root>/BENCHMARK.json` from its files."""
    path = os.path.join(root, "BENCHMARK.json")
    if not os.path.exists(path):
        raise SetupError(f"no BENCHMARK.json at {root}")
    with open(path) as f:
        bm = json.load(f)
    cells = {w["name"]: w for w in bm["workloads"]}
    if cell not in cells:
        raise SetupError(f"unknown workload {cell!r} (known: {sorted(cells)})")
    w = cells[cell]
    centry = {c["name"]: c for c in bm["configs"]}[w["config"]]
    with open(os.path.join(root, centry["file"])) as f:
        cfg = json.load(f)
    cfg_dir = os.path.dirname(os.path.join(root, centry["file"]))
    ref = load_module(os.path.join(cfg_dir, f"{centry['name']}.py"),
                      f"bench_config_{centry['name']}")
    system = load_module(
        os.path.join(root, "bench", "systems", f"{cfg['system']}.py"),
        f"bench_system_{cfg['system']}")
    bench_dir = os.path.join(root, "bench")
    with open(os.path.join(bench_dir, "traffic", f"{w['traffic']}.json")) as f:
        mix = json.load(f)
    e2e = [m for m in bm["end_to_end"] if _listed(m, cell, set())]
    names = {m["name"] for m in e2e}
    per_layer = [m for m in bm["per_layer"] if _listed(m, cell, names)]
    readers = {
        m["name"]: load_module(
            os.path.join(bench_dir, "metrics", f"{m['name']}.py"),
            "bench_metric_" + m["name"].replace(".", "_"))
        for m in per_layer
    }
    return CellSpec(cell, int(w["chips"]), cfg, mix, ref, system, e2e,
                    per_layer, readers, dict(cfg["limits"]))


class CompileCounter:
    """Counts executables the process builds or loads (every backend
    compile, the persistent cache's hits included), from JAX's own
    monitoring events."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax

        self.names: list[str] = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    @property
    def count(self) -> int:
        return len(self.names)

    def _on(self, event: str, duration: float, **kw) -> None:
        if event == self.EVENT:
            self.names.append(str(kw.get("fun_name", "?")))


class Tracer:
    """Records a profiler trace of a slice of the window: from
    `start_after` seconds after the window opens, for `length` seconds.
    The system under test calls `poll()` from its loop; `span(name)`
    writes a `TraceAnnotation` only while the profiler is on."""

    def __init__(self, out_dir: Optional[str], start_after: float = 0.0,
                 length: float = TRACE_SECONDS):
        self.out_dir = out_dir
        self.start_after = start_after
        self.length = length
        self.active = False
        self.done = False
        self.t_window = None
        self.t_on = self.t_off = None

    def start_window(self, t0: float) -> None:
        self.t_window = t0
        self.poll()

    def poll(self) -> None:
        if self.out_dir is None or self.done or self.t_window is None:
            return
        import jax

        t = time.perf_counter() - self.t_window
        if not self.active and t >= self.start_after:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(self.out_dir, profiler_options=opts)
            self.active = True
            self._window = jax.profiler.TraceAnnotation("bench/window")
            self._window.__enter__()
            self.t_on = time.perf_counter()
        elif self.active and t >= self.start_after + self.length:
            self.close()

    def close(self) -> None:
        if self.active:
            import jax

            self.t_off = time.perf_counter()
            self._window.__exit__(None, None, None)
            jax.profiler.stop_trace()
            self.active = False
        self.done = True

    @contextlib.contextmanager
    def span(self, name: str):
        if not self.active:
            yield
            return
        import jax

        with jax.profiler.TraceAnnotation(name):
            yield


@dataclasses.dataclass
class Context:
    """What a system's `Cell` gets."""

    cfg: dict
    mix: dict
    ref: Any
    seed: int
    seconds: float
    key: Any  # jax PRNG key made from the seed
    peak: dict
    limits: dict
    tracer: Tracer
    loadgen: Any
    faults: dict
    chips: int  # the cell's `chips`: the system builds its mesh over these

    def span(self, name: str):
        return self.tracer.span(name)

    def call_seed(self, i: int) -> int:
        """A 32-bit seed for the i-th piece of work drawn from the run's
        seed (i < 0 for set-up's own draws)."""
        import numpy as np

        return int(np.random.default_rng([self.seed, 11, i + 16])
                   .integers(0, 2**32 - 1))


def seed_key(seed: int):
    """A PRNG key from any non-negative seed (64-bit ones included)."""
    import jax

    key = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    return jax.random.fold_in(key, (seed >> 31) & 0x7FFFFFFF)


def device_info(chips: int) -> dict:
    """Platform, kind and count of JAX's devices; a `SetupError` unless
    they are TPUs, at least `chips` of them."""
    import jax

    devs = jax.devices()
    d0 = devs[0]
    if d0.platform != "tpu":
        raise SetupError(
            f"no TPU found: JAX sees {len(devs)} {d0.platform} device(s) "
            f"({d0.device_kind}); the benchmark runs on a TPU only")
    return devices_for(chips)


def devices_for(chips: int) -> dict:
    """Platform, kind and count of JAX's devices, of whatever platform;
    a `SetupError` unless there are at least `chips` of them."""
    import jax

    devs = jax.devices()
    if len(devs) < chips:
        raise SetupError(f"the cell needs {chips} chips, found {len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak_bytes() -> Optional[int]:
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.local_devices()]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


@dataclasses.dataclass
class Reading:
    """What a per-layer metric's `read(r)` gets."""

    counters: dict
    work: dict
    trace: Optional[dict]
    peak: dict
    end_to_end: dict
    chips: int  # the cell's `chips`, to divide work or peak by


def say(msg: str) -> None:
    print(f"[bench] {msg}", flush=True)


def run_cell(spec: CellSpec, seed: int, seconds: float, trace: bool, *,
             device: dict, peak: dict, faults: Optional[dict] = None,
             t_start: float = T_START, control: bool = False) -> dict:
    """Set up, run the window, check, and return the result object."""
    from bench import loadgen, trace_reduce

    tmp = tempfile.TemporaryDirectory(prefix="bench_trace_") if trace else None
    start_after = max(0.0, (seconds - TRACE_SECONDS) / 2)
    tracer = Tracer(tmp.name if tmp else None, start_after,
                    min(TRACE_SECONDS, seconds))
    ctx = Context(spec.cfg, spec.mix, spec.ref, seed, seconds,
                  seed_key(seed), peak, spec.limits, tracer, loadgen,
                  dict(faults or {}), spec.chips)
    cell = spec.system.Cell(ctx)
    cell.setup()
    counter = CompileCounter()
    setup_s = time.perf_counter() - t_start
    say(f"{spec.name}: set-up {setup_s:.3f}s")
    cell.run_window(seconds)
    compiles = counter.count
    mem = memory_peak_bytes()
    say(f"compiles inside the window: {compiles} "
        f"{sorted(set(counter.names)) if compiles else ''}")
    for line in cell.notes():
        say(line)
    reduced = None
    if trace:
        if device["platform"] == "tpu":
            reduced = trace_reduce.reduce_dir(tmp.name)
        else:  # a rehearsal off the chip: the trace has no device plane
            reduced = trace_reduce.empty(tracer.length)
        tmp.cleanup()
    cell.release()
    e2e = cell.end_to_end()
    checks = cell.check()
    controls = cell.control() if control else None
    for c in checks:
        c.setdefault("ok", c["value"] <= c["limit"])
    attempted, failed = cell.attempted_failed()
    if trace:
        reading = Reading(cell.counters(), cell.work(), reduced, peak, e2e,
                          spec.chips)
        metrics = {}
        for m in spec.per_layer:
            v = spec.readers[m["name"]].read(reading)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    else:
        metrics = {m["name"]: {"value": float(e2e[m["name"]]),
                               "unit": m["unit"]}
                   for m in spec.end_to_end
                   if m["name"] in e2e}
        metrics["setup_s"] = {"value": setup_s, "unit": "s"}
    dev = dict(device)
    dev["memory_peak_bytes"] = mem
    out = {
        "correct": all(c["ok"] for c in checks),
        "attempted": attempted,
        "failed": failed,
        "metrics": metrics,
        "device": dev,
    }
    if trace:
        dev["busy_s"] = reduced["busy_s"]
        dev["window_s"] = reduced["window_s"]
        out["breakdown"] = {"device_ops": reduced["device_ops"],
                            "idle_gaps": reduced["idle_gaps"]}
    out["checks"] = {c["name"]: {"value": c["value"], "limit": c["limit"]}
                     for c in checks}
    out["_checks"] = checks
    if controls is not None:
        out["_control"] = controls
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        print("[bench] --seed must be non-negative", file=sys.stderr)
        return 2
    for p in (os.path.join(ROOT, "src"), ROOT):
        if p not in sys.path:
            sys.path.insert(0, p)
    try:
        spec = resolve(args.workload)
        from repro.launch.compile_cache import enable_compile_cache
    except (SetupError, ImportError, OSError, KeyError) as e:
        print(f"[bench] cannot set up {args.workload}: {e}", file=sys.stderr)
        return 2
    cache_dir = enable_compile_cache()
    import jax

    from bench import peaks

    # every executable goes to the cache, however fast it compiled
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    try:
        device = device_info(spec.chips)
        peak = peaks.peaks_for(device["kind"])
    except (SetupError, peaks.UnknownDeviceKind) as e:
        print(f"[bench] {e}", file=sys.stderr)
        return 2
    say(f"{device['kind']} x {device['count']}; compile cache {cache_dir}")
    out = run_cell(spec, args.seed, args.seconds, bool(args.trace),
                   device=device, peak=peak)
    checks = out.pop("_checks")
    for c in checks:
        print(f"[bench] check {c['name']}: {c['value']!r} limit "
              f"{c['limit']!r} {'ok' if c['ok'] else 'FAILED'} "
              f"({c.get('what', '')})", file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
