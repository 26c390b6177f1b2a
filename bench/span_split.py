#!/usr/bin/env python3
"""Split a profiler trace's host time and device idle among the
program's own spans.

The program opens its spans (`stream/*` in the fleet loop, `serve/*` in
the engine's tick) through `repro.obs`; with or without telemetry each
one is a `jax.profiler.TraceAnnotation`, so a trace holds them on its
host planes, on the device trace's clock, beside the harness's `bench/`
spans. For each span name, within the window (`bench/window`, else the
device's extent):

- `count`, `total_s`;
- `self_s`: its time less that of the spans nested in it on the same
  host line;
- `idle_s`: time in it in which no operation ran on the first device;
  `self_idle_s`: the part of that idle in which it was the innermost
  span. Each idle interval is split across the spans it crosses, so the
  `self_idle_s` of all spans and `outside_s` add up to the window's idle.

`metrics()` turns a split and the window's host-read counts into the
per-layer numbers the fleet loop and the decode tick would report.

The benchmark's own reduction (`bench/trace_reduce.py`) keeps only the
harness's spans, so this runs one traced run of a cell beside it:

  python3 bench/span_split.py --workload <cell> --seed <n> --seconds <s>

prints the run's result line, then one JSON line with the split, the
idle gaps named by their innermost span, the host reads and `metrics()`.
"""

from __future__ import annotations

import bisect
import json
import os
import sys
from collections import defaultdict

PREFIXES = ("stream/", "serve/", "bench/")
OUTSIDE = "outside spans"
TOP = 10


def read_spans(path: str) -> list:
    """Host events named with one of `PREFIXES`, as (name, start_ns,
    end_ns, line) with `line` naming the plane and line they sit on."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for k, line in enumerate(plane.lines):
            key = f"{plane.name}#{k}"
            out.extend((e.name, float(e.start_ns), float(e.end_ns), key)
                       for e in line.events if e.name.startswith(PREFIXES))
    return out


class _Busy:
    """Busy time of a merged, sorted interval list inside [a, b]."""

    def __init__(self, merged: list):
        self.starts = [s for s, _ in merged]
        self.ends = [e for _, e in merged]
        self.cum = [0.0]
        for s, e in merged:
            self.cum.append(self.cum[-1] + e - s)

    def __call__(self, a: float, b: float) -> float:
        if b <= a:
            return 0.0
        i = bisect.bisect_right(self.ends, a)  # first interval ending > a
        j = bisect.bisect_left(self.starts, b)  # first starting >= b
        if j <= i:
            return 0.0
        total = self.cum[j] - self.cum[i]
        total -= max(0.0, a - self.starts[i])
        total -= max(0.0, self.ends[j - 1] - b)
        return total


def _self_intervals(events: list) -> list:
    """(name, start, end, self pieces) of nested events on one line."""
    events = sorted(events, key=lambda e: (e[1], -e[2]))
    out = [[name, s, e, []] for name, s, e in events]
    children = [[] for _ in out]
    stack: list = []
    for i, (_, s, e, _) in enumerate(out):
        while stack and out[stack[-1]][2] <= s:
            stack.pop()
        if stack:
            children[stack[-1]].append(i)
        stack.append(i)
    for i, ev in enumerate(out):
        t = ev[1]
        for c in children[i]:
            if out[c][1] > t:
                ev[3].append((t, out[c][1]))
            t = max(t, out[c][2])
        if ev[2] > t:
            ev[3].append((t, ev[2]))
    return out


def split(busy_intervals: list, spans: list, lo: float, hi: float) -> dict:
    """The split of [lo, hi] (ns) among `spans` (`read_spans`), against
    the device's merged busy intervals."""
    from bench.trace_reduce import WINDOW_SPAN, clip

    busy = _Busy(clip(busy_intervals, lo, hi))
    lines = defaultdict(list)
    for name, s, e, line in spans:
        if name != WINDOW_SPAN and e > lo and s < hi:
            lines[line].append((name, max(s, lo), min(e, hi)))
    per = defaultdict(lambda: {"count": 0, "total_s": 0.0, "self_s": 0.0,
                               "idle_s": 0.0, "self_idle_s": 0.0})
    innermost = []  # (start, end, name): the innermost span at each instant
    for events in lines.values():
        for name, s, e, pieces in _self_intervals(events):
            d = per[name]
            d["count"] += 1
            d["total_s"] += (e - s) * 1e-9
            d["idle_s"] += ((e - s) - busy(s, e)) * 1e-9
            for a, b in pieces:
                d["self_s"] += (b - a) * 1e-9
                d["self_idle_s"] += ((b - a) - busy(a, b)) * 1e-9
                innermost.append((a, b, name))
    window_idle = (hi - lo) - busy(lo, hi)
    named = sum(d["self_idle_s"] for d in per.values())
    return {
        "window_s": (hi - lo) * 1e-9,
        "idle_s": window_idle * 1e-9,
        "outside_s": window_idle * 1e-9 - named,
        "spans": dict(per),
        "idle_gaps": _gaps(busy_intervals, innermost, lo, hi),
    }


def _gaps(busy_intervals: list, innermost: list, lo: float,
          hi: float) -> list:
    """The longest idle gaps of the device, each with its seconds in
    each span it crosses (the innermost span at each instant), and named
    by the span that holds most of it."""
    from bench.trace_reduce import clip

    edges = [lo] + [x for iv in clip(busy_intervals, lo, hi) for x in iv]
    edges.append(hi)
    gaps = sorted(((edges[i + 1] - edges[i], edges[i], edges[i + 1])
                   for i in range(0, len(edges), 2)
                   if edges[i + 1] > edges[i]), reverse=True)[:TOP]
    out = []
    for length, g0, g1 in gaps:
        parts = defaultdict(float)
        for a, b, name in innermost:
            if a < g1 and b > g0:
                parts[name] += (min(b, g1) - max(a, g0)) * 1e-9
        rest = length * 1e-9 - sum(parts.values())
        if rest > 1e-12:
            parts[OUTSIDE] += rest
        parts = dict(sorted(parts.items(), key=lambda kv: -kv[1]))
        out.append({"span": next(iter(parts)), "s": length * 1e-9,
                    "parts": parts})
    return out


def reduce_file(path: str) -> dict:
    """`split` of a trace file over the window `trace_reduce` uses."""
    from bench import trace_reduce as T

    raw = T.read(path)
    devices = raw["devices"]
    ops = devices[sorted(devices)[0]].get(T.OPS_LINE, []) if devices else []
    busy = T.merge([[s, e] for _, s, e in ops])
    win = [s for s in raw["spans"] if s[0] == T.WINDOW_SPAN]
    if win:
        lo, hi = win[0][1], win[0][2]
    elif busy:
        lo, hi = busy[0][0], busy[-1][1]
    else:
        raise ValueError(f"{path}: no window span and no device events")
    return split(busy, read_spans(path), lo, hi)


def metrics(sp: dict, host_reads: dict) -> dict:
    """Per-layer numbers of the fleet loop and the decode tick, from a
    split (`spans`) and the window's host reads; a number whose spans or
    counts the split lacks is left out."""
    S = sp["spans"]

    def ms(name, key, per):  # milliseconds of `key` per `per` span
        if name in S and S.get(per, {}).get("count"):
            return 1e3 * S[name][key] / S[per]["count"]
        return None

    def idle_pct(name):
        if S.get(name, {}).get("total_s"):
            return 100.0 * S[name]["idle_s"] / S[name]["total_s"]
        return None

    def reads_per(unit):
        n = host_reads.get(unit)
        return host_reads["reads"] / n if n else None

    out = {
        "va_admit_ms": ms("stream/admit", "self_s", "stream/pack"),
        "va_pack_ms": ms("stream/pack", "self_s", "stream/pack"),
        "va_gather_ms": ms("stream/gather", "self_s", "stream/pack"),
        "va_sync_ms": ms("stream/sync", "self_s", "stream/pack"),
        "va_loop_idle_pct": idle_pct("stream/step"),
        "va_host_reads_per_batch": reads_per("batches"),
        "lm_emit_ms": ms("serve/emit", "self_s", "serve/tick"),
        "lm_admit_ms": ms("serve/admission", "total_s", "serve/tick"),
        "lm_tick_idle_pct": idle_pct("serve/tick"),
        "lm_host_reads_per_tick": reads_per("ticks"),
    }
    return {k: v for k, v in out.items() if v is not None}


def _count_host_reads(stats: dict) -> None:
    """Count the program's host reads inside the benchmark's window:
    `FleetMetrics.host_reads_total` of each `simulate` call and
    `Engine.host_reads` across each tick."""
    import repro.stream as S
    from bench import run
    from repro.serve.engine import Engine

    start, close = run.Tracer.start_window, run.Tracer.close
    simulate, tick = S.simulate, Engine.tick

    def start_window(self, t0):
        stats.update(on=True, reads=0, batches=0, ticks=0)
        start(self, t0)

    def close_window(self):
        stats["on"] = False
        close(self)

    def counted_simulate(*a, **k):
        out = simulate(*a, **k)
        if stats.get("on"):
            stats["reads"] += out["metrics"].get("host_reads_total", 0)
            stats["batches"] += out["metrics"]["batches_total"]
        return out

    def counted_tick(self):
        before = getattr(self, "host_reads", 0)
        n = tick(self)
        if stats.get("on"):
            stats["reads"] += getattr(self, "host_reads", 0) - before
            stats["ticks"] += 1
        return n

    run.Tracer.start_window, run.Tracer.close = start_window, close_window
    S.simulate, Engine.tick = counted_simulate, counted_tick


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    for p in (os.path.join(root, "src"), root):
        if p not in sys.path:
            sys.path.insert(0, p)
    from bench import run, trace_reduce

    got: dict = {}
    reads: dict = {}
    reduce_dir = trace_reduce.reduce_dir

    def split_too(trace_dir):
        got.update(reduce_file(trace_reduce.find_xplane(trace_dir)))
        return reduce_dir(trace_dir)

    trace_reduce.reduce_dir = split_too
    _count_host_reads(reads)
    rc = run.main(["--workload", args.workload, "--seed", str(args.seed),
                   "--seconds", str(args.seconds), "--trace", "1"])
    if got:
        reads.pop("on", None)
        print(json.dumps({"split": got, "host_reads": reads,
                          "metrics": metrics(got, reads)}), flush=True)
    return rc


if __name__ == "__main__":
    sys.exit(main())
