"""Reduce a JAX profiler trace (`.xplane.pb`) to the numbers the
per-layer metrics read.

- The window is the host span `bench/window` that the harness opens
  while the profiler runs (else the extent of the device's events).
- Busy time is the union of the intervals in which an operation ran on a
  device (the `XLA Ops` line of each `/device:TPU:<n>` plane), clipped
  to the window and averaged over the devices; idle is the rest.
- Device time per executable sums the events of the `XLA Modules` line
  by module name, the trailing `(<id>)` dropped: `jit_<function>` as
  JAX names a jitted function.
- Device time per operation is keyed `<module>/<op>`, the op being the
  name its HLO text gives it (`fusion.28`).
- Collective time per executable is the union of the events of the
  collective ops, clipped to the window and averaged over the devices:
  the exposed time of those ops' own events. An op is collective when
  its name starts with a collective's (`all-reduce.3`,
  `all-gather-start.1`, `collective-permute-done`, and XLA:TPU's
  `async-collective-start`/`-done` fusions), or when it is a fusion
  that calls a computation so named (`fusion.168` calling
  `%all-reduce-scatter.3`). An asynchronous collective in flight
  between its start and done while other ops run is not in it, nor is
  a compute fusion that overlaps one (`calls=%async_collective_fusion.N`).
- Busy time of each device is kept by its plane's name.
- The longest idle gaps on the first device are attributed to the
  innermost harness span (`bench/...` on a host thread) open when the
  gap began.
"""

from __future__ import annotations

import bisect
import glob
import os
import re
from collections import defaultdict
from typing import Optional

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench/"
WINDOW_SPAN = "bench/window"
TOP = 10
COLLECTIVE_PREFIXES = ("all-gather", "all-reduce", "reduce-scatter",
                       "collective-permute", "all-to-all",
                       "async-collective-")

_ID_SUFFIX = re.compile(r"\(\d+\)$")
_CALLS = re.compile(r"\bcalls=%?([\w.\-]+)")


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def module_name(event_name: str) -> str:
    return _ID_SUFFIX.sub("", event_name.strip())


def op_name(event_name: str) -> str:
    """`fusion.28` from an op event named by its HLO text
    (`%fusion.28 = bf16[...] fusion(...)`)."""
    return event_name.split(" = ", 1)[0].strip().lstrip("%")


def is_collective(event_name: str) -> bool:
    """Whether an op event, named by its HLO text, is a collective: by
    its own name or by the computation it calls."""
    if op_name(event_name).startswith(COLLECTIVE_PREFIXES):
        return True
    called = _CALLS.search(event_name)
    return bool(called) and called.group(1).startswith(COLLECTIVE_PREFIXES)


def merge(intervals: list) -> list:
    """Union of (start, end) intervals, sorted and disjoint."""
    out: list = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def clip(intervals: list, lo: float, hi: float) -> list:
    return [[max(s, lo), min(e, hi)] for s, e in intervals
            if e > lo and s < hi]


def _events(line):
    return [(e.name, float(e.start_ns), float(e.end_ns))
            for e in line.events]


def read(path: str) -> dict:
    """Planes of the trace as plain lists: {"devices": {plane: {line:
    [(name, start_ns, end_ns)]}}, "spans": [(name, start_ns, end_ns)]}."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    devices, spans = {}, []
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            devices[plane.name] = {line.name: _events(line)
                                   for line in plane.lines}
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend(ev for ev in _events(line)
                             if ev[0].startswith(SPAN_PREFIX))
    return {"devices": devices, "spans": spans}


def reduce(raw: dict) -> dict:
    """Busy and idle time (averaged and per device), device time per
    executable, per op and in collectives, and the longest idle gaps
    with the span open at each."""
    devices, spans = raw["devices"], raw["spans"]
    if not devices:
        raise ValueError("the trace holds no TPU device plane")
    win = [s for s in spans if s[0] == WINDOW_SPAN]
    if win:
        lo, hi = win[0][1], win[0][2]
    else:
        ends = [(s, e) for lines in devices.values()
                for evs in lines.values() for _, s, e in evs]
        lo, hi = min(s for s, _ in ends), max(e for _, e in ends)
    window_ns = hi - lo
    busy, per_module, per_op = [], defaultdict(float), defaultdict(float)
    per_coll = defaultdict(float)
    first = None
    for name in sorted(devices):
        lines = devices[name]
        ops = lines.get(OPS_LINE, [])
        merged = clip(merge([[s, e] for _, s, e in ops]), lo, hi)
        busy.append(sum(e - s for s, e in merged))
        if first is None:
            first = merged
        mods = sorted(lines.get(MODULES_LINE, []), key=lambda m: m[1])
        starts = [m[1] for m in mods]
        coll = defaultdict(list)
        for op, s, e in ops:
            if e > lo and s < hi:
                i = bisect.bisect_right(starts, s) - 1
                mod = (module_name(mods[i][0])
                       if i >= 0 and s < mods[i][2] else "")
                per_op[(mod + "/" if mod else "") + op_name(op)] += (
                    (min(e, hi) - max(s, lo)) / len(devices))
                if is_collective(op):
                    coll[mod].append([s, e])
        for mod, ivs in coll.items():
            per_coll[mod] += sum(
                e - s for s, e in clip(merge(ivs), lo, hi)) / len(devices)
        for mod, s, e in mods:
            if e > lo and s < hi:
                per_module[module_name(mod)] += (
                    (min(e, hi) - max(s, lo)) / len(devices))
    gaps = []
    edges = [lo] + [x for iv in first for x in iv] + [hi]
    for i in range(0, len(edges), 2):
        g0, g1 = edges[i], edges[i + 1]
        if g1 > g0:
            gaps.append((g1 - g0, _open_span(spans, g0)))
    gaps.sort(key=lambda g: -g[0])
    busy_ns = sum(busy) / len(busy)
    return {
        "window_s": window_ns * 1e-9,
        "busy_s": busy_ns * 1e-9,
        "idle_share": 1.0 - busy_ns / window_ns if window_ns else None,
        "executables_s": {k: v * 1e-9 for k, v in per_module.items()},
        "collectives_s": {k: v * 1e-9 for k, v in per_coll.items()},
        "busy_by_device_s": {name: ns * 1e-9
                             for name, ns in zip(sorted(devices), busy)},
        "device_ops": [[k, v * 1e-9] for k, v in
                       sorted(per_op.items(), key=lambda kv: -kv[1])[:TOP]],
        "idle_gaps": [[name, ns * 1e-9] for ns, name in gaps[:TOP]],
        "n_devices": len(devices),
    }


def _open_span(spans: list, t: float) -> str:
    """Innermost harness span (latest start) open at time `t`."""
    best: Optional[tuple] = None
    for name, s, e in spans:
        if name != WINDOW_SPAN and s <= t < e and (best is None
                                                    or s > best[1]):
            best = (name, s)
    return best[0] if best else "outside bench spans"


def empty(window_s: float) -> dict:
    """The reduction of a trace with no device plane (a rehearsal of
    the harness off the chip): nothing ran on a device."""
    return {"window_s": window_s, "busy_s": 0.0, "idle_share": None,
            "executables_s": {}, "collectives_s": {},
            "busy_by_device_s": {}, "device_ops": [], "idle_gaps": [],
            "n_devices": 0}


def describe(raw: dict) -> str:
    """Planes, lines and event counts of a read trace (for a trace the
    reduction cannot use)."""
    out = []
    for plane, lines in sorted(raw["devices"].items()):
        out.append(f"{plane}: " + ", ".join(
            f"{name} ({len(evs)})" for name, evs in sorted(lines.items())))
    out.append(f"host bench spans: {len(raw['spans'])}")
    return "\n".join(out)


def reduce_dir(trace_dir: str) -> dict:
    raw = read(find_xplane(trace_dir))
    try:
        return reduce(raw)
    except (ValueError, KeyError, IndexError) as e:
        raise ValueError(f"cannot reduce the trace ({e}); it holds:\n"
                         + describe(raw)) from e
