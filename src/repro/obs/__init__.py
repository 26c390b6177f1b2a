"""repro.obs — unified telemetry: metrics, traces, compile visibility.

One `Telemetry` object per process bundles the three probes every
subsystem shares:

  * `registry` — counters / gauges / mergeable log-bucket histograms
    (`obs.registry`): O(buckets) tail latency (p50/p99/p99.9) at fleet
    scale;
  * `tracer`   — structured spans with a JSONL event log and a
    Chrome/Perfetto export (`obs.trace`), virtual-time aware;
  * `probe`    — per-compiled-cell jit recompile tracking, bounded step
    timing, device-memory gauges (`obs.jaxprobe`).

The hot paths (trainer step loop, stream fleet loop, serve engine
admission/tick) call `obs.get()` each time and emit unconditionally;
the **default telemetry is disabled**: registry and tracer emissions
are no-ops costing nanoseconds, and a span is a bare
`jax.profiler.TraceAnnotation` (about a microsecond; bounds asserted in
`tests/test_obs.py`), so the instrumentation has no off-switch to
forget and no measurable tax when off. The annotation is what puts the
program's spans into a `jax.profiler` trace, on the device's clock,
whether telemetry is on or off. Launchers enable telemetry behind
`--trace-out`, benchmarks always enable it and attach
`telemetry_section()` to their BENCH records. Hot-path call sites build
span attributes only under `tel.enabled`: the annotation takes none.

Usage:

    from repro import obs

    tel = obs.configure(enabled=True)        # launchers / benchmarks
    with obs.get().span("train/step", step=i):
        ...
    obs.get().registry.histogram("train.step_latency_s").observe(dt)
    tel.finish("/tmp/run")   # -> /tmp/run.jsonl + /tmp/run.json
    obs.reset()              # back to the disabled default
"""

from __future__ import annotations

from jax.profiler import TraceAnnotation

from repro.obs.jaxprobe import (
    NULL_PROBE,
    JitProbe,
    device_memory_bytes,
    jit_cache_size,
    observe_memory,
    timed_call,
)
from repro.obs.registry import (
    NULL_COUNTER,
    NULL_GAUGE,
    NULL_HISTOGRAM,
    Counter,
    Gauge,
    Histogram,
    Registry,
    latency_bounds,
    signed_bounds,
)
from repro.obs.lineage import (
    assert_joined,
    critical_path,
    join_lineage,
    serve_rid,
    stream_rid,
)
from repro.obs.trace import (
    NULL_SPAN,
    NULL_TRACER,
    Tracer,
    validate_chrome,
    validate_event,
    validate_jsonl,
)

SCHEMA_VERSION = 1


class Telemetry:
    """Registry + tracer + jit probe with one shared enabled flag."""

    __slots__ = ("enabled", "registry", "tracer", "probe")

    def __init__(self, enabled: bool = True):
        self.enabled = enabled
        self.registry = Registry(enabled=enabled)
        self.tracer = Tracer(enabled=enabled) if enabled else NULL_TRACER
        self.probe = JitProbe(enabled=enabled) if enabled else NULL_PROBE

    # hot-path conveniences ---------------------------------------------

    def span(self, name: str, cat: str = "app", **attrs):
        """A span of the JSONL tracer (which also annotates the
        profiler's trace) when enabled; else the bare profiler
        annotation, recorded only while a `jax.profiler` session runs.
        Attributes go to the JSONL tracer alone."""
        if self.enabled:
            return self.tracer.span(name, cat, **attrs)
        return TraceAnnotation(name)

    def block(self, x):
        """`jax.block_until_ready(x)` only when telemetry is enabled —
        span durations then bound device work, while the disabled path
        never serializes the async pipeline."""
        if self.enabled:
            import jax

            jax.block_until_ready(x)
        return x

    # lifecycle ----------------------------------------------------------

    def finish(self, out_prefix: str) -> tuple[str, str]:
        """Write the JSONL event log and the Chrome/Perfetto export:
        `<out_prefix>.jsonl` + `<out_prefix>.json`. Returns the two
        paths."""
        jsonl = out_prefix + ".jsonl"
        chrome = out_prefix + ".json"
        self.tracer.write_jsonl(jsonl)
        self.tracer.export_chrome(chrome)
        return jsonl, chrome


_DISABLED = Telemetry(enabled=False)
_current = _DISABLED


def get() -> Telemetry:
    """The process-wide telemetry (disabled no-op by default)."""
    return _current


def configure(enabled: bool = True) -> Telemetry:
    """Install (and return) a fresh process-wide Telemetry. Call
    *before* constructing engines/runners so their compiled cells
    register with the probe."""
    global _current
    _current = Telemetry(enabled=enabled)
    return _current


def install(tel: Telemetry) -> Telemetry:
    """Re-install a previously captured Telemetry (e.g. after an A/B
    overhead measurement swapped in throwaway instances)."""
    global _current
    _current = tel
    return _current


def reset() -> None:
    """Back to the shared disabled default (test teardown)."""
    global _current
    _current = _DISABLED


def telemetry_section(tel: Telemetry | None = None) -> dict:
    """The shared BENCH `telemetry` schema — identical across
    BENCH_dist / BENCH_stream / BENCH_decode:

      {
        "schema_version": 1,
        "enabled": bool,
        "counters":   {name: int},
        "gauges":     {name: {"value", "peak"}},
        "histograms": {name: {count,sum,min,max,mean,
                              p50,p90,p99,p999,layout,
                              n_buckets,nonzero_buckets}},
        "recompiles": {cell name: compiled-variant count},
        "peak_device_memory_bytes": int,
      }

    Benchmarks may add an "overhead" sub-record (the stream benchmark
    records its measured enabled-vs-disabled wall delta there)."""
    tel = tel or get()
    if tel.enabled:
        observe_memory(tel.registry)
    snap = tel.registry.snapshot()
    mem = snap["gauges"].get("jax.device_bytes", {})
    return {
        "schema_version": SCHEMA_VERSION,
        "enabled": tel.enabled,
        **snap,
        "recompiles": tel.probe.cache_sizes(),
        "peak_device_memory_bytes": int(mem.get("peak") or 0),
    }


__all__ = [
    "SCHEMA_VERSION",
    "Telemetry",
    "configure",
    "get",
    "install",
    "reset",
    "telemetry_section",
    # registry
    "Counter",
    "Gauge",
    "Histogram",
    "Registry",
    "latency_bounds",
    "signed_bounds",
    "NULL_COUNTER",
    "NULL_GAUGE",
    "NULL_HISTOGRAM",
    # lineage
    "assert_joined",
    "critical_path",
    "join_lineage",
    "serve_rid",
    "stream_rid",
    # trace
    "Tracer",
    "NULL_SPAN",
    "NULL_TRACER",
    "validate_chrome",
    "validate_event",
    "validate_jsonl",
    # jaxprobe
    "JitProbe",
    "NULL_PROBE",
    "device_memory_bytes",
    "jit_cache_size",
    "observe_memory",
    "timed_call",
]
