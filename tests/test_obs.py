"""repro.obs: registry/histogram properties, tracer round-trip, jit
recompile guards, and the disabled-no-op / enabled-overhead contracts.

The histogram merge property and the two recompile regression guards
are the ISSUE-mandated satellites: merging per-shard histograms must be
bucket-exact vs the histogram of the concatenated samples, and the
stream classify cells / decode-engine admission cells must show zero
jit cache misses after warmup (the probe's `new_misses` diff).
"""

import gc
import json
import math
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro import configs, obs
from repro.core import compiler, vadetect
from repro.models import api
from repro.obs.registry import PER_DECADE, Histogram
from repro.serve import engine as E
from repro.serve.paging import PagingConfig, validate_page_size
from repro.stream import FleetConfig, FleetRunner, simulate


@pytest.fixture(autouse=True)
def _reset_obs():
    """Every test leaves the process-wide telemetry at the disabled
    default — an enabled registry leaking across tests would skew the
    no-op timing assertions and pin jit caches."""
    yield
    obs.reset()


@pytest.fixture(scope="module")
def program():
    params = vadetect.init(jax.random.PRNGKey(0))
    return compiler.compile_model(params)


# ---------------------------------------------------------------------------
# histogram: merge property + quantile error bound
# ---------------------------------------------------------------------------

# one log-spaced bucket spans a ratio of r; the rank-interpolated
# quantile can land anywhere in the bucket holding the rank, and the
# empirical quantile convention can differ by at most one more bucket
_R = 10.0 ** (1.0 / PER_DECADE)
_QUANTILE_RATIO = _R**2


@settings(max_examples=25, deadline=None)
@given(
    n_shards=st.integers(1, 6),
    seed=st.integers(0, 2**31 - 1),
    log_scale=st.floats(min_value=-5.0, max_value=2.0),
    spread=st.floats(min_value=0.1, max_value=2.5),
)
def test_histogram_merge_is_bucket_exact(n_shards, seed, log_scale,
                                         spread):
    """Merging per-shard histograms == histogram of the concatenated
    samples, bit-exact in every bucket; the merged quantile is within
    one bucket ratio of the exact sorted-sample quantile."""
    rng = np.random.RandomState(seed)
    shards = [
        rng.lognormal(mean=log_scale * math.log(10.0), sigma=spread,
                      size=rng.randint(1, 400))
        for _ in range(n_shards)
    ]
    all_samples = np.concatenate(shards)

    per_shard = []
    for s in shards:
        h = Histogram("t", "latency")
        h.observe_array(s)
        per_shard.append(h)
    merged = Histogram.merged(per_shard)

    whole = Histogram("t", "latency")
    whole.observe_array(all_samples)

    # bucket-exact: same counts array, same exact count/sum/min/max
    np.testing.assert_array_equal(merged.counts, whole.counts)
    assert merged.count == whole.count == all_samples.size
    assert merged.sum == pytest.approx(whole.sum)
    assert merged.min == whole.min and merged.max == whole.max

    # quantile error bounded by the (log-spaced) bucket width
    srt = np.sort(all_samples)
    for q in (0.5, 0.9, 0.99):
        exact = float(srt[min(int(math.ceil(q * srt.size)) - 1,
                              srt.size - 1)])
        est = merged.quantile(q)
        assert est == whole.quantile(q)  # merge preserves quantiles
        if exact > 0:
            assert exact / _QUANTILE_RATIO <= est <= \
                exact * _QUANTILE_RATIO, (q, est, exact)


def test_histogram_merge_rejects_layout_mismatch():
    with pytest.raises(ValueError, match="layout mismatch"):
        Histogram("a", "latency").merge(Histogram("b", "signed"))


def test_signed_histogram_exact_zero_split():
    """The signed layout keeps 0 an explicit edge so deadline-slack
    violations (samples <= 0) are counted exactly, not re-bucketed."""
    rng = np.random.RandomState(7)
    xs = np.concatenate([
        rng.uniform(-5e-3, 5e-3, size=500),
        np.zeros(17),  # exactly-on-time segments land at the 0 edge
    ])
    h = Histogram("slack", "signed")
    h.observe_array(xs)
    assert h.count_at_or_below(0.0) == int((xs <= 0).sum())
    assert h.min == xs.min() and h.max == xs.max()


# ---------------------------------------------------------------------------
# tracer: JSONL + Chrome round-trip
# ---------------------------------------------------------------------------


def test_trace_roundtrip_and_virtual_track(tmp_path):
    tel = obs.configure(enabled=True)
    with tel.span("stream/flush", cat="stream", bucket=32,
                  v_ts_s=1.5, v_dur_s=0.25):
        with tel.span("stream/classify", cat="stream"):
            pass
    tel.tracer.instant("fleet/start", cat="stream", patients=4)
    tel.tracer.counter("queue_depth", 3.0, cat="stream")

    jsonl, chrome = tel.finish(str(tmp_path / "t"))
    assert obs.validate_jsonl(jsonl) == 4
    # 4 events + 2 process-name metadata + 1 virtual-time mirror
    assert obs.validate_chrome(chrome) == 7

    doc = json.load(open(chrome))
    virt = [e for e in doc["traceEvents"]
            if e.get("pid") == 1 and e.get("ph") == "X"]
    assert len(virt) == 1
    assert virt[0]["ts"] == pytest.approx(1.5e6)
    assert virt[0]["dur"] == pytest.approx(0.25e6)


def test_span_stack_is_thread_local():
    """Parent/child edges from worker threads: each thread keeps its
    own open-span stack, so a child opened on thread B while thread A
    also has a span open parents to B's outer span — never across
    threads. Lineage joining (repro.obs.lineage) trusts these edges,
    and a process-global stack would interleave them arbitrarily."""
    import threading

    tel = obs.configure(enabled=True)
    barrier = threading.Barrier(2)

    def worker(name: str):
        with tel.span(f"outer/{name}", cat="t"):
            barrier.wait(timeout=10)  # both outers open concurrently
            with tel.span(f"inner/{name}", cat="t"):
                barrier.wait(timeout=10)  # both inners overlap too

    threads = [
        threading.Thread(target=worker, args=(n,)) for n in ("a", "b")
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()

    ev = {e["name"]: e for e in tel.tracer.events()}
    assert len(ev) == 4
    ids = {e["span_id"] for e in ev.values()}
    assert len(ids) == 4 and 0 not in ids  # process-unique, nonzero
    for n in ("a", "b"):
        outer, inner = ev[f"outer/{n}"], ev[f"inner/{n}"]
        assert outer["parent_id"] == 0  # roots
        assert inner["parent_id"] == outer["span_id"]
        assert inner["tid"] == outer["tid"]


def test_validate_event_rejects_malformed():
    ok = {"type": "span", "name": "x", "cat": "c", "ts_us": 1.0,
          "dur_us": 2.0, "tid": 3, "attrs": {}}
    obs.validate_event(ok)
    for bad in (
        {**ok, "type": "nope"},
        {**ok, "ts_us": -1.0},
        {k: v for k, v in ok.items() if k != "attrs"},
    ):
        with pytest.raises(ValueError):
            obs.validate_event(bad)


def test_telemetry_section_schema():
    tel = obs.configure(enabled=True)
    tel.registry.counter("x.total").inc(3)
    tel.registry.gauge("x.depth").set(2.0)
    tel.registry.histogram("x.lat_s").observe(1e-3)
    tel.probe.track("x.cell", jax.jit(lambda v: v + 1))
    keep = jnp.ones((8,))  # a live array so the memory gauge is > 0

    sec = obs.telemetry_section()
    assert sec["schema_version"] == obs.SCHEMA_VERSION and sec["enabled"]
    assert sec["counters"]["x.total"] == 3
    assert sec["gauges"]["x.depth"]["value"] == 2.0
    h = sec["histograms"]["x.lat_s"]
    assert h["count"] == 1 and h["p50"] is not None
    assert "x.cell" in sec["recompiles"]
    assert sec["peak_device_memory_bytes"] >= keep.nbytes


# ---------------------------------------------------------------------------
# profiler sink: the program's spans on the device trace's clock
# ---------------------------------------------------------------------------


def _profiled_span_names(trace_dir) -> list:
    """Names of the `stream/*` and `serve/*` events on the host planes
    of the profiler trace under `trace_dir`."""
    from jax.profiler import ProfileData

    (path,) = sorted(trace_dir.glob("**/*.xplane.pb"))
    names = []
    for plane in ProfileData.from_file(str(path)).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                names += [e.name for e in line.events
                          if e.name.startswith(("stream/", "serve/"))]
    return names


def _paged_engine(batch_size: int):
    cfg = configs.reduced("qwen3_8b")
    model = api.build_model(cfg, tp=1, max_seq=32)
    params = model.init(jax.random.PRNGKey(0))
    span = validate_page_size(8, model.attn_capacities())
    eng = E.Engine(model, params, batch_size=batch_size,
                   paging=PagingConfig(8, batch_size * span + 1))
    return eng, cfg


def test_disabled_spans_reach_the_profiler(program, tmp_path):
    """With telemetry off, the fleet loop's and the decode tick's spans
    are written onto a host plane of a `jax.profiler` trace: one
    `stream/step` per packed batch, its children, and the tick's."""
    assert not obs.get().enabled
    cfg = FleetConfig(n_patients=8, segments_per_patient=6,
                      buckets=(4, 16), seed=2)
    runner = FleetRunner(program, path="twin")
    eng, lm = _paged_engine(batch_size=2)
    for uid in range(2):
        eng.submit(E.Request(
            uid=uid, prompt=jax.random.randint(
                jax.random.PRNGKey(uid), (6,), 0, lm.vocab),
            max_new=4))
    eng.tick()  # compiles admission and decode outside the trace
    with jax.profiler.trace(str(tmp_path)):
        out = simulate(cfg, runner=runner)
        for _ in range(2):
            eng.tick()
    names = _profiled_span_names(tmp_path)
    batches = out["metrics"]["batches_total"]
    assert names.count("stream/step") == batches
    assert names.count("stream/pack") == batches
    assert names.count("stream/admit") == batches
    assert names.count("stream/gather") == batches
    assert names.count("stream/setup") == 1
    # urgent, and emit (with diag where a vote fired): two sync spans
    assert names.count("stream/sync") == 2 * batches
    for name in ("stream/flush", "stream/classify", "stream/vote",
                 "stream/bookkeep", "serve/admission", "serve/decode"):
        assert name in names, name
    for name in ("serve/tick", "serve/emit", "serve/pages"):
        assert names.count(name) == 2, name


def test_enabled_span_lands_in_both_sinks(tmp_path):
    """One `tel.span` with the JSONL tracer on feeds the JSONL log (with
    its attributes) and the profiler's trace (by its bare name)."""
    tel = obs.configure(enabled=True)
    with jax.profiler.trace(str(tmp_path / "prof")):
        with tel.span("serve/tick", cat="serve", n=3):
            jnp.ones(4).block_until_ready()
    assert _profiled_span_names(tmp_path / "prof") == ["serve/tick"]
    (ev,) = tel.tracer.events()
    assert ev["name"] == "serve/tick" and ev["attrs"] == {"n": 3}


# ---------------------------------------------------------------------------
# jit recompile regression guards (generalized via obs.jaxprobe)
# ---------------------------------------------------------------------------


def test_recompile_guard_stream_buckets(program):
    """Stream classify over the declared buckets: after one warmup pass
    per bucket, further traffic causes zero jit cache misses."""
    obs.configure(enabled=True)
    buckets = (8, 16)
    runner = FleetRunner(program, path="twin")
    for b in buckets:
        runner.classify(jnp.zeros((b, vadetect.RECORD_LEN)))

    probe = obs.get().probe
    snap = probe.snapshot()
    assert snap.get("stream.classify.twin") == len(buckets)
    for _ in range(3):
        for b in buckets:
            runner.classify(jnp.zeros((b, vadetect.RECORD_LEN)))
    assert probe.new_misses(snap) == {}


def test_recompile_guard_decode_admission_widths():
    """Decode engine over its admission widths: after one warmup round
    covering each (group rows, prompt len) shape, re-serving the same
    shapes causes zero cache misses in the decode step, the prefill
    cell, or the seating cell."""
    obs.configure(enabled=True)
    cfg = configs.reduced("qwen3_8b")
    model = api.build_model(cfg, tp=1, max_seq=48)
    params = model.init(jax.random.PRNGKey(0))
    eng = E.Engine(model, params, batch_size=2)

    def serve_round(uid0):
        # two widths: a 2-row group (len 5) then a 1-row group (len 9)
        for uid, n_tok in ((uid0, 5), (uid0 + 1, 5), (uid0 + 2, 9)):
            eng.submit(E.Request(
                uid=uid,
                prompt=jax.random.randint(
                    jax.random.PRNGKey(uid), (n_tok,), 0, cfg.vocab),
                max_new=3,
            ))
        eng.run(max_ticks=40)

    serve_round(0)  # warmup: compiles decode + admission cells
    probe = obs.get().probe
    snap = probe.snapshot()
    for cell in ("serve.decode_step", "serve.prefill", "serve.seat"):
        assert snap.get(cell), (cell, snap)
    serve_round(10)
    assert probe.new_misses(snap) == {}


# ---------------------------------------------------------------------------
# disabled no-op + enabled overhead contracts
# ---------------------------------------------------------------------------


def test_disabled_telemetry_is_noop():
    """The disabled default costs nanoseconds per emission — hot paths
    emit unconditionally, so this bound is what makes that free."""
    obs.reset()
    tel = obs.get()
    assert not tel.enabled
    # null instruments are shared singletons, nothing accumulates
    assert tel.registry.counter("a") is tel.registry.counter("b")
    assert tel.registry.snapshot() == {
        "counters": {}, "gauges": {}, "histograms": {}
    }

    n = 20_000
    t0 = time.perf_counter()
    for _ in range(n):
        tel.registry.counter("stream.enqueued_total").inc()
        tel.registry.histogram("serve.ttft_s").observe(1e-3)
        with tel.span("serve/tick", cat="serve"):
            pass
    per_emission_ns = (time.perf_counter() - t0) / (3 * n) * 1e9
    # ~200-450 ns each measured; 2 us leaves CI-noise headroom while
    # still catching an accidental allocation/lock on the no-op path
    assert per_emission_ns < 2_000, per_emission_ns


def test_enabled_emission_cost_bounded():
    """Enabled-path per-emission budget — the noise-immune half of the
    overhead contract. The wall-clock A/B below can only be asserted
    on a quiet host; this tight CPU-bound micro-loop is stable
    anywhere and catches a catastrophic regression (an O(events) scan,
    a blocking call, a lock convoy) on the enabled hot path."""
    obs.configure(enabled=True)
    try:
        tel = obs.get()
        n = 5_000
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            for i in range(n):
                tel.registry.counter("stream.enqueued_total").inc()
                tel.registry.histogram("serve.ttft_s").observe(1e-3)
                tel.tracer.instant(
                    "stream/enqueue", cat="stream",
                    request_id="stream:0:1", v_ts_s=0.5,
                )
                with tel.span("serve/tick", cat="serve",
                              request_ids=["serve:0"]):
                    pass
            best = min(best, (time.perf_counter() - t0) / (4 * n))
        # ~1-3 us each measured; 25 us leaves heavy CI-noise headroom
        # while still catching anything super-linear
        assert best * 1e6 < 25.0, best * 1e6
    finally:
        obs.reset()


def test_enabled_overhead_under_three_percent(program):
    """Enabled telemetry stays under the 3% wall budget on the stream
    fleet loop — measured on a pre-warmed runner with interleaved
    disabled/enabled reps (min-of-N), the same protocol
    `benchmarks/stream_throughput.py` records in its BENCH telemetry
    `overhead` sub-record. The strict assert is gated on the
    measurement's own noise floor: when the disabled-side walls spread
    more than 3% (shared-VM steal time), a 3% A/B difference is below
    the measurement resolution and the assert would be a coin flip —
    skip with the evidence instead (the per-emission budget test above
    still enforces the enabled-path cost unconditionally)."""
    cfg = FleetConfig(
        n_patients=128, segments_per_patient=5, va_fraction=0.05,
        jitter_frac=0.02, buckets=(16, 64), path="twin",
    )
    runner = FleetRunner(program, path="twin")
    simulate(cfg, runner=runner)  # untimed: compile both bucket cells
    walls = {"disabled": [], "enabled": []}
    for rep in range(10):
        # alternate which mode runs first: VM scheduling noise arrives
        # in multi-second bursts, and a fixed order would let a burst
        # systematically land on one mode's phase across several reps
        order = ("disabled", "enabled") if rep % 2 == 0 else (
            "enabled", "disabled")
        for mode in order:
            if mode == "enabled":
                obs.configure(enabled=True)
            else:
                obs.reset()
            gc.disable()
            try:
                t0 = time.perf_counter()
                simulate(cfg, runner=runner)
                walls[mode].append(time.perf_counter() - t0)
            finally:
                gc.enable()
    # min-of-reps on both sides: noise (OS scheduling, GC) only ever
    # adds time, so the mins are the comparable noise floors
    ratio = min(walls["enabled"]) / min(walls["disabled"])
    dis = sorted(walls["disabled"])
    spread = dis[len(dis) // 2] / dis[0] - 1.0
    if spread > 0.03:
        pytest.skip(
            f"host too noisy to resolve a 3% A/B: disabled-side "
            f"median/min spread {spread:.1%} (ratio measured "
            f"{ratio:.3f}, recorded for reference)"
        )
    assert ratio < 1.03, (ratio, walls)
