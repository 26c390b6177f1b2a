"""Virtual-time fleet simulation: sources → scheduler → runner → vote.

This is the subsystem's facade: wire a synthetic P-patient fleet through
the deadline-aware micro-batcher, the sharded bucketed runner, and the
vectorized vote machines, and report fleet metrics. Time is two-track:

  * *virtual* time drives arrivals, deadlines, and modeled completions
    (each bucket costs `runner.batch_service_s` of chip-twin time), so
    deadline slack is a property of the modeled fleet, reproducible on
    any host;
  * *wall* time measures what this host actually sustains
    (`segments_per_s_wall`), which is what the ≥real-time smoke
    criterion checks.

Signals can be pre-materialized (`pregen=True`, the default) so the
timed loop measures serving work — scheduling, packing, inference,
voting — not telemetry synthesis, which in deployment arrives from the
implants.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Optional

import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core import compiler, vadetect
from repro.stream import vote as V
from repro.stream.metrics import FleetMetrics
from repro.stream.runner import FleetRunner
from repro.stream.scheduler import (
    PRIORITY_URGENT,
    MicroBatchScheduler,
    SchedulerConfig,
)
from repro.stream.sources import (
    SEGMENT_PERIOD_S,
    FleetSource,
    SourceConfig,
    advance_virtual_time,
    check_refs,
)


@dataclasses.dataclass(frozen=True)
class FleetConfig:
    n_patients: int = 64
    segments_per_patient: int = 6
    seed: int = 0
    va_fraction: float = 0.5
    jitter_frac: float = 0.0
    dropout: float = 0.0
    buckets: tuple[int, ...] = (8, 32, 128, 256)
    max_wait_s: float = 0.256
    path: str = "twin"
    pregen: bool = True
    # segment completion period; non-default values are for stress tests
    # (e.g. adversarially large virtual times exercising fp boundaries)
    period_s: float = SEGMENT_PERIOD_S

    def source_config(self) -> SourceConfig:
        return SourceConfig(
            n_patients=self.n_patients,
            seed=self.seed,
            va_fraction=self.va_fraction,
            jitter_frac=self.jitter_frac,
            dropout=self.dropout,
            period_s=self.period_s,
        )

    def scheduler_config(self) -> SchedulerConfig:
        return SchedulerConfig(
            buckets=self.buckets, max_wait_s=self.max_wait_s
        )


class _SignalBank:
    """Pre-materialized (patient, seq) → signal rows, built in chunks."""

    def __init__(self, source: FleetSource, refs, chunk: int = 1024):
        pats = np.array([r.patient for r in refs], np.int64)
        seqs = np.array([r.seq for r in refs], np.int64)
        rows = []
        for lo in range(0, len(refs), chunk):
            hi = min(lo + chunk, len(refs))
            # fixed chunk shape (tail padded) -> one jit trace
            p = np.zeros(chunk, np.int64)
            s = np.zeros(chunk, np.int64)
            p[: hi - lo] = pats[lo:hi]
            s[: hi - lo] = seqs[lo:hi]
            out = source.signals(p, s)
            rows.append(np.asarray(out["signal"][: hi - lo]))
        self._signals = (
            np.concatenate(rows) if rows else np.zeros((0, 512), np.float32)
        )
        self._index = {
            (int(p), int(s)): i for i, (p, s) in enumerate(zip(pats, seqs))
        }

    def gather(self, patients: np.ndarray, seqs: np.ndarray) -> np.ndarray:
        idx = np.fromiter(
            (
                self._index[(int(p), int(s))]
                for p, s in zip(patients, seqs)
            ),
            np.int64,
            count=len(patients),
        )
        return self._signals[idx]


def _admit(sched: MicroBatchScheduler, refs: list, i: int,
           now: float) -> tuple[int, float]:
    """Enqueue arrivals and advance virtual time until the scheduler
    should flush or every arrival is in; returns (index of the next
    arrival, now)."""
    n = len(refs)
    while True:
        if sched.ready() == 0 and i < n:
            now = max(now, refs[i].arrival_s)
        while i < n and refs[i].arrival_s <= now:
            sched.enqueue(refs[i])
            i += 1
        if i >= n or sched.should_flush(now):
            return i, now
        # advance virtual time to the next trigger: the next arrival or
        # the oldest queued segment aging past max_wait; if the trigger
        # cannot move time forward (fp boundary: at large virtual times
        # `oldest + max_wait` can round to <= now), pack instead of
        # spinning — `should_flush`'s ulp-relative tolerance makes the
        # two sides of this boundary agree
        t_next = refs[i].arrival_s
        if sched.ready():
            t_next = min(
                t_next, sched.oldest_arrival() + sched.cfg.max_wait_s
            )
        if t_next <= now:
            return i, now
        now = t_next


def simulate(
    cfg: FleetConfig,
    program: Optional[compiler.AcceleratorProgram] = None,
    *,
    runner: Optional[FleetRunner] = None,
    mesh=None,
    collect_diagnoses: bool = False,
    arrivals=None,
    pinned_urgent=None,
    collect_latency: bool = False,
) -> dict:
    """Run the fleet for `segments_per_patient` segments per patient and
    return {metrics, chip, accuracy, ...}. Pass either a compiled
    `program` (a runner is built over it) or a ready `runner`.

    Load-lab hooks: `arrivals` replaces the source's periodic schedule
    with an explicit `SegmentRef` list (the open-loop Poisson /
    trace-driven schedules `obs.loadlab` generates); `pinned_urgent`
    (bool (n_patients,)) pins the scheduler's URGENT bitmap to a fixed
    cohort — it *replaces* the vote layer's feedback, so class
    survival under overload is testable independent of what an
    untrained classifier happens to vote;
    `collect_latency=True` returns raw per-segment arrays under
    "latency" — `latency_s` (modeled completion − *intended arrival*,
    the coordinated-omission-safe measurement), `slack_s`, `urgent`
    (priority class at pack time), and `latency_from_pack_s`
    (completion − pack instant, the dequeue-based number the CO guard
    must dominate)."""
    if runner is None:
        if program is None:
            import jax

            params = vadetect.init(jax.random.PRNGKey(cfg.seed))
            program = compiler.compile_model(params)
        runner = FleetRunner(program, path=cfg.path, mesh=mesh)

    tel = obs.get()
    # the call's set-up: arrival check, signal bank, warm-up; outside
    # the loop clock
    with tel.span("stream/setup", cat="stream"):
        source = FleetSource(cfg.source_config())
        refs = (
            check_refs(list(arrivals), cfg.n_patients)
            if arrivals is not None
            else source.arrivals(cfg.segments_per_patient)
        )
        sched = MicroBatchScheduler(cfg.scheduler_config(), cfg.n_patients)
        if pinned_urgent is not None:
            pinned_urgent = np.asarray(pinned_urgent, bool)
            sched.set_urgent(pinned_urgent)
        vstate = V.init(cfg.n_patients)
        metrics = FleetMetrics()
        bank = _SignalBank(source, refs) if cfg.pregen else None

        # the vote cell is probe-tracked like the classify cells, so the
        # repro.analysis cell audit covers it from the same registry
        vote_update = tel.probe.track("stream.vote", V.update)

        # warmup: compile every bucket shape outside the timed region
        for b in cfg.buckets:
            runner.classify(
                jnp.zeros((b, vadetect.RECORD_LEN))).block_until_ready()
            vote_update(
                vstate,
                jnp.zeros((b,), jnp.int32),
                jnp.zeros((b,), jnp.int32),
                jnp.zeros((b,), bool),
            )
    metrics.start_clock()
    flush_hist = tel.registry.histogram("stream.flush_wall_s")
    enqueued = tel.registry.counter("stream.enqueued_total")

    chip_s_per_patient = np.zeros(cfg.n_patients)
    final_diag = np.full(cfg.n_patients, -1, np.int64)
    diagnoses = []
    lat_records = (
        {"latency_s": [], "slack_s": [], "urgent": [],
         "latency_from_pack_s": [], "patient": []}
        if collect_latency
        else None
    )
    # one `stream/step` span per packed batch; its children tile it:
    # admit, pack, flush (gather, classify, vote), sync (each
    # device-to-host read) and bookkeep
    i, now = 0, 0.0
    while i < len(refs) or sched.ready():
        with tel.span("stream/step", cat="stream"):
            with tel.span("stream/admit", cat="stream"):
                i0 = i
                i, now = _admit(sched, refs, i, now)
                enqueued.add(i - i0)
            batch = sched.next_batch(now)
            if batch is None:
                continue
            if tel.enabled:
                # one rid list per batch, computed at pack time and
                # shared by every hop the batch's segments take (flush /
                # classify / vote) — the lineage join reads it back as
                # `request_ids`
                tagged = (
                    {"request_ids": batch.request_ids}
                    if batch.request_ids is not None
                    else {}
                )
                flush_attrs = dict(
                    bucket=batch.bucket, n_valid=batch.n_valid,
                    v_ts_s=now,
                    v_dur_s=runner.batch_service_s(batch.bucket),
                    **tagged,
                )
                classify_attrs = dict(
                    bucket=batch.bucket, v_ts_s=now, **tagged)
                vote_attrs = dict(v_ts_s=now, **tagged)
            else:
                flush_attrs = classify_attrs = vote_attrs = {}
            t_flush = time.perf_counter()
            with tel.span("stream/flush", cat="stream", **flush_attrs):
                with tel.span("stream/gather", cat="stream"):
                    if bank is not None:
                        sigs = bank.gather(batch.patients, batch.seqs)
                    else:
                        sigs = np.asarray(source.signals(
                            batch.patients, batch.seqs)["signal"])
                        metrics.host_reads_total += 1
                    sigs = jnp.asarray(sigs)
                with tel.span(
                    "stream/classify", cat="stream", **classify_attrs
                ):
                    preds = tel.block(runner.classify(sigs))
                with tel.span("stream/vote", cat="stream", **vote_attrs):
                    # deliberately NOT tel.block()ed: the vote result is
                    # read (`stream/sync`) a few statements down, so
                    # blocking here would only add a sync when telemetry
                    # is on and blow the <3% enabled budget. Wall dur is
                    # dispatch-only; the virtual track (v_ts_s/v_dur_s on
                    # the flush span) carries timing.
                    vstate, emit, diag, urgent = vote_update(
                        vstate,
                        jnp.asarray(batch.patients),
                        preds,
                        jnp.asarray(batch.valid),
                    )
            flush_hist.observe(time.perf_counter() - t_flush)
            if pinned_urgent is None:
                with tel.span("stream/sync", cat="stream"):
                    urgent = np.asarray(urgent, bool)
                    metrics.host_reads_total += 1
            else:
                urgent = pinned_urgent
            with tel.span("stream/bookkeep", cat="stream"):
                sched.set_urgent(urgent)
                service = runner.batch_service_s(batch.bucket)
                # forced minimum progress: at adversarially large virtual
                # times `now + service` can round back to exactly `now`
                # (service below one ulp), freezing completion times for
                # the rest of the run
                completion = advance_virtual_time(now, now + service)
                now = completion
                valid = batch.valid
                np.add.at(
                    chip_s_per_patient,
                    batch.patients[valid],
                    runner.chip_latency_s,
                )
                metrics.observe_batch(
                    bucket=batch.bucket,
                    n_valid=batch.n_valid,
                    n_urgent=int(
                        (batch.priorities[valid] == PRIORITY_URGENT).sum()
                    ),
                    slack_s=batch.deadlines[valid] - completion,
                    queue_depth=sched.ready(),
                    completion_s=completion,
                )
                if lat_records is not None:
                    lat_records["latency_s"].append(
                        completion - batch.arrivals[valid]
                    )
                    lat_records["slack_s"].append(
                        batch.deadlines[valid] - completion
                    )
                    lat_records["urgent"].append(
                        batch.priorities[valid] == PRIORITY_URGENT
                    )
                    lat_records["latency_from_pack_s"].append(
                        np.full(int(valid.sum()),
                                completion - batch.formed_at_s)
                    )
                    lat_records["patient"].append(batch.patients[valid])
            with tel.span("stream/sync", cat="stream"):
                # masks/indices pinned: empty device results must never
                # decay to float64 (the mark_urgent([]) class)
                emit_np = np.asarray(emit, bool)
                metrics.host_reads_total += 1
                diag_np = None
                if emit_np.any():
                    diag_np = np.asarray(diag, np.int64)
                    metrics.host_reads_total += 1
            if diag_np is not None:
                with tel.span("stream/bookkeep", cat="stream"):
                    who = np.nonzero(emit_np)[0]
                    metrics.observe_diagnoses(
                        len(who), int(diag_np[who].sum())
                    )
                    final_diag[who] = diag_np[who]
                    if collect_diagnoses:
                        diagnoses.extend(
                            (int(p), int(diag_np[p]), float(completion))
                            for p in who
                        )
    metrics.stop_clock()

    metrics.dropped_total = sched.enqueued_total - sched.packed_total
    tel.registry.counter("stream.dropped_total").add(metrics.dropped_total)
    labels = np.asarray(source.labels(np.arange(cfg.n_patients)))
    diagnosed = final_diag >= 0
    acc = (
        float((final_diag[diagnosed] == labels[diagnosed]).mean())
        if diagnosed.any()
        else float("nan")
    )
    # required aggregate real-time rate: one 512-sample segment per
    # patient per segment period (2.048 s at the paper's front end)
    required_rate = cfg.n_patients / cfg.period_s
    summ = metrics.summary()
    return {
        "config": {
            "n_patients": cfg.n_patients,
            "segments_per_patient": cfg.segments_per_patient,
            "buckets": list(cfg.buckets),
            "path": cfg.path,
            "n_devices": runner.n_devices,
            "jitter_frac": cfg.jitter_frac,
            "dropout": cfg.dropout,
        },
        "metrics": summ,
        "realtime": {
            "required_segments_per_s": required_rate,
            "sustained_segments_per_s": summ["segments_per_s_wall"],
            "realtime_factor": summ["segments_per_s_wall"]
            / max(required_rate, 1e-9),
        },
        "chip": {
            "latency_us_per_segment": runner.chip_latency_s * 1e6,
            "energy_nj_per_segment": runner.program.report.energy_j * 1e9,
            "modeled_fleet_segments_per_s": runner.modeled_segments_per_s(),
            "chip_s_per_patient_mean": float(chip_s_per_patient.mean()),
            "chip_s_per_patient_max": float(chip_s_per_patient.max()),
        },
        "accuracy": {
            "patients_diagnosed": int(diagnosed.sum()),
            "diagnostic_accuracy_synthetic": acc,
        },
        "jit_cache_misses": runner.jit_cache_misses(),
        "diagnoses": diagnoses if collect_diagnoses else None,
        "latency": (
            {
                k: (
                    np.concatenate(v)
                    if v
                    else np.zeros(0, {
                        "urgent": bool, "patient": np.int64,
                    }.get(k, np.float64))
                )
                for k, v in lat_records.items()
            }
            if lat_records is not None
            else None
        ),
    }
