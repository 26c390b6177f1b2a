"""The VA patient fleet under test: `repro.stream.simulate` (scheduler ->
`FleetRunner.classify` -> `vote.update`) driven with a runner built and
warmed in set-up.

The window is made of whole `simulate` calls, each over the mix's fleet
for `segments_per_patient` segments of every patient, repeated until the
calls' own loop clocks (`FleetMetrics.start_clock/stop_clock`) add up to
`--seconds`. The arrivals are the benchmark's own
(`loadgen.fleet_arrivals`: every patient on its own phase), handed to
`simulate` through its `arrivals=` hook. What a call does outside its
loop (checking the arrival list and synthesising the signals, which in
deployment come from the implants) is not in the window; its share of
the wall time is printed.

The harness wraps the layer calls of the loop: the scheduler's
`next_batch` (a subclass swapped into `repro.stream.fleet` for the length
of each call), the runner's `classify` (through a proxy object) and
`repro.stream.vote.update` (swapped in its module). The wrappers keep
what the timed path produced, for the check after the window, and open
`jax.profiler.TraceAnnotation` spans while tracing. The check rebuilds
each sampled row's signal from (seed, patient, segment) itself, so a
row that reaches `classify` from the wrong patient or segment is caught.
"""

from __future__ import annotations

import contextlib
import gc
import time

import numpy as np

# the classify executable's name in a profiler trace: `FleetRunner`
# jits a lambda, which JAX names `jit__lambda`
CLASSIFY_MODULE = "jit__lambda"
# rows per call of the signal bank's synthesis (`repro.stream.fleet`)
BANK_CHUNK = 1024


class _Classify:
    """Proxy for a `FleetRunner`: forwards everything, and records each
    `classify` call's inputs and predictions."""

    def __init__(self, runner, cell):
        self._runner = runner
        self._cell = cell

    def __getattr__(self, name):
        return getattr(self._runner, name)

    def classify(self, signals):
        cell = self._cell
        cell.tracer.poll()
        if cell.faults.get("rows_swapped"):
            signals = signals[::-1]
        with cell.span("bench/va_classify"):
            preds = self._runner.classify(signals)
        if cell.faults.get("answer_altered"):
            preds = preds.at[0].set(1 - preds[0])
        if cell.faults.get("half_batch"):
            n = preds.shape[0]
            preds = preds.at[n // 2:].set(0)
        cell.on_classify(signals, preds)
        return preds


class Cell:
    """One VA fleet cell: `ctx` carries the config, the mix, the seed,
    the reference module, the tracer and any planted faults."""

    def __init__(self, ctx):
        self.ctx = ctx
        self.cfg, self.mix, self.ref = ctx.cfg, ctx.mix, ctx.ref
        self.tracer = ctx.tracer
        self.faults = ctx.faults
        self.span = ctx.span

    # -- set-up -------------------------------------------------------------

    def setup(self) -> None:
        import jax
        import jax.numpy as jnp

        from repro.core import compiler, vadetect
        from repro.core.spe import SPEConfig
        from repro.stream import FleetConfig, FleetRunner, simulate

        cfg, mix = self.cfg, self.mix
        self.period_s = cfg["record_len"] / cfg["sample_rate_hz"]
        self.vcfg = vadetect.VAConfig(
            layers=tuple(tuple(x) for x in cfg["layers"]),
            spe=SPEConfig(bits=cfg["weight_bits"], group_size=cfg["group_size"],
                          keep=cfg["keep"], sparse=True, quantized=True),
        )
        calib = self._signals(self.ctx.call_seed(-2),
                              np.arange(BANK_CHUNK) % mix["n_patients"],
                              np.arange(BANK_CHUNK) // mix["n_patients"])
        self.params = self.ref.center_head(
            self.ref.make_params(self.ctx.key, cfg), cfg, calib)
        program = compiler.compile_model(self.params, self.vcfg)
        self.runner = FleetRunner(program, self.vcfg, path=mix["path"])
        self.proxy = _Classify(self.runner, self)
        self._simulate = simulate
        self._fleet = lambda seed, spp: FleetConfig(
            n_patients=mix["n_patients"], segments_per_patient=spp,
            seed=seed, va_fraction=mix["va_fraction"],
            buckets=tuple(mix["buckets"]), max_wait_s=mix["max_wait_s"],
            path=mix["path"],
        )
        for b in mix["buckets"]:
            self.runner.classify(
                jnp.zeros((b, vadetect.RECORD_LEN))).block_until_ready()
        # one short call at the cell's fleet size compiles every shape the
        # window uses (vote state, labels, the signal bank's chunks of
        # 1024 rows and its last partial chunk); its records go
        p, spp = mix["n_patients"], mix["segments_per_patient"]
        warm = next(s for s in range(1, spp + 1)
                    if (p * s) % BANK_CHUNK == (p * spp) % BANK_CHUNK)
        self.arrivals = self._arrivals(spp)
        self._reset()
        self._call(self.ctx.call_seed(-1), warm, self._arrivals(warm))
        jax.effects_barrier()
        self._reset()

    def _arrivals(self, spp: int) -> list:
        """The fleet's segment arrivals for one call (the same in every
        call of a run), as `simulate` takes them."""
        from repro.stream.sources import SegmentRef

        pats, seqs, t = self.ctx.loadgen.fleet_arrivals(
            self.mix["n_patients"], spp, self.period_s, self.ctx.seed)
        return [SegmentRef(int(p), int(k), float(a), float(a) + self.period_s)
                for p, k, a in zip(pats, seqs, t)]

    def _signals(self, seed: int, patients, seqs) -> np.ndarray:
        """(n, 512) signals of rows (patient, segment) of the fleet with
        call seed `seed`, made from the seed as the implants' telemetry,
        in chunks of the bank's shape."""
        from repro.stream.sources import FleetSource, SourceConfig

        src = FleetSource(SourceConfig(
            n_patients=self.mix["n_patients"], seed=seed,
            va_fraction=self.mix["va_fraction"]))
        out = []
        for lo in range(0, len(patients), BANK_CHUNK):
            p = np.zeros(BANK_CHUNK, np.int64)
            s = np.zeros(BANK_CHUNK, np.int64)
            n = min(BANK_CHUNK, len(patients) - lo)
            p[:n], s[:n] = patients[lo:lo + n], seqs[lo:lo + n]
            out.append(np.asarray(src.signals(p, s)["signal"])[:n])
        return np.concatenate(out)

    def _reset(self) -> None:
        self.records, self.sample, self.traced_rows = [], [], []
        self._rng = np.random.default_rng([self.ctx.seed, 7])
        self._seen = 0

    # -- the timed path -----------------------------------------------------

    @contextlib.contextmanager
    def _wrappers(self):
        """Swap the scheduler and the vote for recording wrappers."""
        import repro.stream.fleet as F
        import repro.stream.vote as V

        orig_update, orig_sched = V.update, F.MicroBatchScheduler
        faults, batches = self.faults, self._call_batches

        def update(state, patients, preds, valid):
            with self.span("bench/va_vote"):
                out = orig_update(state, patients, preds, valid)
            if faults.get("state_unchanged"):
                out = (state,) + tuple(out[1:])
            return out

        class Scheduler(orig_sched):
            def next_batch(s, now_s):
                with self.span("bench/va_pack"):
                    b = super().next_batch(now_s)
                if b is not None:
                    batches.append(b)
                return b

        V.update, F.MicroBatchScheduler = update, Scheduler
        try:
            yield
        finally:
            V.update, F.MicroBatchScheduler = orig_update, orig_sched

    def on_classify(self, signals, preds) -> None:
        self._call_classify += 1
        if self.tracer.active:
            self.traced_rows.append(int(signals.shape[0]))
        if self._call_classify <= len(self.mix["buckets"]):
            return  # the call's own warm-up batches
        # the batch the scheduler packed last is the one classified now
        b = self._call_batches[-1]
        v = b.valid
        row = (self._call_seed, b.patients[v], b.seqs[v], preds, v)
        self._call_rows.append(row)
        # reservoir sample of the window's batches, drawn from the seed
        k = self.mix["check_batches"]
        self._seen += 1
        if len(self.sample) < k:
            self.sample.append(row)
        else:
            j = int(self._rng.integers(self._seen))
            if j < k:
                self.sample[j] = row

    def _call(self, seed: int, spp: int, arrivals: list) -> dict:
        self._call_batches, self._call_rows = [], []
        self._call_classify, self._call_seed = 0, seed
        t0 = time.perf_counter()
        with self._wrappers(), self.span("bench/va_simulate"):
            out = self._simulate(self._fleet(seed, spp), runner=self.proxy,
                                 collect_diagnoses=True, arrivals=arrivals)
        wall = time.perf_counter() - t0
        m = out["metrics"]
        rec = {
            "wall_s": wall, "loop_s": m["wall_s"], "spp": spp,
            "segments": m["segments_total"], "batches": m["batches_total"],
            "padded": m["padded_total"], "dropped": m["dropped_total"],
            "diagnoses": out["diagnoses"], "rows": self._call_rows,
        }
        self._call_batches = []
        self.records.append(rec)
        return rec

    def run_window(self, seconds: float) -> None:
        loop_s, i = 0.0, 0
        t0 = time.perf_counter()
        self.tracer.start_window(t0)
        while loop_s < seconds:
            rec = self._call(self.ctx.call_seed(i),
                             self.mix["segments_per_patient"], self.arrivals)
            loop_s += rec["loop_s"]
            i += 1
        self.tracer.close()
        self.wall_s = time.perf_counter() - t0

    # -- after the window ---------------------------------------------------

    def release(self) -> None:
        """Bring the window's predictions to the host, and drop the
        runner."""
        import jax

        def host(row):
            seed, pats, seqs, preds, v = row
            return seed, pats, seqs, np.asarray(jax.device_get(preds))[v]

        for r in self.records:
            r["rows"] = [host(x) for x in r["rows"]]
        self.sample = [host(x) for x in self.sample]
        del self.proxy, self.runner
        gc.collect()

    def counters(self) -> dict:
        R = self.records
        seg = sum(r["segments"] for r in R)
        return {
            "calls": len(R),
            "segments": seg,
            "batches": sum(r["batches"] for r in R),
            "padded": sum(r["padded"] for r in R),
            "dropped": sum(r["dropped"] for r in R),
            "loop_s": sum(r["loop_s"] for r in R),
            "wall_s": self.wall_s,
        }

    def end_to_end(self) -> dict:
        c = self.counters()
        return {"va_segments_per_s": c["segments"] / c["loop_s"]}

    def attempted_failed(self) -> tuple[int, int]:
        c = self.counters()
        return c["segments"], c["dropped"]

    def notes(self) -> list[str]:
        c = self.counters()
        return [
            f"fleet {self.mix['n_patients']} patients x "
            f"{self.mix['segments_per_patient']} segments per call, "
            f"{c['calls']} calls, {c['segments']} segments in "
            f"{c['batches']} batches, loop {c['loop_s']:.3f}s of "
            f"{c['wall_s']:.3f}s wall; outside the loop "
            f"{100 * (1 - c['loop_s'] / c['wall_s']):.1f}% of the wall",
        ]

    def work(self) -> dict:
        """Work of the classify executable over the traced calls."""
        cfg = self.cfg
        flops = sum(self.ref.classify_flops(cfg, r) for r in self.traced_rows)
        nbytes = sum(self.ref.classify_bytes(cfg, r) for r in self.traced_rows)
        least = sum(
            max(self.ref.classify_flops(cfg, r) / self.ctx.peak["bf16_flops"],
                self.ref.classify_bytes(cfg, r)
                / self.ctx.peak["hbm_bytes_per_s"])
            for r in self.traced_rows
        )
        return {"classify": {
            "module": CLASSIFY_MODULE, "calls": len(self.traced_rows),
            "flops": flops, "bytes": nbytes, "least_s": least,
            "flops_per_segment": self.ref.classify_flops(cfg, 1),
        }}

    def _sampled(self):
        """The sampled batches' rows: signals rebuilt from (seed, patient,
        segment), and the predictions served for them."""
        sig = np.concatenate([self._signals(seed, p, s)
                              for seed, p, s, _ in self.sample])
        pred = np.concatenate([y for *_, y in self.sample]).astype(np.int64)
        return sig, pred

    def _reference_classes(self, sig, bits=None) -> np.ndarray:
        ref = self.ref
        weights = ref.reference_weights(self.params, self.cfg, bits=bits)
        return ref.reference_logits(weights, sig, self.cfg).argmax(-1)

    def control(self) -> dict:
        """The control's reading on the same sampled rows: the reference
        at the next width down (int4 weights) in the program's place."""
        sig, _ = self._sampled()
        want = self._reference_classes(sig)
        low = self._reference_classes(sig, bits=4)
        return {"va_wrong_share": float(np.mean(low != want))}

    def check(self) -> list[dict]:
        """The sampled batches' predictions against the reference's
        classes on signals it rebuilt itself; every diagnosis against the
        reference vote over the window's predictions, each under the
        patient the scheduler packed it for; and every segment of the
        arrival list classified exactly once."""
        sig, pred = self._sampled()
        wrong = pred != self._reference_classes(sig)
        mism, n_diag = self._vote_mismatches()
        unserved, n_seg = self._segments_not_once()
        return [
            {"name": "va_wrong_share", "value": float(np.mean(wrong)),
             "limit": self.ctx.limits["va_wrong_share"],
             "what": f"share of served predictions unlike the reference's "
                     f"class: {int(wrong.sum())} of {len(pred)} segments in "
                     f"{len(self.sample)} sampled batches"},
            {"name": "va_diagnosis_mismatches", "value": mism, "limit": 0,
             "what": f"diagnoses unlike the reference vote, of {n_diag}"},
            {"name": "va_segments_not_once", "value": unserved, "limit": 0,
             "what": f"arrived segments not classified exactly once, or "
                     f"classified without arriving, of {n_seg}"},
        ]

    def _segments_not_once(self) -> tuple[int, int]:
        p = self.mix["n_patients"]
        bad = total = 0
        for r in self.records:
            spp = r["spp"]
            pats = np.concatenate([x[1] for x in r["rows"]]).astype(np.int64)
            seqs = np.concatenate([x[2] for x in r["rows"]]).astype(np.int64)
            inside = (pats >= 0) & (pats < p) & (seqs >= 0) & (seqs < spp)
            counts = np.bincount(pats[inside] * spp + seqs[inside],
                                 minlength=p * spp)
            bad += int(np.abs(counts - 1).sum()) + int((~inside).sum())
            total += p * spp
        return bad, total

    def _vote_mismatches(self) -> tuple[int, int]:
        """Each patient's served predictions, in the order they were
        classified, cut into windows of `vote_segments`; the reference
        vote of each window against the diagnosis `simulate` emitted for
        that patient in the same turn."""
        n = self.cfg["vote_segments"]
        bad = total = 0
        for r in self.records:
            pats = np.concatenate([x[1] for x in r["rows"]]).astype(np.int64)
            ys = np.concatenate([x[3] for x in r["rows"]]).astype(np.int64)
            want_p, want_k, windows = _windows(pats, ys, n)
            want = self.ref.reference_votes(windows, self.cfg)
            d = np.asarray([(p, y) for p, y, _ in r["diagnoses"]],
                           np.int64).reshape(-1, 2)
            got_p, got_k, got = _windows(d[:, 0], d[:, 1], 1)
            width = int(max(want_k.max(initial=0), got_k.max(initial=0))) + 1
            kw, kg = want_p * width + want_k, got_p * width + got_k
            common, iw, ig = np.intersect1d(kw, kg, return_indices=True)
            bad += int((want[iw] != got[ig, 0]).sum())
            bad += len(kw) + len(kg) - 2 * len(common)
            total += len(kw) + len(kg) - len(common)
        return bad, total


def _windows(patients: np.ndarray, values: np.ndarray, n: int):
    """Group `values` by patient, keeping their order, into whole windows
    of n: the patient and the turn (0, 1, ...) of each window, and the
    (windows, n) values."""
    order = np.argsort(patients, kind="stable")
    ps, vs = patients[order], values[order]
    rank = np.arange(len(ps)) - np.searchsorted(ps, ps, side="left")
    count = np.bincount(ps, minlength=1)[ps] if len(ps) else ps
    full = rank < (count // n) * n
    return ps[full][::n], rank[full][::n] // n, vs[full].reshape(-1, n)
