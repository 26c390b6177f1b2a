"""Deadline-aware micro-batching scheduler for the monitoring fleet.

Packs ready segments from many patients into *fixed-shape* padded device
batches so the jitted inference step never retraces: every emitted batch
is padded up to one of the declared bucket sizes (`SchedulerConfig.
buckets`), and the set of distinct shapes the runner ever sees is
exactly that tuple — `tests/test_stream.py` asserts it via the jit cache
miss count.

Two priority classes with preemption:

  * URGENT  — patients with a recent VA-positive segment (within
    `vote.URGENT_WINDOW` processed segments; the vote layer owns that
    state machine and feeds the bitmap back). Their queued segments are
    packed first, ahead of every routine segment, regardless of arrival
    order: a VA-suspect must clear the 6-segment vote as fast as
    possible because the next step is a defibrillation decision.
  * ROUTINE — everyone else.

Within a class, segments are packed in deadline order (earliest first),
so deadlines are monotone within a class across a batch and across
consecutive batches drained at the same instant. Queues are unbounded
and every enqueued segment is eventually packed exactly once — the
scheduler *never* drops (drops happen only at the source, as modeled
telemetry gaps).
"""

from __future__ import annotations

import dataclasses
import itertools

import numpy as np

from repro import obs
from repro.concurrency import driver_thread_only
from repro.stream.sources import SEGMENT_PERIOD_S, SegmentRef
from repro.stream.vote import VOTE_SEGMENTS

PRIORITY_URGENT = 0
PRIORITY_ROUTINE = 1


@dataclasses.dataclass(frozen=True)
class SchedulerConfig:
    buckets: tuple[int, ...] = (8, 32, 128, 256)  # ascending batch shapes
    deadline_s: float = SEGMENT_PERIOD_S
    max_wait_s: float = 0.256  # time-trigger: flush a partial batch

    def __post_init__(self):
        assert self.buckets == tuple(sorted(self.buckets)), self.buckets
        assert all(b > 0 for b in self.buckets)


@dataclasses.dataclass
class PackedBatch:
    """One fixed-shape device batch. Arrays have length `bucket`; rows
    past `n_valid` are padding (patient/seq repeat the last valid row so
    the padded compute is well-formed; `valid` masks them out)."""

    patients: np.ndarray  # (bucket,) int32
    seqs: np.ndarray  # (bucket,) int32
    arrivals: np.ndarray  # (bucket,) float64 — virtual arrival times
    deadlines: np.ndarray  # (bucket,) float64
    priorities: np.ndarray  # (bucket,) int32 — class at pack time
    valid: np.ndarray  # (bucket,) bool
    bucket: int
    n_valid: int
    formed_at_s: float
    # lineage ids of the valid rows, computed once at pack time when
    # telemetry is enabled (None when disabled) — every downstream hop
    # (flush / classify / vote) attaches this same list instead of
    # re-deriving it, keeping the enabled hot path cheap
    request_ids: "list[str] | None" = None


class MicroBatchScheduler:
    """Admission queue + pad-to-bucket packer with urgent preemption."""

    def __init__(self, cfg: SchedulerConfig, n_patients: int):
        self.cfg = cfg
        self.n_patients = n_patients
        # (admission_index, ref) pairs: the index is the FIFO tiebreak
        # for equal deadlines AND the removal key at pack time — unique
        # per enqueue even if one ref object is enqueued twice (e.g. a
        # retransmission path), so 'never drops' holds per enqueue
        self._queue: list[tuple[int, SegmentRef]] = []
        self._tie = itertools.count()
        # cached min arrival over the queue: maintained at enqueue
        # (min is monotone under insertion), invalidated when `_pack`
        # removes entries, lazily recomputed on the next read. None
        # means stale; an empty queue short-circuits before the cache
        # is consulted.
        self._oldest_cache: float | None = None
        # urgency bitmap: owned by the vote layer's per-patient state
        # machine (`stream.vote.update` returns it); the scheduler only
        # *consumes* it at pack time.
        self._urgent = np.zeros(n_patients, bool)
        # segments packed so far per patient == the vote layer's
        # processed count (every packed row goes straight to one
        # vote.update); used to align batches to vote windows
        self._packed_count = np.zeros(n_patients, np.int64)
        self.enqueued_total = 0
        self.packed_total = 0

    # -- admission ----------------------------------------------------------

    @driver_thread_only
    def enqueue(self, ref: SegmentRef) -> None:
        if not self._queue:
            self._oldest_cache = ref.arrival_s
        elif self._oldest_cache is not None and (
            ref.arrival_s < self._oldest_cache
        ):
            self._oldest_cache = ref.arrival_s
        self._queue.append((next(self._tie), ref))
        self.enqueued_total += 1
        # the registry's `stream.enqueued_total` is the caller's to add
        # (`simulate` once per admission span)
        tel = obs.get()
        if tel.enabled:
            # lineage root: mints the segment's request id at admission
            # with its *intended* arrival on the virtual track
            tel.tracer.instant(
                "stream/enqueue", cat="stream",
                request_id=f"stream:{ref.patient}:{ref.seq}",
                v_ts_s=ref.arrival_s,
            )

    @driver_thread_only
    def extend(self, refs) -> None:
        for r in refs:
            self.enqueue(r)

    # -- urgency feedback (from stream.vote) --------------------------------

    @driver_thread_only
    def set_urgent(self, urgent: np.ndarray) -> None:
        """Overwrite the urgency bitmap (one bool per patient)."""
        urgent = np.asarray(urgent, bool)
        assert urgent.shape == (self.n_patients,), urgent.shape
        self._urgent = urgent.copy()

    @driver_thread_only
    def mark_urgent(self, patients, flag: bool = True) -> None:
        # force an integer index dtype: `np.asarray([])` defaults to
        # float64, and float-array indexing raises even for zero
        # elements — an empty update (no patients changed state this
        # tick) must be a no-op, not a crash
        idx = np.asarray(patients, np.intp)
        if idx.size:
            self._urgent[idx] = flag

    def is_urgent(self, patient: int) -> bool:
        return bool(self._urgent[patient])

    # -- introspection ------------------------------------------------------

    def ready(self) -> int:
        return len(self._queue)

    def earliest_deadline(self) -> float:
        if not self._queue:
            return float("inf")
        return min(r.deadline_s for _, r in self._queue)

    def oldest_arrival(self) -> float:
        """Min arrival over the queue, O(1) amortized: `should_flush`
        polls this every iteration of the virtual-time loop, and a full
        min-scan per poll is O(n²) per drain cycle at fleet backlogs.
        The cache is maintained incrementally at enqueue and recomputed
        at most once per pack (the only removal point)."""
        if not self._queue:
            return float("inf")
        if self._oldest_cache is None:
            self._oldest_cache = min(r.arrival_s for _, r in self._queue)
        return self._oldest_cache

    def should_flush(self, now_s: float) -> bool:
        """Size trigger (a full largest bucket is ready) or time trigger
        (the oldest queued segment has waited max_wait_s)."""
        if not self._queue:
            return False
        if len(self._queue) >= self.cfg.buckets[-1]:
            return True
        # tolerance guards the fp boundary now == oldest + max_wait,
        # where (oldest + max_wait) - oldest can round below max_wait
        # and livelock a virtual-time loop that advances `now` to the
        # trigger. The rounding error is an ulp of the *operand
        # magnitude* — at large virtual times (adversarial jitter, long
        # horizons) it dwarfs any fixed epsilon — so the tolerance is a
        # few ulp of the larger operand, floored at the old 1e-9.
        oldest = self.oldest_arrival()
        tol = max(1e-9, 4.0 * np.spacing(max(abs(now_s), abs(oldest))))
        return now_s - oldest >= self.cfg.max_wait_s - tol

    # -- packing ------------------------------------------------------------

    def _bucket_for(self, n: int) -> int:
        for b in self.cfg.buckets:
            if n <= b:
                return b
        return self.cfg.buckets[-1]

    @driver_thread_only
    def next_batch(self, now_s: float) -> PackedBatch | None:
        """Pack up to largest-bucket segments: urgent first, then
        routine, each class in (deadline, admission) order; pad the
        result up to the smallest declared bucket that fits.

        A patient's rows in one batch never cross a 6-segment vote
        window boundary: the per-batch cap is the remaining slots in
        the patient's current window (VOTE_SEGMENTS − packed % 6). The
        vote layer's scatter addresses ring slot (count + in-batch
        rank) % 6 and votes once at end of batch, so a straddling batch
        would overwrite pre-boundary slots before the vote. A
        backlogged patient just drains through consecutive batches —
        still never dropped, excess rows stay queued."""
        if not self._queue:
            return None
        tel = obs.get()
        attrs = (
            {"queue_depth": len(self._queue), "v_ts_s": now_s}
            if tel.enabled else {}
        )
        with tel.span("stream/pack", cat="stream", **attrs) as sp:
            batch = self._pack(now_s)
            if tel.enabled:
                # which segments this pack chose is only known now —
                # late-set so the span joins each one's lineage.
                # tolist() converts in C; per-element numpy-scalar
                # formatting is ~5x slower and shows up in the enabled
                # overhead budget
                ps = batch.patients[batch.valid].tolist()
                ss = batch.seqs[batch.valid].tolist()
                batch.request_ids = [
                    f"stream:{p}:{s}" for p, s in zip(ps, ss)
                ]
                sp.set(request_ids=batch.request_ids)
        return batch

    def _pack(self, now_s: float) -> PackedBatch:
        urgent, routine = [], []
        for entry in self._queue:
            (urgent if self.is_urgent(entry[1].patient)
             else routine).append(entry)
        key = lambda e: (e[1].deadline_s, e[0])
        urgent.sort(key=key)
        routine.sort(key=key)
        take, take_prio = [], []
        per_patient: dict[int, int] = {}
        for order, r in urgent + routine:
            if len(take) >= self.cfg.buckets[-1]:
                break
            c = per_patient.get(r.patient, 0)
            window_left = VOTE_SEGMENTS - (
                int(self._packed_count[r.patient]) % VOTE_SEGMENTS
            )
            if c >= window_left:
                continue
            per_patient[r.patient] = c + 1
            take.append((order, r))
            take_prio.append(
                PRIORITY_URGENT
                if self.is_urgent(r.patient)
                else PRIORITY_ROUTINE
            )
        for p, c in per_patient.items():
            self._packed_count[p] += c
        taken = {order for order, _ in take}
        self._queue = [e for e in self._queue if e[0] not in taken]
        # removal can only raise the min — invalidate; the next
        # `oldest_arrival` recomputes once over the survivors
        self._oldest_cache = None
        self.packed_total += len(take)

        n = len(take)
        bucket = self._bucket_for(n)
        pad = bucket - n
        rows = [r for _, r in take]
        rows = rows + [rows[-1]] * pad
        prio = np.full(bucket, PRIORITY_ROUTINE, np.int32)
        prio[:n] = take_prio
        return PackedBatch(
            patients=np.array([r.patient for r in rows], np.int32),
            seqs=np.array([r.seq for r in rows], np.int32),
            arrivals=np.array([r.arrival_s for r in rows], np.float64),
            deadlines=np.array([r.deadline_s for r in rows], np.float64),
            priorities=prio,
            valid=np.arange(bucket) < n,
            bucket=bucket,
            n_valid=n,
            formed_at_s=now_s,
        )
