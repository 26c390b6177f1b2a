"""repro.stream: sources, scheduler (property tests), runner, vote, fleet."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import compiler, vadetect
from repro.data import iegm
from repro.stream import (
    FleetConfig,
    FleetRunner,
    MicroBatchScheduler,
    RingBuffer,
    SchedulerConfig,
    SegmentRef,
    simulate,
)
from repro.stream import vote as V
from repro.stream.scheduler import PRIORITY_ROUTINE, PRIORITY_URGENT


@pytest.fixture(scope="module")
def program():
    params = vadetect.init(jax.random.PRNGKey(0))
    return compiler.compile_model(params)


# ---------------------------------------------------------------------------
# sources / data.iegm per-patient streams
# ---------------------------------------------------------------------------


def test_ring_buffer_segments():
    rb = RingBuffer(segments=2, record_len=8)
    assert rb.push(np.arange(5)) == []
    (seg,) = rb.push(np.arange(5, 11))
    np.testing.assert_array_equal(seg, np.arange(8, dtype=np.float32))
    assert rb.fill == 3
    segs = rb.push(np.arange(11, 24))
    assert len(segs) == 2
    np.testing.assert_array_equal(segs[0], np.arange(8, 16))


def test_stream_segments_same_patient_agree():
    """Two iterators for the same (seed, patient) yield identical
    segments — the fold_in determinism contract."""
    it_a = iegm.stream_segments(7, seed=3)
    it_b = iegm.stream_segments(7, seed=3)
    for _ in range(3):
        a, b = next(it_a), next(it_b)
        assert a["seq"] == b["seq"] and a["label"] == b["label"]
        np.testing.assert_array_equal(
            np.asarray(a["signal"]), np.asarray(b["signal"])
        )
    # different patient: different telemetry
    c = next(iegm.stream_segments(8, seed=3))
    assert not np.array_equal(
        np.asarray(c["signal"]),
        np.asarray(next(iegm.stream_segments(7, seed=3))["signal"]),
    )


def test_stream_segments_restart_mid_stream():
    it = iegm.stream_segments(5, seed=1)
    next(it)
    second = next(it)
    restarted = next(iegm.stream_segments(5, seed=1, start=1))
    np.testing.assert_array_equal(
        np.asarray(second["signal"]), np.asarray(restarted["signal"])
    )


def test_segment_batch_composition_invariant():
    """A (patient, seq) row is bit-identical regardless of which batch
    it is generated in — what makes fleet tests reproducible."""
    a = iegm.segment_batch(0, np.array([3, 9, 4]), np.array([2, 0, 7]))
    b = iegm.segment_batch(0, np.array([9]), np.array([0]))
    np.testing.assert_array_equal(
        np.asarray(a["signal"][1]), np.asarray(b["signal"][0])
    )
    assert int(a["label"][1]) == int(b["label"][0])
    # labels are persistent per patient across seqs
    c = iegm.segment_batch(0, np.array([9]), np.array([5]))
    assert int(c["label"][0]) == int(b["label"][0])


# ---------------------------------------------------------------------------
# scheduler properties (hypothesis-style, deterministic stub in CI)
# ---------------------------------------------------------------------------

_BUCKETS = (4, 8, 16)


def _refs(n_patients, n_segments, seed):
    rng = np.random.default_rng(seed)
    refs = []
    for k in range(n_segments):
        p = int(rng.integers(n_patients))
        t = float(rng.uniform(0, 10))
        refs.append(
            SegmentRef(patient=p, seq=k, arrival_s=t, deadline_s=t + 2.048)
        )
    return refs


@settings(max_examples=25, deadline=None)
@given(
    n_patients=st.integers(2, 12),
    n_segments=st.integers(1, 60),
    n_urgent=st.integers(0, 3),
    seed=st.integers(0, 10_000),
)
def test_scheduler_no_drop_and_declared_buckets(
    n_patients, n_segments, n_urgent, seed
):
    """Every enqueued segment is packed exactly once (no drops), and
    every emitted batch hits a declared bucket shape with a correct
    padding mask."""
    cfg = SchedulerConfig(buckets=_BUCKETS)
    sched = MicroBatchScheduler(cfg, n_patients)
    refs = _refs(n_patients, n_segments, seed)
    rng = np.random.default_rng(seed + 1)
    urgent = np.zeros(n_patients, bool)
    urgent[rng.choice(n_patients, size=min(n_urgent, n_patients),
                      replace=False)] = True
    sched.set_urgent(urgent)

    packed = []
    i = 0
    while i < len(refs) or sched.ready():
        # interleave admission and packing in random chunk sizes
        take = int(rng.integers(1, 9))
        for r in refs[i : i + take]:
            sched.enqueue(r)
        i = min(i + take, len(refs))
        if sched.ready() and (rng.random() < 0.6 or i >= len(refs)):
            b = sched.next_batch(now_s=float(rng.uniform(0, 20)))
            assert b.bucket in _BUCKETS
            assert b.patients.shape == (b.bucket,)
            assert b.valid.sum() == b.n_valid
            assert not b.valid[b.n_valid :].any()
            packed.append(b)
    seen = sorted(
        (int(p), int(s))
        for b in packed
        for p, s, v in zip(b.patients, b.seqs, b.valid)
        if v
    )
    expected = sorted((r.patient, r.seq) for r in refs)
    assert seen == expected  # nothing dropped, nothing duplicated
    assert sched.enqueued_total == sched.packed_total == len(refs)


@settings(max_examples=25, deadline=None)
@given(
    n_patients=st.integers(2, 10),
    n_segments=st.integers(2, 40),
    seed=st.integers(0, 10_000),
)
def test_scheduler_deadline_monotone_within_class(
    n_patients, n_segments, seed
):
    """Within one packed batch, deadlines are non-decreasing within each
    priority class, and every urgent row precedes every routine row."""
    cfg = SchedulerConfig(buckets=_BUCKETS)
    sched = MicroBatchScheduler(cfg, n_patients)
    rng = np.random.default_rng(seed)
    urgent = rng.random(n_patients) < 0.3
    sched.set_urgent(urgent)
    for r in _refs(n_patients, n_segments, seed):
        sched.enqueue(r)
    while sched.ready():
        b = sched.next_batch(now_s=0.0)
        prio = b.priorities[b.valid]
        dl = b.deadlines[b.valid]
        assert (np.diff(prio) >= 0).all()  # urgent block first
        for cls in (PRIORITY_URGENT, PRIORITY_ROUTINE):
            d = dl[prio == cls]
            assert (np.diff(d) >= 0).all()
        # class assignment matches the urgency bitmap at pack time
        for p, c in zip(b.patients[b.valid], prio):
            assert c == (
                PRIORITY_URGENT if urgent[p] else PRIORITY_ROUTINE
            )


def test_scheduler_duplicate_ref_object_both_copies_packed():
    """Enqueueing the same SegmentRef *object* twice (a retransmission
    path reusing the ref) counts as two segments: the bucket cap may
    split them across batches but both copies must be packed."""
    sched = MicroBatchScheduler(SchedulerConfig(buckets=(1,)), n_patients=2)
    ref = SegmentRef(patient=0, seq=0, arrival_s=0.0, deadline_s=2.0)
    sched.enqueue(ref)
    sched.enqueue(ref)
    a = sched.next_batch(now_s=0.0)
    b = sched.next_batch(now_s=0.0)
    assert a is not None and b is not None
    assert a.n_valid == b.n_valid == 1
    assert sched.ready() == 0
    assert sched.enqueued_total == sched.packed_total == 2


def test_scheduler_urgent_preempts_routine():
    """An urgent patient's late-arriving segment jumps ahead of earlier
    routine segments when a batch can't take everyone."""
    cfg = SchedulerConfig(buckets=(4,))
    sched = MicroBatchScheduler(cfg, n_patients=8)
    for k in range(6):  # 6 routine segments, arrivals 0..5
        sched.enqueue(
            SegmentRef(patient=k, seq=0, arrival_s=float(k),
                       deadline_s=10.0 + k)
        )
    sched.enqueue(
        SegmentRef(patient=7, seq=0, arrival_s=9.0, deadline_s=99.0)
    )
    sched.mark_urgent([7])
    b = sched.next_batch(now_s=9.0)
    assert b.bucket == 4
    assert b.patients[0] == 7 and b.priorities[0] == PRIORITY_URGENT


def test_scheduler_caps_rows_per_patient_and_vote_stays_exact():
    """A patient 14 segments behind drains at most VOTE_SEGMENTS rows
    per batch (the vote scatter must never wrap its ring within one
    update), nothing is dropped, and the vote layer emits one diagnosis
    per completed 6-segment window — two for 14 segments."""
    cfg = SchedulerConfig(buckets=(16,))
    sched = MicroBatchScheduler(cfg, n_patients=2)
    for k in range(14):
        sched.enqueue(
            SegmentRef(patient=0, seq=k, arrival_s=float(k) * 0.01,
                       deadline_s=2.048 + k * 0.01)
        )
    state = V.init(2)
    emitted = 0
    batches = 0
    while sched.ready():
        b = sched.next_batch(now_s=1.0)
        batches += 1
        assert np.bincount(
            b.patients[b.valid], minlength=2
        ).max() <= V.VOTE_SEGMENTS
        # alternating preds so windows vote on what was written
        preds = (b.seqs % 2).astype(np.int32)
        state, emit, diag, _ = V.update(
            state,
            jnp.asarray(b.patients),
            jnp.asarray(preds),
            jnp.asarray(b.valid),
        )
        emitted += int(np.asarray(emit).sum())
    assert batches == 3  # 6 + 6 + 2
    assert sched.enqueued_total == sched.packed_total == 14
    assert int(state.count[0]) == 14
    assert emitted == 2  # windows at count 6 and 12


def test_scheduler_aligns_batches_to_vote_windows():
    """Regression: a batch must not straddle a patient's 6-segment vote
    boundary — the post-boundary row would overwrite ring slot 0 before
    the end-of-batch vote and flip the emitted diagnosis. Patient at
    count 5 with window preds [1,1,1,0,0]: segment 6 (pred 0) completes
    the window as a 3/6 tie -> VA; segment 7 must wait for the next
    batch."""
    cfg = SchedulerConfig(buckets=(4,))
    sched = MicroBatchScheduler(cfg, n_patients=1)
    state = V.init(1)
    window_preds = [1, 1, 1, 0, 0]
    for k, y in enumerate(window_preds):
        sched.enqueue(
            SegmentRef(patient=0, seq=k, arrival_s=0.0, deadline_s=2.0)
        )
        b = sched.next_batch(now_s=0.0)
        assert b.n_valid == 1
        state, emit, diag, _ = V.update(
            state,
            jnp.asarray(b.patients),
            jnp.full((b.bucket,), y, jnp.int32),
            jnp.asarray(b.valid),
        )
        assert not bool(emit[0])
    # segments 6 and 7 queued together: the batch may only take seg 6
    sched.enqueue(
        SegmentRef(patient=0, seq=5, arrival_s=0.1, deadline_s=2.1)
    )
    sched.enqueue(
        SegmentRef(patient=0, seq=6, arrival_s=0.2, deadline_s=2.2)
    )
    b = sched.next_batch(now_s=0.2)
    assert b.n_valid == 1 and int(b.seqs[0]) == 5
    state, emit, diag, _ = V.update(
        state,
        jnp.asarray(b.patients),
        jnp.zeros((b.bucket,), jnp.int32),
        jnp.asarray(b.valid),
    )
    assert bool(emit[0])
    assert int(diag[0]) == 1  # 3/6 tie breaks toward VA, not overwritten
    # segment 7 drains in the next batch, opening the new window
    b = sched.next_batch(now_s=0.3)
    assert b.n_valid == 1 and int(b.seqs[0]) == 6
    assert sched.enqueued_total == sched.packed_total == 7


# ---------------------------------------------------------------------------
# runner: twin path numerics, sharding, no silent recompiles
# ---------------------------------------------------------------------------


def test_twin_path_matches_reference(program):
    """The decompressed conv twin contracts the same weights the chip
    stores: logits match the program's reference execution."""
    runner_twin = FleetRunner(program, path="twin")
    x = iegm.synth_batch(jax.random.PRNGKey(5), 64)["signal"]
    from repro.stream.runner import _twin_logits, twin_weights

    lt = _twin_logits(twin_weights(program), program.layer_meta, x)
    lr = compiler.execute(program, x, path="reference")
    np.testing.assert_allclose(
        np.asarray(lt), np.asarray(lr), rtol=2e-4, atol=2e-4
    )
    preds = runner_twin.classify(x)
    assert preds.shape == (64,) and preds.dtype == jnp.int32
    agree = float((preds == jnp.argmax(lr, -1)).mean())
    assert agree >= 0.98, agree


def test_runner_no_silent_recompiles(program):
    """Only declared bucket shapes ever reach the jit: cache misses ==
    number of distinct shapes == len(buckets)."""
    runner = FleetRunner(program, path="twin")
    for b in (8, 16):
        for _ in range(3):
            runner.classify(jnp.zeros((b, vadetect.RECORD_LEN)))
    assert runner.jit_cache_misses() == 2


def test_runner_batch_service_accounting(program):
    runner = FleetRunner(program, path="twin")
    lat = runner.chip_latency_s
    assert lat == pytest.approx(35e-6, rel=0.1)  # paper's 35 us point
    assert runner.batch_service_s(64) == pytest.approx(64 * lat)
    assert runner.modeled_segments_per_s() == pytest.approx(1 / lat)


multidevice = pytest.mark.skipif(
    jax.device_count() < 2,
    reason="needs >=2 devices (scripts/ci.sh forces 8 host devices)",
)


@multidevice
def test_runner_sharded_matches_unsharded(program):
    from repro.launch.stream import make_data_mesh

    n = min(8, jax.device_count())
    mesh = make_data_mesh(n)
    sharded = FleetRunner(program, path="twin", mesh=mesh)
    plain = FleetRunner(program, path="twin")
    x = iegm.synth_batch(jax.random.PRNGKey(6), 32)["signal"]
    np.testing.assert_array_equal(
        np.asarray(sharded.classify(x)), np.asarray(plain.classify(x))
    )
    assert sharded.n_devices == n
    assert sharded.modeled_segments_per_s() == pytest.approx(
        n * plain.modeled_segments_per_s()
    )
    # modeled linear chip-fleet scaling: the benchmark's scaling claim
    assert sharded.batch_service_s(32) == pytest.approx(
        plain.batch_service_s(32) / n
    )


# ---------------------------------------------------------------------------
# vote: vectorized state machines vs python reference
# ---------------------------------------------------------------------------


def _vote_reference(n_patients, batches):
    """Per-patient python state machines (the thing vote.py vectorizes)."""
    ring = np.zeros((n_patients, V.VOTE_SEGMENTS), np.int64)
    count = np.zeros(n_patients, np.int64)
    last_pos = np.full(n_patients, -(10**9), np.int64)
    emitted = []
    for patients, preds, valid in batches:
        emit_now = set()
        for p, y, ok in zip(patients, preds, valid):
            if not ok:
                continue
            ring[p, count[p] % V.VOTE_SEGMENTS] = y
            count[p] += 1
            if y:
                last_pos[p] = count[p]
            if count[p] % V.VOTE_SEGMENTS == 0:
                emit_now.add(p)
        emitted.append(
            {
                p: int(2 * ring[p].sum() >= V.VOTE_SEGMENTS)
                for p in emit_now
            }
        )
    urgent = (count - last_pos) < V.URGENT_WINDOW
    return count, urgent, emitted


@settings(max_examples=15, deadline=None)
@given(
    n_patients=st.integers(2, 9),
    n_batches=st.integers(1, 6),
    bucket=st.sampled_from([4, 8]),
    seed=st.integers(0, 10_000),
)
def test_vote_matches_python_reference(n_patients, n_batches, bucket, seed):
    """Batches honor vote.update's documented precondition (the
    scheduler's window alignment: a patient's rows in one batch never
    cross a 6-segment boundary); within it, the vectorized machines
    must match the sequential reference exactly."""
    rng = np.random.default_rng(seed)
    count = np.zeros(n_patients, np.int64)
    batches = []
    for _ in range(n_batches):
        patients = rng.integers(0, n_patients, bucket)
        preds = rng.integers(0, 2, bucket)
        n_valid = int(rng.integers(1, bucket + 1))
        valid = np.arange(bucket) < n_valid
        in_batch = np.zeros(n_patients, np.int64)
        for i in range(bucket):
            if not valid[i]:
                continue
            p = patients[i]
            if in_batch[p] >= V.VOTE_SEGMENTS - count[p] % V.VOTE_SEGMENTS:
                valid[i] = False  # would straddle: scheduler defers it
            else:
                in_batch[p] += 1
        count += in_batch
        batches.append((patients, preds, valid))
    state = V.init(n_patients)
    for patients, preds, valid in batches:
        state, emit, diag, urgent = V.update(
            state,
            jnp.asarray(patients, jnp.int32),
            jnp.asarray(preds, jnp.int32),
            jnp.asarray(valid),
        )
    ref_count, ref_urgent, ref_emitted = _vote_reference(
        n_patients, batches
    )
    np.testing.assert_array_equal(np.asarray(state.count), ref_count)
    np.testing.assert_array_equal(np.asarray(urgent), ref_urgent)
    # re-run tracking emissions batch-by-batch
    state = V.init(n_patients)
    for (patients, preds, valid), ref_emit in zip(batches, ref_emitted):
        state, emit, diag, _ = V.update(
            state,
            jnp.asarray(patients, jnp.int32),
            jnp.asarray(preds, jnp.int32),
            jnp.asarray(valid),
        )
        got = {
            int(p): int(np.asarray(diag)[p])
            for p in np.nonzero(np.asarray(emit))[0]
        }
        assert got == ref_emit


def test_vote_duplicate_patient_rows_fill_consecutive_slots():
    state = V.init(2)
    patients = jnp.array([0, 0, 0, 1], jnp.int32)
    preds = jnp.array([1, 0, 1, 1], jnp.int32)
    valid = jnp.array([True, True, True, True])
    state, emit, diag, urgent = V.update(state, patients, preds, valid)
    np.testing.assert_array_equal(
        np.asarray(state.ring[0, :3]), [1, 0, 1]
    )
    assert int(state.count[0]) == 3 and int(state.count[1]) == 1
    assert bool(urgent[0]) and bool(urgent[1])
    assert not bool(emit[0])


# ---------------------------------------------------------------------------
# fleet: end-to-end virtual-time simulation
# ---------------------------------------------------------------------------


def test_fleet_simulation_deterministic_no_drops(program):
    cfg = FleetConfig(
        n_patients=12,
        segments_per_patient=6,
        buckets=(4, 16),
        va_fraction=0.4,
        jitter_frac=0.05,
        seed=11,
    )
    a = simulate(cfg, program)
    b = simulate(cfg, program)
    assert a["metrics"]["dropped_total"] == 0
    assert a["metrics"]["segments_total"] == 12 * 6
    # every patient completes exactly one 6-segment vote
    assert a["metrics"]["diagnoses_total"] == 12
    assert a["accuracy"]["patients_diagnosed"] == 12
    for k in ("segments_total", "batches_total", "diagnoses_total",
              "va_diagnoses_total", "dropped_total"):
        assert a["metrics"][k] == b["metrics"][k], k
    # no silent recompiles across the whole run
    assert a["jit_cache_misses"] == len(cfg.buckets)
    # virtual-time deadline slack is host-independent and recorded
    assert a["metrics"]["deadline_slack_s"]["violations"] == \
        b["metrics"]["deadline_slack_s"]["violations"]


@pytest.mark.parametrize("pregen,pinned", [
    (True, False), (True, True), (False, False),
])
def test_fleet_host_reads_counted(program, pregen, pinned):
    """`host_reads_total` counts every device-to-host read of the loop:
    the vote's urgency bitmap (unless pinned) and emission mask each
    batch, its diagnoses in each batch where a vote fired, and the
    synthesised signals when they are not pre-materialised."""
    cfg = FleetConfig(n_patients=12, segments_per_patient=6,
                      buckets=(4, 16), va_fraction=0.4, seed=11,
                      pregen=pregen)
    out = simulate(cfg, program, collect_diagnoses=True,
                   pinned_urgent=np.zeros(12, bool) if pinned else None)
    m = out["metrics"]
    # completion times rise batch by batch: one per batch that voted
    voted = len({t for *_, t in out["diagnoses"]})
    assert voted > 0
    per_batch = 1 + (not pinned) + (not pregen)
    assert m["host_reads_total"] == per_batch * m["batches_total"] + voted


def test_should_flush_fp_boundary_at_large_virtual_times():
    """Regression: the flush predicate must hold at now == oldest +
    max_wait even when fp cancellation rounds the recovered wait below
    max_wait. At large virtual times the rounding error is an ulp of
    the *magnitude* — adversarial bases make it dwarf the old fixed
    1e-9 epsilon, which livelocked the event loop (time could not
    advance past a trigger the predicate refused to fire on)."""
    cfg = SchedulerConfig(buckets=(8,), max_wait_s=0.256)
    for base in (0.0, 1.0, 2.0**30, 2.0**40, 1e15):
        sched = MicroBatchScheduler(cfg, n_patients=1)
        sched.enqueue(
            SegmentRef(patient=0, seq=0, arrival_s=base,
                       deadline_s=base + 2.048)
        )
        trigger = base + cfg.max_wait_s  # what the event loop advances to
        assert sched.should_flush(trigger), (
            base, trigger - base - cfg.max_wait_s
        )
        # and never fires meaningfully early: strictly before the
        # trigger's fp neighborhood the predicate stays False
        if base <= 2.0**30:
            assert not sched.should_flush(base + cfg.max_wait_s * 0.5)


def test_advance_virtual_time_forces_progress():
    from repro.stream import advance_virtual_time

    # normal advance: target wins
    assert advance_virtual_time(1.0, 2.5) == 2.5
    # fp-stalled advance: target rounds to now (service below one ulp)
    big = 2.0**50
    assert big + 1e-6 == big  # the adversarial premise
    assert advance_virtual_time(big, big + 1e-6) > big
    # equal-time trigger cannot stall either
    assert advance_virtual_time(big, big) > big


def test_fleet_simulation_survives_adversarial_virtual_times(program):
    """End-to-end livelock regression: a fleet whose virtual clock sits
    at adversarially large magnitudes (huge segment period pushing
    arrivals to ~1e12 s, where one ulp exceeds the chip service time
    and rivals max_wait rounding) must still terminate, pack every
    segment exactly once, and keep completions finite and ordered."""
    cfg = FleetConfig(
        n_patients=6,
        segments_per_patient=6,
        buckets=(4, 16),
        jitter_frac=0.3,  # adversarial jitter at huge period magnitudes
        seed=3,
        period_s=2.0**40,  # ~1.1e12 s: ulp ~2.4e-4 s >> 35 us service
    )
    out = simulate(cfg, program)
    assert out["metrics"]["segments_total"] == 6 * 6
    assert out["metrics"]["dropped_total"] == 0
    assert out["metrics"]["diagnoses_total"] == 6
    assert np.isfinite(out["metrics"]["virtual_horizon_s"])
    # completions advanced past the last arrival: time really moved
    assert out["metrics"]["virtual_horizon_s"] > 6 * cfg.period_s


def test_fleet_simulation_with_dropout_counts_source_gaps(program):
    cfg = FleetConfig(
        n_patients=10,
        segments_per_patient=6,
        buckets=(4, 16),
        dropout=0.2,
        seed=5,
    )
    out = simulate(cfg, program)
    # source gaps reduce the segment count; the scheduler still drops 0
    assert out["metrics"]["segments_total"] < 60
    assert out["metrics"]["dropped_total"] == 0


def test_mark_urgent_empty_update_is_noop():
    """Regression: `mark_urgent([])` crashed — `np.asarray([])`
    defaults to float64, and float-array indexing raises even with
    zero elements. An empty urgency update (e.g. a flush with no
    newly-urgent patients) must be a no-op, for both an empty list and
    an empty ndarray."""
    sched = MicroBatchScheduler(
        SchedulerConfig(buckets=(4,)), n_patients=4
    )
    before = sched._urgent.copy()
    sched.mark_urgent([])                       # empty list
    sched.mark_urgent(np.array([]))             # empty float64 ndarray
    sched.mark_urgent(np.array([], np.int64))   # empty int ndarray
    np.testing.assert_array_equal(sched._urgent, before)
    sched.mark_urgent([2])
    assert sched._urgent[2] and sched._urgent.sum() == 1
    sched.mark_urgent(np.array([]))  # still a no-op after a real mark
    assert sched._urgent[2] and sched._urgent.sum() == 1


@settings(max_examples=25, deadline=None)
@given(
    n_patients=st.integers(2, 8),
    n_segments=st.integers(1, 50),
    seed=st.integers(0, 10_000),
)
def test_oldest_arrival_cache_matches_naive_min(
    n_patients, n_segments, seed
):
    """The incrementally-cached `oldest_arrival` (seeded at enqueue,
    invalidated by `_pack`, recomputed at most once per pack) must
    equal the naive min over the live queue across randomized
    enqueue/pack interleavings — including repeated polls against an
    unchanged queue, the `should_flush` hot path."""
    sched = MicroBatchScheduler(
        SchedulerConfig(buckets=(1, 4)), n_patients
    )
    rng = np.random.default_rng(seed)
    refs = _refs(n_patients, n_segments, seed)

    def naive():
        return min(
            (r.arrival_s for _, r in sched._queue), default=float("inf")
        )

    i = 0
    while i < len(refs) or sched.ready():
        take = int(rng.integers(1, 6))
        for r in refs[i : i + take]:
            sched.enqueue(r)
            assert sched.oldest_arrival() == naive()
        i = min(i + take, len(refs))
        assert sched.oldest_arrival() == naive()  # cached re-poll
        if sched.ready() and (rng.random() < 0.5 or i >= len(refs)):
            sched.next_batch(now_s=float(rng.uniform(0, 20)))
            assert sched.oldest_arrival() == naive()
    assert sched.oldest_arrival() == float("inf")  # drained queue
