"""The split of host time and device idle among the program's spans."""

from __future__ import annotations

import os

import pytest

from bench import span_split as P
from bench import trace_reduce as T

US = 1e3  # ns


def _step(t0: float) -> list:
    """One fleet step of 100 us on one host line: admit, pack, flush
    (gather, classify, vote), sync, bookkeep."""
    line = "/host:CPU#0"
    ev = [("stream/step", 0, 100), ("stream/admit", 0, 30),
          ("stream/pack", 30, 40), ("stream/flush", 40, 80),
          ("stream/gather", 40, 50), ("stream/classify", 50, 60),
          ("stream/vote", 60, 70), ("stream/sync", 80, 90),
          ("stream/bookkeep", 90, 100)]
    return [(n, (t0 + s) * US, (t0 + e) * US, line) for n, s, e in ev]


# the device runs classify at 55..58 us and the vote at 85..88 us
BUSY = [[55 * US, 58 * US], [85 * US, 88 * US]]


def _split():
    return P.split(BUSY, _step(0), 0.0, 100 * US)


def test_self_time_subtracts_nested_spans():
    s = _split()["spans"]
    assert s["stream/step"]["total_s"] == pytest.approx(100e-6)
    assert s["stream/step"]["self_s"] == pytest.approx(0.0, abs=1e-15)
    assert s["stream/flush"]["total_s"] == pytest.approx(40e-6)
    assert s["stream/flush"]["self_s"] == pytest.approx(10e-6)
    assert s["stream/admit"]["self_s"] == pytest.approx(30e-6)
    assert all(d["count"] == 1 for d in s.values())


def test_idle_inside_and_innermost():
    sp = _split()
    s = sp["spans"]
    assert sp["idle_s"] == pytest.approx(94e-6)
    assert s["stream/step"]["idle_s"] == pytest.approx(94e-6)
    assert s["stream/step"]["self_idle_s"] == pytest.approx(0.0, abs=1e-15)
    assert s["stream/flush"]["idle_s"] == pytest.approx(37e-6)
    assert s["stream/classify"]["self_idle_s"] == pytest.approx(7e-6)
    assert s["stream/sync"]["self_idle_s"] == pytest.approx(7e-6)
    # the innermost split is a partition of the window's idle
    named = sum(d["self_idle_s"] for d in s.values())
    assert named + sp["outside_s"] == pytest.approx(sp["idle_s"])
    assert sp["outside_s"] == pytest.approx(0.0, abs=1e-15)


def test_a_gap_is_split_across_the_spans_it_crosses():
    gaps = _split()["idle_gaps"]
    assert [round(g["s"] * 1e6, 6) for g in gaps] == [55.0, 27.0, 12.0]
    first, second = gaps[0], gaps[1]
    # 0..55 us: admit 30, pack 10, gather 10, classify 5
    assert first["span"] == "stream/admit"
    assert first["parts"] == pytest.approx({
        "stream/admit": 30e-6, "stream/pack": 10e-6,
        "stream/gather": 10e-6, "stream/classify": 5e-6})
    # 58..85 us: classify 2, vote 10, flush's own 10, sync 5
    assert second["span"] in ("stream/vote", "stream/flush")  # a tie
    assert second["parts"] == pytest.approx({
        "stream/classify": 2e-6, "stream/vote": 10e-6,
        "stream/flush": 10e-6, "stream/sync": 5e-6})


def test_time_outside_every_span_and_the_window():
    # two steps with a host gap between them, the window cutting the
    # second in half
    spans = _step(0) + _step(150)
    busy = BUSY + [[205 * US, 208 * US]]
    sp = P.split(busy, spans, 0.0, 200 * US)
    s = sp["spans"]
    assert s["stream/step"]["count"] == 2
    assert s["stream/step"]["total_s"] == pytest.approx(150e-6)
    assert sp["outside_s"] == pytest.approx(50e-6)
    gap = sp["idle_gaps"][0]
    assert gap["s"] == pytest.approx(112e-6)  # 88 us to the window's end
    assert gap["span"] == P.OUTSIDE
    assert gap["parts"] == pytest.approx({
        "stream/sync": 2e-6, "stream/bookkeep": 10e-6, P.OUTSIDE: 50e-6,
        "stream/admit": 30e-6, "stream/pack": 10e-6,
        "stream/gather": 10e-6})


def test_metrics_per_batch_and_per_tick():
    sp = _split()
    m = P.metrics(sp, {"reads": 6, "batches": 2})
    assert m["va_admit_ms"] == pytest.approx(30e-3)
    assert m["va_pack_ms"] == pytest.approx(10e-3)
    assert m["va_gather_ms"] == pytest.approx(10e-3)
    assert m["va_sync_ms"] == pytest.approx(10e-3)
    assert m["va_loop_idle_pct"] == pytest.approx(94.0)
    assert m["va_host_reads_per_batch"] == pytest.approx(3.0)
    assert not any(k.startswith("lm_") for k in m)


def test_metrics_of_an_empty_split_are_left_out():
    sp = P.split([], [], 0.0, 1.0)
    assert P.metrics(sp, {}) == {}
    assert sp["spans"] == {} and sp["idle_gaps"][0]["span"] == P.OUTSIDE


RECORDED = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "testdata", "va_classify_vote.xplane.pb")


def test_recorded_chip_trace_agrees_with_the_reduction():
    """On a trace recorded on one TPU v5e (harness spans only), the
    window's idle and its longest gaps are those `trace_reduce` finds,
    and the innermost split accounts for all of the idle."""
    r = T.reduce(T.read(RECORDED))
    sp = P.reduce_file(RECORDED)
    assert sp["window_s"] == pytest.approx(r["window_s"])
    assert sp["idle_s"] == pytest.approx(r["window_s"] - r["busy_s"])
    named = sum(d["self_idle_s"] for d in sp["spans"].values())
    assert named + sp["outside_s"] == pytest.approx(sp["idle_s"])
    assert [g["s"] for g in sp["idle_gaps"]] == pytest.approx(
        [s for _, s in r["idle_gaps"]])
    assert {"bench/va_classify", "bench/va_vote"} <= set(sp["spans"])
