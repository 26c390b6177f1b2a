"""The reduction from a profiler trace to busy/idle time, device time per
executable and attributed idle gaps."""

from __future__ import annotations

import os

import pytest

from bench import trace_reduce as T


def _raw():
    ms = 1e6
    ops = [("fusion.1", 0 * ms, 2 * ms), ("convolution.3", 1 * ms, 3 * ms),
           ("fusion.1", 6 * ms, 7 * ms), ("copy.2", 9 * ms, 12 * ms)]
    mods = [("jit__lambda(123)", 0 * ms, 3 * ms),
            ("jit_update(77)", 6 * ms, 7 * ms),
            ("jit__lambda(123)", 9 * ms, 12 * ms)]
    spans = [("bench/window", 1 * ms, 11 * ms),
             ("bench/va_simulate", 0 * ms, 12 * ms),
             ("bench/va_vote", 3.5 * ms, 5 * ms),
             ("bench/va_classify", 7.5 * ms, 8 * ms)]
    return {"devices": {"/device:TPU:0": {"XLA Ops": ops,
                                          "XLA Modules": mods}},
            "spans": spans}


def test_busy_idle_within_the_window():
    r = T.reduce(_raw())
    # window 1..11 ms; busy 1..3, 6..7, 9..11 = 5 ms
    assert r["window_s"] == pytest.approx(10e-3)
    assert r["busy_s"] == pytest.approx(5e-3)
    assert r["idle_share"] == pytest.approx(0.5)


def test_executables_by_module_name():
    r = T.reduce(_raw())
    assert r["executables_s"]["jit__lambda"] == pytest.approx(4e-3)
    assert r["executables_s"]["jit_update"] == pytest.approx(1e-3)


def test_idle_gaps_name_the_open_span():
    r = T.reduce(_raw())
    gaps = dict((round(s * 1e3, 6), n) for n, s in r["idle_gaps"])
    # 3..6 ms opens in va_simulate (va_vote starts later); 7..9 ms too
    assert gaps == {3.0: "bench/va_simulate", 2.0: "bench/va_simulate"}
    assert r["device_ops"][0][0] in ("jit__lambda/fusion.1",
                                     "jit__lambda/convolution.3",
                                     "jit__lambda/copy.2")
    assert "jit_update/fusion.1" in dict(r["device_ops"])


def test_overlapping_intervals_merge():
    assert T.merge([[0, 2], [1, 3], [5, 6]]) == [[0, 3], [5, 6]]
    assert T.clip([[0, 3], [5, 6]], 1, 5.5) == [[1, 3], [5, 5.5]]


def test_no_device_plane_is_an_error():
    with pytest.raises(ValueError):
        T.reduce({"devices": {}, "spans": []})


RECORDED = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "testdata", "va_classify_vote.xplane.pb")


def test_recorded_chip_trace():
    """A trace recorded on one TPU v5e: eight 256-row VA classify calls
    and votes under the harness's spans, 2 ms host sleeps between."""
    raw = T.read(RECORDED)
    assert list(raw["devices"]) == ["/device:TPU:0"]
    r = T.reduce(raw)
    assert r["n_devices"] == 1
    assert 0.0 < r["busy_s"] < r["window_s"] < 0.1
    assert r["idle_share"] > 0.9
    assert set(r["executables_s"]) == {"jit__lambda", "jit_update"}
    assert sum(r["executables_s"].values()) <= r["busy_s"] * 1.01
    names = {n for n, _ in r["device_ops"]}
    assert all(n.split("/")[0] in ("jit__lambda", "jit_update")
               for n in names)
    # the host sleeps between calls: the gaps open outside the spans
    assert r["idle_gaps"][0][0] == "outside bench spans"
    assert r["idle_gaps"][0][1] >= 0.002
