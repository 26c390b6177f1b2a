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


# Op events as XLA:TPU names them in the sharded qwen3-8b decode step and
# admission prefill on a 2x2 mesh (HLO text, shapes cut short)
ASYNC_START = ("%async-collective-start = (bf16[16,128,2048]) "
               "fusion(%bitcast.300), kind=kCustom, "
               "calls=%fused_computation.170")
ASYNC_DONE = ("%async-collective-done.1 = bf16[16,128,4096] "
              "fusion(%get-tuple-element.1007), kind=kCustom, "
              "calls=%fused_computation.177")
SCATTER_FUSION = ("%fusion.168 = (bf16[256,8,128]) fusion(%fusion.165), "
                  "kind=kCustom, calls=%all-reduce-scatter.3.clone.clone")
OVERLAP_FUSION = ("%fusion.186 = (f32[8,4]) fusion(%get-tuple-element.981),"
                  " kind=kOutput, calls=%async_collective_fusion.186")


def _four_devices():
    """Four device planes, one module: a plain fusion, an all-reduce, an
    asynchronous all-gather whose start and done events bracket compute,
    an all-reduce-scatter fusion, and a compute fusion overlapping an
    asynchronous collective; one event outside the window."""
    ms = 1e6
    devices = {}
    for d in range(4):
        ops = [("%fusion.7 = f32[8] fusion(x)", 0 * ms, 2 * ms),
               ("all-reduce.3", 2 * ms, (3 + d) * ms),
               ("all-gather-start.1", 8 * ms, 9 * ms),
               ("fusion.7", 9 * ms, 10 * ms),
               ("all-gather-done.1", 10 * ms, 10.5 * ms),
               (ASYNC_START, 7 * ms, 7.25 * ms),
               (OVERLAP_FUSION, 7.25 * ms, 7.5 * ms),
               (ASYNC_DONE, 7.5 * ms, 7.75 * ms),
               (SCATTER_FUSION, 10.5 * ms, 11.5 * ms),
               ("all-reduce.3", 12 * ms, 13 * ms)]
        mods = [("jit_step(5)", 0 * ms, 13 * ms)]
        devices[f"/device:TPU:{d}"] = {"XLA Ops": ops, "XLA Modules": mods}
    return {"devices": devices,
            "spans": [("bench/window", 0 * ms, 11 * ms)]}


def test_collective_time_by_executable():
    r = T.reduce(_four_devices())
    # device d: all-reduce 1 + d ms, all-gather start 1 and done 0.5 ms,
    # the asynchronous collective's start and done 0.25 ms each (not the
    # compute between), the scatter fusion's first 0.5 ms; the last
    # all-reduce and the rest of the scatter fusion lie after the window
    assert list(r["collectives_s"]) == ["jit_step"]
    assert r["collectives_s"]["jit_step"] == pytest.approx(
        sum(1 + d + 1.5 + 0.5 + 0.5 for d in range(4)) / 4 * 1e-3)
    assert r["executables_s"]["jit_step"] == pytest.approx(11e-3)


def test_busy_time_by_device():
    r = T.reduce(_four_devices())
    # device d: 0..3+d ms, then 7..7.75 and 8..11 ms
    want = {f"/device:TPU:{d}": (3 + d + 0.75 + 3) * 1e-3 for d in range(4)}
    assert r["busy_by_device_s"] == pytest.approx(want)
    assert r["busy_s"] == pytest.approx(sum(want.values()) / 4)
    assert r["n_devices"] == 4


def test_collective_names():
    for op in ("all-reduce.3", "all-gather-start.1", "all-gather-done.1",
               "reduce-scatter.2", "collective-permute-done",
               "%all-to-all.5 = bf16[2,8,1,2048] all-to-all(%all-reduce.11)",
               "all-reduce-scatter-fusion.1", ASYNC_START, ASYNC_DONE,
               SCATTER_FUSION):
        assert T.is_collective(op), op
    for op in ("fusion.7", "copy.2", "reduce.1", "scatter.3", "while.5",
               "%fusion.22 = bf16[256] fusion(%copy.2), kind=kOutput, "
               "calls=%fused_computation.14", OVERLAP_FUSION):
        assert not T.is_collective(op), op


def test_recorded_chip_trace_has_no_collectives():
    r = T.reduce(T.read(RECORDED))
    assert r["collectives_s"] == {}
    assert r["busy_by_device_s"] == {"/device:TPU:0": r["busy_s"]}


def test_empty_reduction_has_every_key():
    r = T.reduce(_raw())
    assert set(T.empty(1.0)) == set(r)
