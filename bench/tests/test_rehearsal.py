"""The harness end to end on the CPU, the chip check skipped: tiny cells
over the real systems, metrics and references."""

from __future__ import annotations

import json

import pytest

from bench.tests import rehearse

KEYS = ["correct", "attempted", "failed", "metrics", "device", "checks"]


@pytest.mark.parametrize("cell", ["tiny_va.fleet", "tiny_lm.serve"])
def test_run_line_and_correct(tmp_path, cell):
    out = rehearse.run(cell, str(tmp_path), seed=2**31 + 9)
    out.pop("_checks")
    line = json.loads(json.dumps(out))
    assert list(line) == KEYS
    assert line["correct"] is True
    assert line["attempted"] > 0 and line["failed"] == 0
    assert "setup_s" in line["metrics"]
    assert set(line["device"]) >= {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    for c in line["checks"].values():
        assert c["value"] <= c["limit"]


@pytest.mark.parametrize("cell", ["tiny_va.fleet", "tiny_lm.serve"])
def test_traced_run_reports_per_layer_metrics(tmp_path, cell):
    out = rehearse.run(cell, str(tmp_path), trace=True)
    assert out["correct"] is True
    assert "setup_s" not in out["metrics"]
    counters = {"tiny_va.fleet": {"va_batch_ms", "va_pad_pct"},
                "tiny_lm.serve": {"lm_slot_occupancy_pct"}}[cell]
    assert counters <= set(out["metrics"])
    assert {"busy_s", "window_s"} <= set(out["device"])
    assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}
