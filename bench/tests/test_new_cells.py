"""A cell a later change adds, one chip or four, takes only new files and
entries: the rehearsal root drops cells it has no tiny stand-in for, and
a four-chip cell rehearses on four forced host devices in a child
process, its system and readers told the cell's `chips`."""

from __future__ import annotations

import os

import pytest

from bench import run as R
from bench.tests import rehearse

EXTRA = {"qwen3_8b.more_slots": 1, "qwen3_8b_full.decode_4chip": 4}

PROBE_SYSTEM = '''"""The LM cell, recording the chips it was given and the devices JAX
sees."""

import jax

from bench.systems import lm_engine


class Cell(lm_engine.Cell):
    def counters(self):
        return dict(super().counters(), chips=self.ctx.chips,
                    devices=jax.device_count())
'''

PROBE_READERS = {
    "probe_chips": "r.counters.get('chips')",
    "probe_devices": "r.counters.get('devices')",
    "probe_reading_chips": "r.chips",
}


def _with_extra_cells(tmp_path):
    """A copy of `BENCHMARK.json` naming one more one-chip cell and one
    four-chip cell, in `lm_tokens_per_s` and in a per-layer list."""
    bm = rehearse.read_json(os.path.join(rehearse.ROOT, "BENCHMARK.json"))
    for name, chips in EXTRA.items():
        config = name.split(".")[0]
        if config not in {c["name"] for c in bm["configs"]}:
            bm["configs"].append({"name": config, "source": "new",
                                  "file": f"bench/configs/{config}.json",
                                  "reduced": [], "why": "new"})
        bm["workloads"].append({"name": name, "config": config,
                                "traffic": name.split(".")[1],
                                "chips": chips, "why": "new"})
    for m in bm["end_to_end"] + bm["per_layer"]:
        if m["name"] in ("lm_tokens_per_s", "lm_slot_occupancy_pct"):
            m["workloads"] += list(EXTRA)
    src = tmp_path / "src_root"
    src.mkdir()
    rehearse.write_json(bm, src / "BENCHMARK.json")
    return str(src)


@pytest.mark.parametrize("cell", ["tiny_va.fleet", "tiny_lm.serve"])
def test_root_drops_cells_it_does_not_rehearse(tmp_path, cell):
    root = rehearse.make_root(str(tmp_path / "root"),
                              root=_with_extra_cells(tmp_path))
    bm = rehearse.read_json(os.path.join(root, "BENCHMARK.json"))
    for m in bm["end_to_end"] + bm["per_layer"]:
        assert not set(m.get("workloads", [])) & set(EXTRA), m["name"]
    spec = R.resolve(cell, root)
    assert spec.chips == 1 and spec.end_to_end and spec.per_layer
    if cell == "tiny_lm.serve":
        assert "lm_slot_occupancy_pct" in spec.readers
        import jax

        out = rehearse.run_in(root, cell, seed=2**31 + 11)
        assert out["correct"] is True, out["checks"]
        assert out["device"]["count"] == jax.device_count()


def _add_four_chip_cell(root: str) -> str:
    """The tiny LM under a system of its own, a mix and three readers,
    as a four-chip cell."""
    cell = "tiny_lm_4chip.serve_4chip"
    rehearse.add_cell(
        root, cell, mix=dict(rehearse.TINY_LM_MIX, slots=8), chips=4,
        config={"system": "lm_probe"}, system=PROBE_SYSTEM,
        readers={name: ("serving loop", "chips", expr)
                 for name, expr in PROBE_READERS.items()})
    return cell


def test_four_chip_cell_is_only_new_files(tmp_path):
    root = rehearse.make_root(str(tmp_path))
    cell = _add_four_chip_cell(root)
    spec = R.resolve(cell, root)
    assert spec.chips == 4
    assert sorted(spec.readers) == sorted(PROBE_READERS)
    out = rehearse.run_child(root, cell, 4, seed=2**31 + 13, trace=True)
    assert out["correct"] is True, out["checks"]
    assert out["device"]["count"] == 4
    assert {k: v["value"] for k, v in out["metrics"].items()} == {
        "probe_chips": 4.0, "probe_devices": 4.0, "probe_reading_chips": 4.0}


def test_a_cell_is_refused_fewer_devices_than_its_chips(tmp_path,
                                                       monkeypatch):
    import jax

    root = rehearse.make_root(str(tmp_path))
    cell = _add_four_chip_cell(root)
    one = jax.devices()[:1]
    monkeypatch.setattr(jax, "devices", lambda *a: one)
    with pytest.raises(R.SetupError, match="needs 4 chips, found 1"):
        rehearse.run_in(root, cell)
