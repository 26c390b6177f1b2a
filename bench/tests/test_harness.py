"""The harness without a chip: discovery by name, the contract's shape of
`BENCHMARK.json`, the traffic generator, the peaks table and the work
counts."""

from __future__ import annotations

import json
import os
import re
import subprocess
import sys

import numpy as np
import pytest

from bench import loadgen, peaks
from bench import run as R
from bench.tests import rehearse

ROOT = rehearse.ROOT
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.fixture(scope="module")
def bm():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_benchmark_json_shape(bm):
    assert set(bm) == {"command", "paths", "run_seconds", "configs",
                       "workloads", "end_to_end", "per_layer"}
    assert bm["paths"] == ["bench"]
    assert bm["command"][1].startswith("bench/")
    names = [m["name"] for m in bm["end_to_end"] + bm["per_layer"]]
    names += [w["name"] for w in bm["workloads"]]
    names += [c["name"] for c in bm["configs"]]
    assert len(names) == len(set(names))
    for n in names:
        assert NAME.match(n), n
    for m in bm["end_to_end"] + bm["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
    e2e = {m["name"]: m for m in bm["end_to_end"]}
    assert e2e["setup_s"]["bound"] <= 0.25
    for m in bm["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25 and m["source"] == "host_clock"
    four = [w["name"] for w in bm["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(bm["workloads"]) // 2), four
    for w in bm["workloads"]:
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        reported = {m["name"] for m in bm["end_to_end"]
                    if w["name"] in m.get("workloads", [w["name"]])}
        assert "setup_s" in reported and len(reported) >= 2, w["name"]
        layer = [m for m in bm["per_layer"] if w["name"] in m["workloads"]]
        assert layer, w["name"]
        for m in layer:
            assert m["moves"] in reported, (w["name"], m["name"])
    used = {w["config"] for w in bm["workloads"]}
    assert used == {c["name"] for c in bm["configs"]}
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 64 * 1024


with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    CELLS = [w["name"] for w in json.load(_f)["workloads"]]


@pytest.mark.parametrize("cell", CELLS)
def test_cell_loads_from_its_files(bm, cell):
    """Config, reference, system, mix and metric readers all come from
    files named after the entries of `BENCHMARK.json`."""
    spec = R.resolve(cell)
    w = {x["name"]: x for x in bm["workloads"]}[cell]
    assert spec.cfg["name"] == w["config"]
    assert spec.mix["kind"] in ("va_fleet", "lm_open_loop")
    assert hasattr(spec.system, "Cell") and hasattr(spec.ref, "make_params")
    declared = {m["name"]: m for m in bm["per_layer"]}
    for name, reader in spec.readers.items():
        m = declared[name]
        assert (reader.LAYER, reader.UNIT, reader.MOVES, reader.SOURCE) == (
            m["layer"], m["unit"], m["moves"], m["source"]), name


def test_config_files_state_their_cuts(bm):
    for c in bm["configs"]:
        with open(os.path.join(ROOT, c["file"])) as f:
            cfg = json.load(f)
        assert cfg["reduced"] == c["reduced"] and cfg["source"] == c["source"]
        for k in c["reduced"]:
            assert k in cfg["published"] and cfg[k] != cfg["published"][k]


def test_a_new_cell_is_only_new_files(tmp_path):
    """A configuration with its own limit, a mix and a metric, added as
    files plus entries, load by name with no existing file edited."""
    root = rehearse.make_root(str(tmp_path))
    rehearse.add_cell(
        root, "tiny_lm_wide.longer",
        mix=dict(rehearse.TINY_LM_MIX, prompt_len=24),
        config={"hidden_size": 96, "limits": {"lm_token_gap": 0.07}},
        readers={"ticks_seen": ("engine slots and admission", "ticks",
                                'r.counters.get("ticks")')})
    spec = R.resolve("tiny_lm_wide.longer", root)
    assert spec.cfg["hidden_size"] == 96
    assert spec.limits == {"lm_token_gap": 0.07}
    assert R.resolve("tiny_lm.serve", root).limits == \
        rehearse.TINY_LIMITS["tiny_lm"]
    assert spec.mix["prompt_len"] == 24
    assert list(spec.readers) == ["ticks_seen"]
    assert [m["name"] for m in spec.end_to_end] == ["lm_tokens_per_s",
                                                    "setup_s"]


def test_every_configuration_states_its_limits(bm):
    for c in bm["configs"]:
        with open(os.path.join(ROOT, c["file"])) as f:
            limits = json.load(f)["limits"]
        assert limits and all(v > 0 for v in limits.values()), c["name"]


@pytest.mark.parametrize("seed", [0, 2**31 + 5])
def test_fleet_arrivals_spread_every_patient_on_its_own_phase(seed):
    pats, seqs, t = loadgen.fleet_arrivals(1000, 3, 2.048, seed)
    assert len(t) == 3000 and np.all(np.diff(t) >= 0)
    assert len(set(zip(pats.tolist(), seqs.tolist()))) == 3000
    # one segment a patient in every period, the fleet spread evenly
    first = t[seqs == 0]
    assert first.min() > 0 and first.max() < 2.048
    assert np.histogram(first, bins=8, range=(0, 2.048))[0].tolist() == \
        [125] * 8
    _, _, t2 = loadgen.fleet_arrivals(1000, 3, 2.048, 1)
    np.testing.assert_allclose(t, t2)
    by_patient = t[np.lexsort((seqs, pats))].reshape(1000, 3)
    np.testing.assert_allclose(np.diff(by_patient, axis=1), 2.048)


def _run_entry(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "va_cnn.fleet_full",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_no_chip_exits_nonzero_without_a_result():
    p = _run_entry(ROOT, {"PYTHONPATH": os.path.join(ROOT, "src")})
    assert p.returncode != 0
    assert "no TPU found" in p.stderr
    assert not p.stdout.strip().startswith("{")
    assert "{" not in p.stdout


def test_bare_checkout_exits_nonzero(tmp_path):
    """A directory with only `BENCHMARK.json` and the benchmark's files
    has no program to run."""
    import shutil

    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "va_cnn.fleet_full",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=dict(env, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=300)
    assert p.returncode != 0 and "{" not in p.stdout


def test_unknown_device_kind_raises():
    with pytest.raises(peaks.UnknownDeviceKind):
        peaks.peaks_for("TPU v99")
    assert peaks.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9


@pytest.mark.parametrize("seed", [0, 7, 2**31 + 5, 2**40 + 3])
def test_lengths_are_one_multiset_in_seed_order(seed):
    spec = {"dist": "lognormal", "median": 256, "sigma": 0.6, "min": 32,
            "max": 1024}
    a = loadgen.lengths(spec, 200, seed, 2)
    b = loadgen.lengths(spec, 200, 1, 2)
    assert sorted(a) == sorted(b)
    assert a.min() >= 32 and a.max() <= 1024
    np.testing.assert_array_equal(a, loadgen.lengths(spec, 200, seed, 2))
    t = loadgen.arrival_times({"process": "poisson", "rate_per_s": 4.0},
                              120, seed)
    assert np.all(np.diff(t) > 0)
    assert sorted(np.diff(np.concatenate([[0], t]))) == pytest.approx(
        sorted(np.diff(np.concatenate(
            [[0], loadgen.arrival_times({"process": "poisson",
                                         "rate_per_s": 4.0}, 120, 3)]))))


def test_schedule_is_deterministic_in_the_seed():
    mix = loadgen.load_mix("decode_overload")
    a = loadgen.lm_schedule(mix, 2**33, 10.0, 151936)
    b = loadgen.lm_schedule(mix, 2**33, 10.0, 151936)
    np.testing.assert_array_equal(a.prompts, b.prompts)
    np.testing.assert_array_equal(a.arrival_s, b.arrival_s)
    k = mix.get("initial_backlog", 0)
    assert len(a) == k + int(np.ceil(mix["arrivals"]["rate_per_s"] * 10))
    assert np.all(a.arrival_s[:k] == 0) and np.all(a.arrival_s[k:] > 0)
    assert a.prompts.shape[1] == mix["prompt_len"]


def test_seed_key_takes_large_seeds():
    import jax

    k1, k2 = R.seed_key(2**31 + 1), R.seed_key(1)
    assert not np.array_equal(jax.random.key_data(k1),
                              jax.random.key_data(k2))
    assert np.array_equal(jax.random.key_data(R.seed_key(2**40)),
                          jax.random.key_data(R.seed_key(2**40)))
