"""Build a tiny copy of the benchmark's cells under a temporary root, so
that the harness can run end to end on the CPU."""

from __future__ import annotations

import json
import os
import shutil

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)

TINY_LM = dict(hidden_size=64, intermediate_size=128, num_attention_heads=4,
               num_key_value_heads=2, head_dim=16, num_hidden_layers=2,
               vocab_size=512)
TINY_LM_MIX = {
    "kind": "lm_open_loop", "slots": 4, "page_size": 16, "max_seq": 64,
    "prompt_len": 16,
    "output_len": {"dist": "lognormal", "median": 8, "sigma": 0.5,
                   "min": 2, "max": 24},
    "arrivals": {"process": "poisson", "rate_per_s": 60.0},
    "check_requests": 64,
}
# Limits at the tiny sizes, on the CPU: there the program's VA path
# answers as the reference does on every sampled row (share 0.0; one
# wrong row of the ~30 sampled reads 0.03) and its LM (bfloat16 matmuls,
# vocabulary 512) reads at most a few hundredths, while each planted
# fault reads 0.3 and more.
TINY_LIMITS = {"tiny_va": {"va_wrong_share": 0.02},
               "tiny_lm": {"lm_token_gap": 0.15}}

TINY_VA_MIX = {
    "kind": "va_fleet", "n_patients": 48, "va_fraction": 0.5,
    "buckets": [8, 32],
    "max_wait_s": 0.256, "segments_per_patient": 12, "path": "twin",
    "check_batches": 4,
}


def make_root(tmp: str) -> str:
    """A benchmark root with two tiny cells, `tiny_va.fleet` and
    `tiny_lm.serve`, over the real systems and metrics, with limits of
    their own."""
    b = os.path.join(tmp, "bench")
    for d in ("systems", "metrics"):
        shutil.copytree(os.path.join(BENCH, d), os.path.join(b, d))
    shutil.copy(os.path.join(BENCH, "__init__.py"), b)
    os.makedirs(os.path.join(b, "configs"))
    os.makedirs(os.path.join(b, "traffic"))
    with open(os.path.join(BENCH, "configs", "qwen3_8b.json")) as f:
        lm = json.load(f)
    lm.update(TINY_LM, name="tiny_lm")
    with open(os.path.join(BENCH, "configs", "va_cnn.json")) as f:
        va = json.load(f)
    va["name"] = "tiny_va"
    for name, cfg, src in (("tiny_lm", lm, "qwen3_8b"),
                           ("tiny_va", va, "va_cnn")):
        cfg["limits"] = TINY_LIMITS[name]
        with open(os.path.join(b, "configs", f"{name}.json"), "w") as f:
            json.dump(cfg, f)
        shutil.copy(os.path.join(BENCH, "configs", f"{src}.py"),
                    os.path.join(b, "configs", f"{name}.py"))
    for name, mix in (("serve", TINY_LM_MIX), ("fleet", TINY_VA_MIX)):
        with open(os.path.join(b, "traffic", f"{name}.json"), "w") as f:
            json.dump(mix, f)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bm = json.load(f)
    rename = {"va_cnn.fleet_full": "tiny_va.fleet",
              "qwen3_8b.decode_overload": "tiny_lm.serve"}
    bm["configs"] = [
        {"name": n, "source": "tiny", "file": f"bench/configs/{n}.json",
         "reduced": [], "why": "tiny"} for n in ("tiny_va", "tiny_lm")]
    bm["workloads"] = [
        {"name": "tiny_va.fleet", "config": "tiny_va", "traffic": "fleet",
         "chips": 1, "why": "tiny"},
        {"name": "tiny_lm.serve", "config": "tiny_lm", "traffic": "serve",
         "chips": 1, "why": "tiny"},
    ]
    for m in bm["end_to_end"] + bm["per_layer"]:
        if "workloads" in m:
            m["workloads"] = sorted({rename[w] for w in m["workloads"]})
    with open(os.path.join(tmp, "BENCHMARK.json"), "w") as f:
        json.dump(bm, f)
    return tmp


def run(cell: str, tmp: str, *, seed: int = 3, seconds: float = 1.0,
        trace: bool = False, faults=None) -> dict:
    """One run of a tiny cell on whatever JAX has, the chip check
    skipped."""
    import sys

    for p in (os.path.join(ROOT, "src"), ROOT):
        if p not in sys.path:
            sys.path.insert(0, p)
    from bench import peaks
    from bench import run as R

    spec = R.resolve(cell, make_root(tmp))
    return R.run_cell(spec, seed, seconds, trace,
                      device={"platform": "cpu", "kind": "cpu", "count": 1},
                      peak=peaks.PEAKS["TPU v5 lite"], faults=faults)
