"""Build a tiny copy of the benchmark's cells under a temporary root, so
that the harness can run end to end on the CPU.

  python -m bench.tests.rehearse <root> <cell> [--seed n] [--seconds s]
      [--trace 0|1]

runs one cell of a prepared root in this process and prints its result
as the last stdout line; `run_child` starts that in a child process on
as many forced host devices as a multi-chip cell needs."""

from __future__ import annotations

import argparse
import json
import os
import shutil
import subprocess
import sys

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


def make_root(tmp: str, root: str = ROOT) -> str:
    """A benchmark root with two tiny cells, `tiny_va.fleet` and
    `tiny_lm.serve`, over the real systems and metrics, with limits of
    their own. The metrics' entries come from `<root>/BENCHMARK.json`;
    each `workloads` list keeps the tiny stand-ins of the cells it
    names, and drops any other cell."""
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
    with open(os.path.join(root, "BENCHMARK.json")) as f:
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
            m["workloads"] = sorted({rename[w] for w in m["workloads"]
                                     if w in rename})
    with open(os.path.join(tmp, "BENCHMARK.json"), "w") as f:
        json.dump(bm, f)
    return tmp


def read_json(path: str):
    with open(path) as f:
        return json.load(f)


def write_json(obj, path: str) -> None:
    with open(path, "w") as f:
        json.dump(obj, f)


def add_cell(root: str, cell: str, *, mix: dict, config=None, chips: int = 1,
             system=None, readers=None, reports: str = "lm_tokens_per_s"):
    """Add `cell` (`<config>.<traffic>`) to the prepared root `root` as a
    later change would, with new files and entries alone: a
    configuration, `tiny_lm`'s updated by `config`, beside a copy of
    its reference; the mix; where given, the source of the system the
    configuration names; per-layer readers `{name: (layer, unit,
    expression of r)}`. The cell joins the `workloads` of the
    end-to-end metric `reports`, which its readers move."""
    b = os.path.join(root, "bench")
    name, traffic = cell.split(".")
    cfg = read_json(os.path.join(b, "configs", "tiny_lm.json"))
    cfg.update(config or {}, name=name)
    write_json(cfg, os.path.join(b, "configs", f"{name}.json"))
    shutil.copy(os.path.join(b, "configs", "tiny_lm.py"),
                os.path.join(b, "configs", f"{name}.py"))
    if system is not None:
        with open(os.path.join(b, "systems", f"{cfg['system']}.py"),
                  "w") as f:
            f.write(system)
    write_json(mix, os.path.join(b, "traffic", f"{traffic}.json"))
    bm = read_json(os.path.join(root, "BENCHMARK.json"))
    bm["configs"].append({"name": name, "source": "tiny",
                          "file": f"bench/configs/{name}.json",
                          "reduced": [], "why": "new"})
    bm["workloads"].append({"name": cell, "config": name,
                            "traffic": traffic, "chips": chips,
                            "why": "new"})
    for m in bm["end_to_end"]:
        if m["name"] == reports:
            m["workloads"].append(cell)
    for metric, (layer, unit, expr) in (readers or {}).items():
        with open(os.path.join(b, "metrics", f"{metric}.py"), "w") as f:
            f.write(f"LAYER = {layer!r}\nUNIT = {unit!r}\n"
                    f"MOVES = {reports!r}\nSOURCE = \"program_counter\""
                    f"\n\n\ndef read(r):\n    return {expr}\n")
        bm["per_layer"].append({"name": metric, "unit": unit,
                                "better": "higher",
                                "source": "program_counter",
                                "layer": layer, "moves": reports,
                                "workloads": [cell]})
    write_json(bm, os.path.join(root, "BENCHMARK.json"))


def _on_path() -> None:
    for p in (os.path.join(ROOT, "src"), ROOT):
        if p not in sys.path:
            sys.path.insert(0, p)


def run_in(root: str, cell: str, *, seed: int = 3, seconds: float = 1.0,
           trace: bool = False, faults=None) -> dict:
    """One run of a cell of the prepared root `root` on whatever JAX
    has, the chip check skipped: the device is as JAX reports it, and a
    cell that asks for more devices than there are is refused."""
    _on_path()
    from bench import peaks
    from bench import run as R

    spec = R.resolve(cell, root)
    return R.run_cell(spec, seed, seconds, trace,
                      device=R.devices_for(spec.chips),
                      peak=peaks.PEAKS["TPU v5 lite"], faults=faults)


def run(cell: str, tmp: str, **kw) -> dict:
    """One run of a tiny cell of a root made under `tmp` (`run_in`)."""
    return run_in(make_root(tmp), cell, **kw)


def run_child(root: str, cell: str, devices: int, *, seed: int = 3,
              seconds: float = 1.0, trace: bool = False,
              timeout: float = 240.0) -> dict:
    """`run_in` in a child process whose CPU backend has `devices`
    devices, set before JAX is imported there; its parsed result line.
    A child that outlives `timeout` seconds is killed and raises
    `subprocess.TimeoutExpired`."""
    flags = os.environ.get("XLA_FLAGS", "")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"{flags} --xla_force_host_platform_device_count="
                         f"{devices}".strip(),
               PYTHONPATH=os.pathsep.join([os.path.join(ROOT, "src"), ROOT]))
    p = subprocess.run(
        [sys.executable, "-m", "bench.tests.rehearse", root, cell,
         "--seed", str(seed), "--seconds", str(seconds),
         "--trace", str(int(trace))],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=timeout)
    if p.returncode != 0:
        raise RuntimeError(f"rehearsal of {cell} exited {p.returncode}:\n"
                           + p.stderr[-4000:])
    return json.loads(p.stdout.strip().splitlines()[-1])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description="one run of a cell of a "
                                 "prepared root, the chip check skipped")
    ap.add_argument("root")
    ap.add_argument("cell")
    ap.add_argument("--seed", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=1.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    out = run_in(args.root, args.cell, seed=args.seed, seconds=args.seconds,
                 trace=bool(args.trace))
    print(json.dumps(out, default=lambda o: o.item()), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
