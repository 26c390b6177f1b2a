"""The one traffic generator: reads a mix's parameters from
`bench/traffic/<mix>.json` and turns them, with `--seed`, into what a
cell offers the system.

Every seed gets the same multiset of sizes and of inter-arrival gaps,
in another order: sizes are the quantiles (i + 1/2)/n of the stated
distribution, and the seed only permutes them and draws token ids. So
two seeds ask for the same amount of work, and a seed changes which
request waits behind which. The open-loop arrival idea and the tail
summary follow `repro.obs.loadlab` (`interarrival_gaps`,
`tail_summary`); the copy lives here so that no later change to the
program can change the yardstick.
"""

from __future__ import annotations

import dataclasses
import json
import math
import os
from statistics import NormalDist
from typing import Optional

import numpy as np

TRAFFIC_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "traffic")

# stream ids folded into the seed, so that no two draws share randomness
_ORDER_GAPS, _ORDER_LENS, _TOKENS, _SAMPLE, _PHASES = 1, 2, 3, 4, 5


def load_mix(name: str, traffic_dir: str = TRAFFIC_DIR) -> dict:
    """The parameters of traffic mix `name` (`<traffic_dir>/<name>.json`)."""
    with open(os.path.join(traffic_dir, f"{name}.json")) as f:
        mix = json.load(f)
    mix.setdefault("name", name)
    return mix


def rng(seed: int, stream: int) -> np.random.Generator:
    """A numpy generator for one named stream of a run's seed (any
    non-negative integer, 64-bit seeds included)."""
    return np.random.default_rng([int(seed), int(stream)])


def midpoint_quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lengths(spec: dict, n: int, seed: int, stream: int) -> np.ndarray:
    """(n,) int lengths: a fixed multiset drawn from `spec`, permuted by
    the seed. `spec` is {"dist": "fixed", "value": v} or
    {"dist": "lognormal", "median": m, "sigma": s, "min": lo, "max": hi}."""
    if spec["dist"] == "fixed":
        out = np.full(n, int(spec["value"]), np.int64)
    elif spec["dist"] == "lognormal":
        z = np.array([NormalDist().inv_cdf(u) for u in midpoint_quantiles(n)])
        out = np.rint(spec["median"] * np.exp(spec["sigma"] * z))
        out = np.clip(out, spec["min"], spec["max"]).astype(np.int64)
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    return rng(seed, stream).permutation(out)


def arrival_times(spec: dict, n: int, seed: int) -> np.ndarray:
    """(n,) intended arrival offsets in seconds from the window's start.

    `{"process": "poisson", "rate_per_s": r}`: exponential gaps of mean
    1/r at the midpoint quantiles, permuted by the seed (an open loop:
    requests are due whatever the system is doing)."""
    if spec["process"] != "poisson":
        raise ValueError(f"unknown arrival process {spec['process']!r}")
    rate = float(spec["rate_per_s"])
    gaps = -np.log1p(-midpoint_quantiles(n)) / rate
    return np.cumsum(rng(seed, _ORDER_GAPS).permutation(gaps))


def fleet_arrivals(n_patients: int, segments_per_patient: int,
                   period_s: float, seed: int):
    """(patients, seqs, arrival_s) of every segment of a patient fleet,
    sorted by arrival. Patient p's segment k arrives at phase_p + k *
    period_s: each implant records on its own clock, so the phases are
    the midpoint quantiles of [0, period_s), one a patient, and the seed
    deals them out. Every seed gets the same arrival times."""
    p, k = int(n_patients), int(segments_per_patient)
    phase = rng(seed, _PHASES).permutation(midpoint_quantiles(p) * period_s)
    t = (phase[:, None] + np.arange(k)[None, :] * period_s).ravel()
    pat = np.repeat(np.arange(p), k)
    seq = np.tile(np.arange(k), p)
    order = np.lexsort((seq, pat, t))
    return pat[order], seq[order], t[order]


@dataclasses.dataclass
class LMSchedule:
    """What an LM cell offers: request i arrives at `arrival_s[i]` with
    `prompts[i]` and asks for `max_new[i]` tokens."""

    arrival_s: np.ndarray
    prompts: np.ndarray  # (n, prompt_len) int32
    max_new: np.ndarray

    def __len__(self) -> int:
        return len(self.arrival_s)


def lm_schedule(mix: dict, seed: int, seconds: float,
                vocab: int) -> LMSchedule:
    """The requests of one window: as many as the rate brings in
    `seconds`, all of one prompt length (see the mix's `prompt_len`),
    after the mix's `initial_backlog` requests (if any) that are already
    due when the window opens, as in a queue that has been overloaded
    for a while."""
    rate = float(mix["arrivals"]["rate_per_s"])
    k = int(mix.get("initial_backlog", 0))
    n = k + max(1, math.ceil(rate * seconds))
    plen = int(mix["prompt_len"])
    toks = rng(seed, _TOKENS).integers(0, vocab, (n, plen), np.int32)
    return LMSchedule(
        arrival_s=np.concatenate(
            [np.zeros(k), arrival_times(mix["arrivals"], n - k, seed)]),
        prompts=toks,
        max_new=lengths(mix["output_len"], n, seed, _ORDER_LENS),
    )


def sample(seed: int, items: list, k: int,
           must: Optional[list] = None) -> list:
    """k items drawn from the seed, with every item of `must` in."""
    must = list(must or [])
    rest = [x for x in items if x not in must]
    r = rng(seed, _SAMPLE)
    take = max(0, min(k - len(must), len(rest)))
    picked = [rest[i] for i in sorted(r.choice(len(rest), take,
                                               replace=False))]
    return must + picked


def percentile(xs, q: float) -> Optional[float]:
    """q-th percentile (0..100) of raw samples, numpy's linear rule;
    None when there is no sample."""
    xs = np.asarray(list(xs), np.float64)
    return float(np.percentile(xs, q)) if xs.size else None
