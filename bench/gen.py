"""The one traffic generator: reads a mix's parameters from
`bench/traffic/<mix>.json` and makes a run's requests from `--seed`.

Every seed gets the same multiset of sizes and arrival gaps, in another
order: lengths sit at evenly spaced quantiles of the mix's distributions and
gaps at evenly spaced quantiles of the exponential, and the seed only
shuffles them and draws the token ids.  So two seeds do the same work, and
the spread between runs is the system's, not the traffic's.

Mix keys:
  loop       "open" (arrivals on a schedule, at the cell's `rate_per_s`) or
             "closed" (`clients` clients, each sending its next request when
             its reply is complete)
  prompt, output   {"dist": "lognormal", "median", "sigma", "min", "max"} or
             {"dist": "uniform", "min", "max"} (token counts, clipped)
"""
from __future__ import annotations

import dataclasses
import json
import math
from pathlib import Path
from statistics import NormalDist

import numpy as np

BENCH = Path(__file__).resolve().parent


@dataclasses.dataclass(frozen=True)
class Request:
    prompt: np.ndarray        # int32 token ids
    max_new: int
    due_s: float              # open loop: offset from the window's start


def load_mix(name: str) -> dict:
    path = BENCH / "traffic" / f"{name}.json"
    if not path.is_file():
        raise FileNotFoundError(f"no traffic mix {name!r} ({path})")
    return json.loads(path.read_text())


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lengths(spec: dict, n: int) -> np.ndarray:
    """n lengths at evenly spaced quantiles of `spec`, clipped, ascending."""
    q = _quantiles(n)
    if spec["dist"] == "lognormal":
        z = np.array([NormalDist().inv_cdf(p) for p in q])
        x = spec["median"] * np.exp(spec["sigma"] * z)
    elif spec["dist"] == "uniform":
        x = spec["min"] + q * (spec["max"] - spec["min"])
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


def _rng(seed: int) -> np.random.Generator:
    # seeds may exceed 32 (and 63) bits: numpy takes any non-negative int
    return np.random.default_rng(abs(int(seed)))


def make_requests(mix: dict, seed: int, n: int, vocab: int, *,
                  rate_per_s: float = 0.0) -> "list[Request]":
    """n requests of `mix` for `seed`.  Open loop: due times whose gaps are
    the exponential's quantiles at `rate_per_s`, shuffled; closed loop: due
    times are 0 (the client sends as soon as it may)."""
    rng = _rng(seed)
    plens = rng.permutation(lengths(mix["prompt"], n))
    outs = rng.permutation(lengths(mix["output"], n))
    if mix["loop"] == "open":
        if rate_per_s <= 0:
            raise ValueError("an open-loop mix needs the cell's rate_per_s")
        gaps = rng.permutation(-np.log1p(-_quantiles(n)) / rate_per_s)
        due = np.cumsum(gaps)    # all n arrivals end at the same time
    elif mix["loop"] == "closed":
        due = np.zeros(n)
    else:
        raise ValueError(f"unknown loop {mix['loop']!r}")
    return [Request(rng.integers(0, vocab, int(p), dtype=np.int32), int(o),
                    float(d)) for p, o, d in zip(plens, outs, due)]


def count_for(mix: dict, cell: dict, seconds: float) -> int:
    """How many requests a run makes: an open loop's arrivals over the
    window; for a closed loop, three per client (about what a window of
    long requests completes), which the clients cycle through."""
    if mix["loop"] == "open":
        return max(1, math.ceil(cell["rate_per_s"] * seconds))
    return 3 * mix["clients"]
