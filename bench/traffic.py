"""The one traffic generator.  A mix is a file of parameters
(``bench/traffic/<name>.json``); this module turns it, a rate and a seed into
the requests of one run.

Every seed gets the same work: the multiset of prompt lengths, output
lengths and arrival gaps is drawn once from the mix's own ``sample_seed``;
the run's ``--seed`` only permutes it and draws the prompt token ids.  So
two seeds differ in order and content, not in how much they ask for.

Kinds:
  ``open_loop``  requests due on a schedule of gaps, whatever the server
                 does; the rate (requests/s) is the cell's.  The schedule
                 covers the ramp, the window and ``tail_s`` beyond it, so
                 the queue never runs dry inside the window.
  ``backlog``    ``requests`` requests, all due when the run starts.
"""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np


@dataclass
class Planned:
    rid: int
    due_s: float            # seconds after the schedule starts
    prompt: np.ndarray      # (P,) int32 token ids
    max_new: int


def _lengths(dist: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    if dist["dist"] == "lognormal":
        x = np.exp(np.log(dist["median"]) + dist["sigma"]
                   * rng.standard_normal(n))
    elif dist["dist"] == "uniform":
        x = rng.uniform(dist["min"], dist["max"] + 1, n)
    else:
        raise ValueError(f"length distribution {dist['dist']!r}")
    return np.clip(np.floor(x), dist["min"], dist["max"]).astype(np.int64)


def _gaps(arr: dict, n: int, rate: float,
          rng: np.random.Generator) -> np.ndarray:
    """``n`` gaps whose mean is exactly 1/rate."""
    if arr["process"] == "gamma":
        g = rng.gamma(arr["shape"], 1.0, n)
    elif arr["process"] == "poisson":
        g = rng.exponential(1.0, n)
    else:
        raise ValueError(f"arrival process {arr['process']!r}")
    return g * (n / rate) / g.sum()


def request_count(mix: dict, seconds: float, rate: float | None) -> int:
    if mix["kind"] == "backlog":
        return int(mix["requests"])
    horizon = mix["ramp_s"] + seconds + mix["tail_s"]
    return int(np.ceil(rate * horizon))


def generate(mix: dict, seed: int, seconds: float, vocab: int,
             rate: float | None = None) -> list[Planned]:
    """The requests of one run, in order of due time."""
    n = request_count(mix, seconds, rate)
    base = np.random.default_rng(mix["sample_seed"])
    plens = _lengths(mix["prompt_tokens"], n, base)
    outs = _lengths(mix["output_tokens"], n, base)
    run = np.random.default_rng(seed % 2 ** 64)
    order = run.permutation(n)
    plens, outs = plens[order], outs[order]
    if mix["kind"] == "open_loop":
        gaps = _gaps(mix["arrivals"], n, rate, base)[run.permutation(n)]
        due = np.cumsum(gaps) - gaps[0]
    elif mix["kind"] == "backlog":
        due = np.zeros(n)
    else:
        raise ValueError(f"traffic kind {mix['kind']!r}")
    return [Planned(rid=i, due_s=float(due[i]),
                    prompt=run.integers(0, vocab, int(plens[i]),
                                        dtype=np.int32),
                    max_new=int(outs[i]))
            for i in range(n)]
