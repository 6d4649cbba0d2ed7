"""Open-loop request schedule from a traffic file and a seed.

Three phases run back to back at the file's rate: a warm-up of
``warmup_s`` seconds, the measured window of ``seconds``, and a tail of
``drain_limit_s`` seconds that keeps arrivals, and their prefill, on
while the window's requests finish.  The tail's requests are not
measured, and their outputs are cut to ``tail_max_new`` tokens so that
the server goes idle soon after the harness stops the arrivals.  Each
phase holds a fixed number of requests, ``round(rate * length)``:

* inter-arrival gaps are the quantiles (i + 1/2) / n of a Gamma
  distribution with the file's mean (1 / rate) and coefficient of
  variation (1 is Poisson, above 1 is bursty), scaled to fill the phase
  exactly, in an order shuffled once per phase (so that one phase's
  length leaves the others' schedules as they are);
* prompt and output lengths are the same quantiles of a lognormal with
  the file's median and sigma, clipped to [min, max], each shuffled once;
* prompt tokens are drawn from the seed, uniformly over the vocabulary,
  so no two prompts share a block.

Every seed gets the same sizes and arrival times: the order in which
they come sets the tails (which requests collide), and a seed that
reordered them moved TTFT p90 by half between seeds against 3% between
two runs of one seed (internlm2-chat on a TPU v5e).  The seed draws the
tokens, the weights and the requests the check samples.
"""
from __future__ import annotations

import dataclasses
from typing import List

import numpy as np
from scipy import special


@dataclasses.dataclass
class Item:
    due: float            # seconds after the schedule starts
    prompt: np.ndarray    # (S,) int32
    max_new: int
    phase: str            # "warmup" | "window" | "tail"


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def _lengths(spec: dict, n: int, rng) -> np.ndarray:
    # the lognormal's quantiles, as scipy.stats.lognorm.ppf computes them
    # (scipy.special alone imports in a third of the time)
    q = np.exp(spec["sigma"] * special.ndtri(_quantiles(n))) * spec["median"]
    lens = np.clip(np.round(q), spec["min"], spec["max"]).astype(int)
    return rng.permutation(lens)


def _gaps(rate: float, cv: float, n: int, length: float, rng) -> np.ndarray:
    shape = 1.0 / (cv * cv)
    g = special.gammaincinv(shape, _quantiles(n)) * (1.0 / (rate * shape))
    g = g * (length / g.sum())
    return rng.permutation(g)


ORDER_SEED = 0      # the shuffles of sizes and gaps, for every seed
PHASES = ("warmup", "window", "tail")


def schedule(traffic: dict, seed: int, seconds: float,
             vocab: int) -> List[Item]:
    """The whole schedule, sorted by due time."""
    rng = np.random.default_rng(seed)
    rate, cv = float(traffic["rate_rps"]), float(traffic["interarrival_cv"])
    items: List[Item] = []
    start = 0.0
    lengths = (traffic["warmup_s"], seconds, traffic["drain_limit_s"])
    for k, (phase, length) in enumerate(zip(PHASES, lengths)):
        order = np.random.default_rng([ORDER_SEED, k])
        n = max(int(round(rate * length)), 1)
        gaps = _gaps(rate, cv, n, float(length), order)
        plens = _lengths(traffic["prompt"], n, order)
        outs = _lengths(traffic["output"], n, order)
        if phase == "tail":
            outs = np.minimum(outs, traffic["tail_max_new"])
        # the first arrival of a phase is at its start; the gaps fill it
        due = start + np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
        for t, s, m in zip(due, plens, outs):
            items.append(Item(float(t), rng.integers(
                0, vocab, int(s), dtype=np.int32), int(m), phase))
        start += float(length)
    return items
