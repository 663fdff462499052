"""Seeded traffic: open-loop request streams and packed training rows.

Arrivals are a Poisson process conditioned on its count in each stretch
of the run (warm-up, window, drain): every seed's window holds the same
number of requests, rate x length, at independent uniform times, so the
bursts and lulls of a Poisson stream stay and the amount of work does
not change with the seed. The lengths of a stretch are the same
stratified quantiles of their distributions for every seed, shuffled.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from statistics import NormalDist
from typing import List, Sequence

import numpy as np

_NORMAL = NormalDist()


def rng_for(seed: int, *stream: int) -> np.random.Generator:
    """An independent numpy generator per (seed, stream...); any int seed."""
    return np.random.default_rng([int(seed) & 0xFFFFFFFF, int(seed) >> 32,
                                  *stream])


def lognormal_quantiles(n: int, median: float, sigma: float, lo: int,
                        hi: int) -> List[int]:
    """n stratified draws of a lognormal, rounded and clipped to [lo, hi]."""
    out = []
    for i in range(n):
        z = _NORMAL.inv_cdf((i + 0.5) / n)
        out.append(min(hi, max(lo, round(median * math.exp(sigma * z)))))
    return out


@dataclass(frozen=True)
class Arrival:
    due_s: float          # seconds after the stream starts
    prompt_len: int
    output_len: int
    client: int


def open_loop(spec: dict, seed: int,
              stretches: Sequence[float]) -> List[Arrival]:
    """The arrivals of consecutive stretches of the given lengths in
    seconds, described by a traffic file's fields: ``rate_per_s``,
    ``clients``, ``prompt`` and ``output`` (each ``{"median", "sigma",
    "min", "max"}``). A stretch of length d holds round(rate x d)
    requests, due at independent uniform times within it."""
    rate = float(spec["rate_per_s"])
    p, o = spec["prompt"], spec["output"]
    clients = int(spec["clients"])
    out: List[Arrival] = []
    start = 0.0
    for k, length in enumerate(stretches):
        n = round(rate * length)
        rng = rng_for(seed, 1, k)
        due = np.sort(start + length * rng.random(n))
        prompts = rng.permutation(lognormal_quantiles(
            n, p["median"], p["sigma"], p["min"], p["max"]))
        outputs = rng.permutation(lognormal_quantiles(
            n, o["median"], o["sigma"], o["min"], o["max"]))
        for i in range(n):
            out.append(Arrival(float(due[i]), int(prompts[i]),
                               int(outputs[i]), len(out) % clients))
        start += length
    return out


def prompt_tokens(seed: int, index: int, length: int,
                  vocab: int) -> List[int]:
    return rng_for(seed, 2, index).integers(
        0, vocab, size=length).tolist()


def packed_rows(spec: dict, seed: int, step: int, vocab: int) -> dict:
    """One training batch of documents packed into rows.

    Documents have lognormal lengths (``doc`` = {"median", "sigma",
    "min", "max"}) and are separated by ``eos_id``; tokens follow the
    Zipf-plus-Markov draw of the program's synthetic corpus
    (`train/data.py` SyntheticLM, copied here), seeded by (seed, step), so
    every step's rows differ. Labels are the next token."""
    b, s = int(spec["batch"]), int(spec["seq_len"])
    d = spec["doc"]
    rng = rng_for(seed, 3, step)
    v = min(vocab, 4096)
    probs = 1.0 / np.arange(1, v + 1) ** 1.1
    probs /= probs.sum()
    shift = int(rng_for(seed, 4).integers(1, v))
    eos = int(spec["eos_id"])
    rows = np.empty((b, s + 1), np.int32)
    for r in range(b):
        filled = 0
        while filled < s + 1:
            z = rng.standard_normal()
            n = int(min(d["max"], max(d["min"], round(
                d["median"] * math.exp(d["sigma"] * z)))))
            doc = rng.choice(v, size=n, p=probs)
            doc[1::2] = (doc[0::2][:doc[1::2].shape[0]] + shift) % v
            take = min(n, s + 1 - filled)
            rows[r, filled:filled + take] = doc[:take]
            filled += take
            if filled < s + 1:
                rows[r, filled] = eos
                filled += 1
    return {"tokens": rows[:, :s], "labels": rows[:, 1:]}
