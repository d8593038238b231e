"""The one traffic generator. A mix is a data file beside this module,
``<mix>.json``, that names its driver and its parameters.

Every seed gets the same set of sizes and gaps, drawn once from the mix's
own ``sizes_seed``; ``--seed`` draws the token ids and, where the mix sets
``order_block`` above 1, orders sizes and gaps within consecutive blocks of
that many requests (so every stretch of the run still sees the same mix).
So two seeds do the same amount of work, and a difference between their
runs is noise, not load. An open loop whose tails depend on how arrivals
line up keeps one order for every seed (``order_block`` 1).
"""
from __future__ import annotations

import json
import os

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))


def load(name: str) -> dict:
    with open(os.path.join(HERE, f"{name}.json")) as f:
        return json.load(f)


def lognormal_lengths(spec: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    """``n`` integer lengths, lognormal around ``median`` with log-sd
    ``sigma``, clipped to [``min``, ``max``]."""
    x = rng.lognormal(np.log(spec["median"]), spec["sigma"], n)
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


def block_order(n: int, rng: np.random.Generator, block: int) -> np.ndarray:
    """A permutation of range(n) that only moves items within blocks."""
    order = np.arange(n)
    for i in range(0, n, block):
        rng.shuffle(order[i:i + block])
    return order


def requests(mix: dict, n: int, vocab: int, seed: int) -> list:
    """``n`` requests of a serving mix: dicts with ``due_s`` (offset from
    the start of arrivals), ``prompt`` (int32 ids), ``max_new`` and
    ``sample_seed``. Open-loop mixes space arrivals by exponential gaps at
    ``rate_per_s``; a backlog has every request due at 0."""
    fixed = np.random.default_rng(mix["sizes_seed"])
    plen = lognormal_lengths(mix["prompt"], n, fixed)
    olen = lognormal_lengths(mix["output"], n, fixed)
    rate = mix.get("rate_per_s")
    gaps = fixed.exponential(1.0 / rate, n) if rate else np.zeros(n)
    rng = np.random.default_rng(seed)
    plen, olen, gaps = (a[block_order(n, rng, mix["order_block"])] for a in (plen, olen, gaps))
    due = np.cumsum(gaps) - gaps[0]
    out = []
    for i in range(n):
        out.append({
            "due_s": float(due[i]),
            "prompt": rng.integers(0, vocab, int(plen[i]), dtype=np.int32),
            "max_new": int(olen[i]),
            "sample_seed": int(rng.integers(0, 2**31 - 1)),
        })
    return out
