"""The one general traffic generator: every mix file under ``traffic/`` is read here.

A mix is data. What it may say (all lengths in tokens, times in seconds):

``runner``            the kind of cell it drives (a file under ``runners/``)
``loop``              ``closed`` (``clients`` callers, each sends its next
                      request when the last resolves) or ``open`` (arrivals on
                      a schedule, whatever the system does)
``prompt``/``output`` ``{"dist": "lognormal", "median", "sigma", "min", "max"}``
``block``             requests come in blocks of this many; every block holds
                      the same set of (prompt, output) sizes, so every seed and
                      every stretch of a run does the same work in another order
``arrivals``          open loop: ``{"rate_per_s"}``; gaps are the exponential's
                      quantiles, shuffled, so every seed offers the same load

Sizes are stratified, not sampled: with a seed changing only the ORDER, two
seeds differ by noise and not by the luck of the draw.
"""

from __future__ import annotations

import dataclasses
from statistics import NormalDist

import numpy as np


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """Independent numpy streams from one run seed (any whole number >= 0)."""
    return np.random.default_rng([int(seed), int(stream)])


def stratified_lengths(spec: dict, n: int) -> np.ndarray:
    """``n`` lengths at the distribution's evenly spaced quantiles, clipped."""
    if spec["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    nd = NormalDist()
    z = np.array([nd.inv_cdf((i + 0.5) / n) for i in range(n)])
    raw = float(spec["median"]) * np.exp(float(spec["sigma"]) * z)
    return np.clip(np.rint(raw), int(spec["min"]), int(spec["max"])).astype(
        np.int64)


@dataclasses.dataclass(frozen=True)
class Request:
    prompt: np.ndarray   # int32 token ids
    n_out: int           # tokens asked for
    due_s: float         # open loop: seconds after traffic starts; else 0


def request_sizes(mix: dict, n_blocks: int, seed: int) -> np.ndarray:
    """``[n_blocks * block, 2]`` (prompt, output) sizes: one fixed set of
    pairs per block (pairing drawn once, from the mix's own ``pair_seed``),
    each block in an order drawn from ``seed``."""
    block = int(mix["block"])
    prompts = stratified_lengths(mix["prompt"], block)
    outputs = stratified_lengths(mix["output"], block)
    pairing = rng_for(int(mix.get("pair_seed", 0)), 1).permutation(block)
    pairs = np.stack([prompts, outputs[pairing]], axis=1)
    order = rng_for(seed, 2)
    return np.concatenate([pairs[order.permutation(block)]
                           for _ in range(n_blocks)])


def arrival_times(mix: dict, n: int, seed: int) -> np.ndarray:
    """Due times of ``n`` open-loop requests: unit-rate gaps are the
    exponential's stratified quantiles, shuffled per block, summed and
    divided by the mix's rate."""
    block = int(mix["block"])
    rate = float(mix["arrivals"]["rate_per_s"])
    q = (np.arange(block) + 0.5) / block
    unit_gaps = -np.log1p(-q)
    unit_gaps *= 1.0 / unit_gaps.mean()      # quantile means run a hair low
    order = rng_for(seed, 3)
    gaps = np.concatenate([unit_gaps[order.permutation(block)]
                           for _ in range(-(-n // block))])[:n]
    return np.cumsum(gaps) / rate


def serve_requests(mix: dict, vocab: int, n: int, seed: int) -> "list[Request]":
    """``n`` requests of a serving mix: sizes, token ids and due times, all
    from ``seed``. Token ids are uniform over the vocabulary."""
    block = int(mix["block"])
    sizes = request_sizes(mix, -(-n // block), seed)[:n]
    due = (arrival_times(mix, n, seed) if mix["loop"] == "open"
           else np.zeros(n))
    tok = rng_for(seed, 4)
    return [Request(tok.integers(0, vocab, int(p_len), np.int32), int(n_out),
                    float(t))
            for (p_len, n_out), t in zip(sizes, due)]


def percentile(values, q: float) -> float:
    """The ``q``-th percentile (0..100) by linear interpolation."""
    return float(np.percentile(np.asarray(values, np.float64), q))
