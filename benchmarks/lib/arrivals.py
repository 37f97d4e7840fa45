"""Seeded request schedules: arrival times and length draws.

Everything a traffic file of the ``open_loop`` / ``closed_loop`` drivers
can say about its requests is drawn here from ``--seed``; the program
under test sees only the generated prompts.

The AMOUNT of work is fixed by the traffic file, not by the seed: an open
loop sends exactly ``round(rate x seconds)`` requests (a Poisson process
given its count: sorted uniform arrival times), and lengths are the evenly
spaced quantiles of their distribution in a seeded order. Two seeds differ
in when each request arrives and which length it has, not in how many
requests or tokens the run offers; a tail over a few hundred requests
would otherwise move with the draw of the few longest prompts.

A length spec is one of::

    {"dist": "lognormal", "median": 256, "sigma": 1.0, "min": 32, "max": 2048}
    {"dist": "uniform", "min": 16, "max": 64}
    {"dist": "fixed", "value": 128}
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from statistics import NormalDist
from typing import List

import numpy as np


@dataclass
class Request:
    index: int
    due_s: float            # offset from the window start (open loop)
    prompt: np.ndarray      # int32 token ids
    max_new: int
    client: int = 0         # closed loop: which client sends it


def quantile_lengths(spec: dict, n: int) -> np.ndarray:
    """The ``n`` evenly spaced quantiles ((i + 0.5) / n) of the length
    distribution, in ascending order."""
    dist = spec["dist"]
    u = (np.arange(n) + 0.5) / n
    if dist == "lognormal":
        z = np.array([NormalDist().inv_cdf(float(x)) for x in u])
        raw = np.exp(math.log(spec["median"]) + spec["sigma"] * z)
        raw = np.clip(np.rint(raw), spec["min"], spec["max"])
    elif dist == "uniform":
        raw = np.floor(spec["min"] + u * (spec["max"] + 1 - spec["min"]))
    elif dist == "fixed":
        raw = np.full(n, spec["value"])
    else:
        raise ValueError(f"unknown length distribution {dist!r}")
    return raw.astype(np.int64)


def draw_lengths(rs: np.random.RandomState, spec: dict, n: int,
                 block: int = 0) -> np.ndarray:
    """``n`` lengths: the quantiles of the distribution in a seeded order.
    With ``block``, every ``block`` consecutive lengths are the quantiles of
    their own (a run that uses only a prefix still sees the whole
    distribution)."""
    block = block or n
    out = []
    for start in range(0, n, block):
        out.append(rs.permutation(quantile_lengths(
            spec, min(block, n - start))))
    return np.concatenate(out) if out else np.zeros((0,), np.int64)


def poisson_times(rs: np.random.RandomState, rate: float,
                  seconds: float) -> np.ndarray:
    """Arrival offsets on [0, seconds) of a Poisson process of ``rate``/s,
    given that it has exactly ``round(rate x seconds)`` arrivals."""
    if rate <= 0:
        raise ValueError(f"rate must be > 0, got {rate}")
    return np.sort(rs.uniform(0.0, seconds, int(round(rate * seconds))))


def _prompts(rs, lengths, vocab) -> List[np.ndarray]:
    # id 0 is left out: the server pads prompts with it
    return [rs.randint(1, vocab, (int(n),)).astype(np.int32) for n in lengths]


def open_loop_schedule(seed: int, traffic: dict, vocab: int,
                       seconds: float) -> List[Request]:
    rs = np.random.RandomState(seed)
    due = poisson_times(rs, float(traffic["rate_rps"]), seconds)
    plen = draw_lengths(rs, traffic["prompt_len"], due.size)
    olen = draw_lengths(rs, traffic["output_len"], due.size)
    prompts = _prompts(rs, plen, vocab)
    return [Request(i, float(due[i]), prompts[i], int(olen[i]))
            for i in range(due.size)]


# a closed loop sends as many requests as the system answers: its lists are
# stratified in blocks, so any 16 consecutive requests of a client span
# the whole length distribution
CLOSED_BLOCK = 16


def closed_loop_schedule(seed: int, traffic: dict, vocab: int,
                         per_client: int) -> List[List[Request]]:
    """``clients`` lists of ``per_client`` requests; a client sends its
    next request when the previous one has been answered."""
    rs = np.random.RandomState(seed)
    out, index = [], 0
    for c in range(int(traffic["clients"])):
        plen = draw_lengths(rs, traffic["prompt_len"], per_client, CLOSED_BLOCK)
        olen = draw_lengths(rs, traffic["output_len"], per_client, CLOSED_BLOCK)
        prompts = _prompts(rs, plen, vocab)
        out.append([Request(index + i, 0.0, prompts[i], int(olen[i]), c)
                    for i in range(per_client)])
        index += per_client
    return out
