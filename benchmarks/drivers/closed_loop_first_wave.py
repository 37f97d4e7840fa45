"""Closed loop (``closed_loop``'s, every parameter as there) whose FIRST
wave is dealt across the callers: caller ``c``'s first prompt is one of
the ``clients`` evenly spaced quantiles of the length distribution, each
quantile once, in a seeded order; its first answer likewise.

For a cell whose answers outlast the window, so that the callers' first
requests are all the run ever sends. ``arrivals.closed_loop_schedule``
deals each caller its OWN quantiles in a seeded order, so the first wave
is ``clients`` independent draws and its total work follows the seed
(32 callers of lognormal sigma 0.7: the sum of the prompts spreads 10%
over seeds, and a decode round's cache reads with it), against the
generator's own rule that the AMOUNT of work is fixed by the traffic
file, not by the seed. Here two seeds differ in which caller has which
length, not in the lengths. A caller's later requests are as
``closed_loop`` deals them.
"""
import numpy as np

from benchmarks.lib import arrivals, harness, serve_loop


def deal_first_wave(clients: list, seed: int, traffic: dict, vocab: int
                    ) -> list:
    """``clients`` (``arrivals.closed_loop_schedule``'s lists) with each
    caller's first request replaced by its share of the first wave."""
    n = len(clients)
    rs = np.random.RandomState((seed + 7) % (2 ** 32))
    plen = rs.permutation(arrivals.quantile_lengths(traffic["prompt_len"], n))
    olen = rs.permutation(arrivals.quantile_lengths(traffic["output_len"], n))
    for c, reqs in enumerate(clients):
        # id 0 is left out: the server pads prompts with it
        prompt = rs.randint(1, vocab, (int(plen[c]),)).astype(np.int32)
        reqs[0] = arrivals.Request(reqs[0].index, 0.0, prompt, int(olen[c]),
                                   c)
    return clients


def run(run: harness.Run) -> harness.Result:
    dealt = arrivals.closed_loop_schedule

    def schedule(seed, traffic, vocab, per_client):
        return deal_first_wave(dealt(seed, traffic, vocab, per_client),
                               seed, traffic, vocab)

    # serve_loop asks the arrivals module for the schedule by name
    arrivals.closed_loop_schedule = schedule
    try:
        return serve_loop.run_serving(run, "closed")
    finally:
        arrivals.closed_loop_schedule = dealt
