"""Closed loop (``closed_loop``'s, every parameter as there) for a model
that generates by diffusion over blocks: ``correct`` replays the sampled
requests step by step.

``serve_loop.check_outputs`` asks the reference for NEXT-token logits
under a causal mask, which is not what such a model computes: a logit at
position ``i`` is for the token AT ``i``, a block is bidirectional, and
what a request's tokens were picked from is the block's state at the
denoising step that unmasked each. So for the length of the run the check
is this file's: for a seeded sample of completed requests it takes the
tokens AND the denoising step at which each was unmasked from the
request's handle (``GenerateHandle.unmask_steps``) and has the reference
replay every step of every block
(``references/<builder>.py::check_request``). It holds, in the harness's
own tolerance (``serve_loop.LOGIT_TOL``, 2^-5 of the position's largest
reference logit magnitude: 8 bf16 ulps of the logit range; an 8-bit float
is 2^-2 off): (a) every unmasked token's reference logit within the
tolerance of the reference's top logit at that position and step; (b)
every unmasked position's reference log-confidence within the same band
of what the rule asks (the threshold's, or the quota-th best of the
positions still masked). The harness's other conditions (budget returned,
no thread left, no callback error, no client exhausted) stay its own.
"""
import numpy as np

from benchmarks.lib import harness, serve_loop

# the replay of a request is ONE forward over its final tokens and a copy
# of each block a denoising step: at most prompt + 5 x answer rows, padded
# to few distinct compiled lengths
PAD_ROWS = 1280


def check_blocks(run: harness.Run, weights, records, n_sample: int) -> dict:
    """``serve_loop.check_outputs``'s sample and result, the comparison
    the replay's."""
    done = [r for r in records if r.error is None and r.handle is not None
            and len(r.times) == r.req.max_new]
    rs = np.random.RandomState(run.seed + 2)
    picks = [done[i] for i in rs.choice(len(done), min(n_sample, len(done)),
                                        replace=False)] if done else []
    worst_a = worst_b = 0.0
    checked = steps = 0
    for rec in picks:
        out = np.asarray(rec.handle.result(timeout=1.0), np.int32)
        when = rec.handle.unmask_steps()
        if when is None or len(when) != out.size:
            return {"ok": False, "why": "no unmask steps on the handle",
                    "checked": checked}
        rows = rec.req.prompt.size + 5 * out.size
        got = run.reference.check_request(
            weights, run.config, rec.req.prompt, out, when,
            serve_loop.LOGIT_TOL, pad_to=-(-rows // PAD_ROWS) * PAD_ROWS)
        if not got["finite"]:
            return {"ok": False, "why": "reference logits not finite",
                    "checked": checked}
        worst_a = max(worst_a, got["token_gap"])
        worst_b = max(worst_b, got["position_gap"])
        steps += got["steps"]
        checked += 1
    return {"ok": bool(picks) and steps > 0 and worst_a <= 1.0
            and worst_b <= 1.0, "checked": checked, "steps": steps,
            "worst_gap_in_tolerances": worst_a,
            "worst_position_gap_in_tolerances": worst_b}


def run(run: harness.Run) -> harness.Result:
    plain = serve_loop.check_outputs
    serve_loop.check_outputs = check_blocks
    try:
        return serve_loop.run_serving(run, "closed")
    finally:
        serve_loop.check_outputs = plain
