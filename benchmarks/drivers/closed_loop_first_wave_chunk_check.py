"""``closed_loop_first_wave`` whose reference check takes ONE of its
sampled requests from those whose prompt spans more than one prefill
chunk (longer than the server group's largest length bucket), where the
run has such a request and samples at least two.

``serve_loop.check_outputs`` draws its sample from all completed
requests alike; with prompts of which a fifth are longer than a chunk and
two requests checked, two runs in three would hold no prompt that went
through the chunk hand-over (the state a slot carries from one chunk to
the next), which is what a model with recurrent state has to prove. The
comparison itself, its tolerance and the count of checked requests are
``check_outputs``'s own: it is called once on the multi-chunk requests
for one and once on the others for the rest."""
from benchmarks.drivers import closed_loop_first_wave
from benchmarks.lib import serve_loop


def run(run):
    check = serve_loop.check_outputs
    chunk = max(run.traffic["server"]["len_buckets"])

    def chunk_check(run_, weights, records, n_sample):
        spans = [r for r in records if r.req.prompt.size > chunk]
        if not spans or n_sample < 2 or len(spans) == len(records):
            return check(run_, weights, records, n_sample)
        first = check(run_, weights, spans, 1)
        if not first["ok"]:
            return first
        rest = check(run_, weights,
                     [r for r in records if r.req.prompt.size <= chunk],
                     n_sample - 1)
        return dict(rest, checked=first["checked"] + rest["checked"],
                    worst_gap_in_tolerances=max(
                        first["worst_gap_in_tolerances"],
                        rest["worst_gap_in_tolerances"]))

    serve_loop.check_outputs = chunk_check
    try:
        return closed_loop_first_wave.run(run)
    finally:
        serve_loop.check_outputs = check
