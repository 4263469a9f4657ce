"""Device idle time inside the decode step's dispatch and sampling per step
(us): the time of the traced window in which the host was inside a
``serve.decode_step`` or ``serve.sample`` span and no operation ran on
device 0, over the number of ``serve.decode_step`` spans that start in the
window.  Reading tokens back and the caller's loop lie outside both."""

from bench import spans


def read(run):
    lo, hi = run.tr.segment("bench.window")
    steps = spans.starting_in(run.tr, "serve.decode_step", lo, hi)
    if not steps:
        return None
    host = steps + spans.starting_in(run.tr, "serve.sample", lo, hi)
    return spans.idle_inside(run.tr, host, lo, hi) / len(steps) / 1e3
