"""Device idle time inside the program's ``blas.run_op`` spans per call
(us): the time of the traced window in which the host was inside a call to
``run_op`` and no operation ran on device 0, over the number of calls
(``blas.run_op`` spans that start in the window).  The rest of the window's
idle time is the caller's wait and loop, outside ``run_op``."""

from bench import spans


def read(run):
    lo, hi = run.tr.segment("bench.window")
    calls = spans.starting_in(run.tr, "blas.run_op", lo, hi)
    if not calls:
        return None
    return spans.idle_inside(run.tr, calls, lo, hi) / len(calls) / 1e3
