"""Host time of the knob decision per call (us): the summed durations of
the program's ``adsala.select`` spans that start in the traced window, over
the number of ``blas.run_op`` spans that start in it."""

from bench import spans


def read(run):
    lo, hi = run.tr.segment("bench.window")
    calls = spans.starting_in(run.tr, "blas.run_op", lo, hi)
    if not calls:
        return None
    decided = spans.starting_in(run.tr, "adsala.select", lo, hi)
    return sum(e - s for s, e in decided) / len(calls) / 1e3
