"""Share of the traced window in which no operation ran on the device (%)."""


def read(run):
    lo, hi = run.tr.segment("bench.window")
    return 100.0 * (1.0 - run.tr.busy_s(lo, hi) / ((hi - lo) / 1e9))
