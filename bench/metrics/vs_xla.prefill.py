"""Device time of one prefill of the same batch through XLA's dot, over
that of the routed prefill: above 1 when routing is faster."""


def read(run):
    routed = run.tr.busy_s(*run.tr.segment("bench.routed"))
    xla = run.tr.busy_s(*run.tr.segment("bench.xla"))
    return xla / routed if routed > 0 else None
