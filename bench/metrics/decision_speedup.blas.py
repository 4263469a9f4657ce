"""The paper's metric: device time of one round over the pool at each op's
default knob, over that of the same round at the installed model's knobs."""


def read(run):
    tuned = run.tr.busy_s(*run.tr.segment("bench.tuned"))
    default = run.tr.busy_s(*run.tr.segment("bench.default"))
    return default / tuned if tuned > 0 else None
