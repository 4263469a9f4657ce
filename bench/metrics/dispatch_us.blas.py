"""Host time per call from entry into run_op to its return, before the
result is awaited (us), averaged over every call of the window."""


def read(run):
    calls = run.raw["calls"]
    return 1e6 * run.raw["dispatch_s"] / calls if calls else None
