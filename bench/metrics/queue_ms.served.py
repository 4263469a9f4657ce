"""Mean time a request waited in the service before its stack started to
execute (ms): the growth of ``ServeStats.queue_sum`` over the window, over
the growth of ``ServeStats.completed`` (requests whose stack ran in the
window)."""


def read(run):
    done = run.raw.get("completed")
    return 1e3 * run.raw["queue_s"] / done if done else None
