"""Share of the stacked items the service executed in the window that were
filler rows (%): the growth of ``ServeStats.padded_items`` over the items
executed, requests completed or failed plus filler."""


def read(run):
    items = run.raw.get("completed", 0) + run.raw.get("failed", 0) \
        + run.raw.get("padded", 0)
    return 100.0 * run.raw["padded"] / items if items else None
