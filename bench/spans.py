"""The program's own profiler spans in a reduced trace.

The program writes host spans with ``jax.profiler.TraceAnnotation``
(``blas.run_op``, ``adsala.select``, ``serve.decode_step``, ...); they land
in the profiler trace on the device trace's clock, so the device's idle time
can be split by the span the host was in.  The readers of
``bench/metrics/`` that use them take a window ``[lo, hi]`` (in ns) and
return ``None`` where the program wrote none of their spans there, as a
program without them does.
"""

from __future__ import annotations

from bench.tracing import Trace, union


def starting_in(tr: Trace, name: str, lo: float, hi: float) -> list:
    """[(start, end)] of the host spans called ``name`` that start in
    [lo, hi)."""
    return [(s, e) for n, s, e in tr.spans if n == name and lo <= s < hi]


def idle_inside(tr: Trace, spans, lo: float, hi: float) -> float:
    """Nanoseconds of [lo, hi] that lie inside the union of ``spans`` and in
    which no operation ran on device 0."""
    inside = union([(max(s, lo), min(e, hi)) for s, e in spans])
    busy = union([(max(o.start, lo), min(o.end, hi)) for o in tr.ops
                  if o.device == 0 and o.end > lo and o.start < hi])
    overlap, i, j = 0.0, 0, 0
    while i < len(inside) and j < len(busy):
        s = max(inside[i][0], busy[j][0])
        e = min(inside[i][1], busy[j][1])
        overlap += max(e - s, 0.0)
        if inside[i][1] < busy[j][1]:
            i += 1
        else:
            j += 1
    return sum(e - s for s, e in inside) - overlap
