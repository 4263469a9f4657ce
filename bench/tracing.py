"""Capture of a profiler trace and its reduction to numbers.

The profiler writes an ``.xplane.pb``; :func:`load` reads it with JAX's
``ProfileData`` into the device operations of each TPU (their name, start,
end and HLO statistics) and the host's spans.  The benchmark marks its own
segments with ``TraceAnnotation("bench.<segment>")``; every number is then
taken inside one segment:

* busy time: the union of the intervals in which an operation ran on a
  device, averaged over the devices (a loop's or a branch's own event is
  left out: it spans its body's operations and the gaps between them);
* kernel time: the summed durations of the Pallas kernels' events (custom
  calls whose target is ``tpu_custom_call``);
* idle gaps: the holes in the busy union, each named by the innermost host
  span that covers its middle.
"""

from __future__ import annotations

import contextlib
import re
import shutil
from dataclasses import dataclass
from pathlib import Path

#: host events that say nothing about what the host was doing
_HOST_NOISE = re.compile(r"^(\$|ThreadpoolListener)")
#: a Pallas kernel is an XLA custom call whose own target is the TPU's kernel
#: runner; a fusion that only consumes a kernel's result is not one
_KERNEL = re.compile(r'custom_call_target="tpu_custom_call"')
#: control flow whose event spans the operations of its body
_CONTAINER = re.compile(r"^%?(while|conditional|call)(\.\d+)*\s*=")


@dataclass
class Op:
    device: int
    name: str
    start: float          # ns, trace clock
    end: float

    @property
    def is_kernel(self) -> bool:
        return bool(_KERNEL.search(self.name))


@dataclass
class Trace:
    ops: list                 # [Op], every device
    devices: int
    spans: list               # [(name, start, end)] host spans, all threads

    def segment(self, name: str) -> tuple[float, float]:
        """[start, end] of the benchmark's ``name`` annotation (the union
        of its occurrences)."""
        hits = [(s, e) for n, s, e in self.spans if n == name]
        if not hits:
            raise KeyError(f"no host span {name!r} in the trace")
        return min(s for s, _ in hits), max(e for _, e in hits)

    def ops_in(self, lo: float, hi: float) -> list:
        return [o for o in self.ops if o.start >= lo and o.start < hi]

    def busy_s(self, lo: float, hi: float) -> float:
        """Seconds of [lo, hi] in which an operation ran, averaged over the
        devices."""
        total = 0.0
        for d in range(self.devices):
            iv = [(max(o.start, lo), min(o.end, hi)) for o in self.ops
                  if o.device == d and o.end > lo and o.start < hi]
            total += sum(e - s for s, e in union(iv))
        return total / max(self.devices, 1) / 1e9

    def kernel_s(self, lo: float, hi: float) -> float:
        return sum(o.end - o.start for o in self.ops_in(lo, hi)
                   if o.is_kernel) / 1e9

    def top_ops(self, lo: float, hi: float, n: int = 10) -> list:
        by: dict = {}
        for o in self.ops_in(lo, hi):
            k = op_kind(o)
            by[k] = by.get(k, 0.0) + (o.end - o.start) / 1e9
        return sorted(([k, v] for k, v in by.items()),
                      key=lambda kv: -kv[1])[:n]

    def idle_gaps(self, lo: float, hi: float, n: int = 10) -> list:
        """The ``n`` longest idle gaps of device 0 in [lo, hi], each as
        [what the host was doing, seconds]."""
        busy = union([(max(o.start, lo), min(o.end, hi)) for o in self.ops
                      if o.device == 0 and o.end > lo and o.start < hi])
        gaps, t = [], lo
        for s, e in busy + [(hi, hi)]:
            if s > t:
                gaps.append((t, s))
            t = max(t, e)
        gaps.sort(key=lambda g: g[0] - g[1])
        out = []
        for s, e in gaps[:n]:
            mid = (s + e) / 2
            cover = [(sp_e - sp_s, name) for name, sp_s, sp_e in self.spans
                     if sp_s <= mid <= sp_e]
            label = min(cover)[1] if cover else "no host span"
            out.append([label, (e - s) / 1e9])
        return out


def union(intervals) -> list:
    out: list = []
    for s, e in sorted(iv for iv in intervals if iv[1] > iv[0]):
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def op_kind(o: Op) -> str:
    """An operation's HLO name without its instance number, kernels
    marked.  TPU events are named by their HLO text, ``%name.3 = ...``."""
    m = re.match(r"%?([\w\-.]+?)(\.\d+)*\s*=", o.name)
    base = m.group(1) if m else re.sub(r"[.\d]+$", "", o.name) or o.name
    return f"kernel:{base}" if o.is_kernel else base


@contextlib.contextmanager
def capture(path: Path):
    import jax
    shutil.rmtree(path, ignore_errors=True)
    path.mkdir(parents=True, exist_ok=True)
    jax.profiler.start_trace(str(path))
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def load(path: Path) -> Trace:
    from jax.profiler import ProfileData
    files = sorted(Path(path).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {path}")
    pd = ProfileData.from_file(str(files[-1]))
    ops, spans, devices = [], [], 0
    for plane in pd.planes:
        m = re.match(r"/device:TPU:(\d+)$", plane.name)
        if m:
            devices += 1
            for line in plane.lines:
                if line.name != "XLA Ops":
                    continue
                for ev in line.events:
                    if _CONTAINER.match(ev.name):
                        continue
                    ops.append(Op(int(m.group(1)), ev.name, ev.start_ns,
                                  ev.start_ns + ev.duration_ns))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if not _HOST_NOISE.match(ev.name) and ev.duration_ns > 0:
                        spans.append((ev.name, ev.start_ns,
                                      ev.start_ns + ev.duration_ns))
    return Trace(ops=ops, devices=devices, spans=spans)

