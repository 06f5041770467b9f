"""From a JAX profiler trace to the numbers the per-layer metrics read.

* busy: the union of the intervals in which an operation ran on a device
  (the ``XLA Ops`` line of each ``/device:`` plane), inside the traced
  window, averaged over the devices used;
* window: the harness's own host span ``bench/window`` (the whole traced
  plane range where it is missing);
* top device ops: device seconds per operation name, averaged over the
  devices;
* idle gaps: the device's idle time inside the window, attributed to what
  the host was doing: each gap goes to the shortest host event that covers
  its midpoint (a harness span such as ``bench/submit``, or a JAX runtime
  event such as ``PjitFunction(train_step)``), summed per label.
"""
from __future__ import annotations

import dataclasses
import glob
import heapq
import os
from collections import defaultdict
from typing import Dict, List, Tuple

WINDOW_SPAN = "bench/window"
OP_LINES = ("XLA Ops",)
TOP = 10
NAME_CHARS = 120       # an XLA op's name and result type; its operands cut


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


@dataclasses.dataclass
class Plane:
    name: str
    lines: Dict[str, List[Event]]


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def load_planes(path: str) -> List[Plane]:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    planes = []
    for p in pd.planes:
        lines: Dict[str, List[Event]] = defaultdict(list)
        for line in p.lines:
            for e in line.events:
                lines[line.name].append(Event(e.name, float(e.start_ns),
                                              float(e.duration_ns)))
        planes.append(Plane(p.name, dict(lines)))
    return planes


def union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def clip(intervals, lo: float, hi: float):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def device_planes(planes: List[Plane]) -> List[Plane]:
    devs = [p for p in planes if p.name.startswith("/device:")
            and any(line in p.lines for line in OP_LINES)]
    return sorted(devs, key=lambda p: p.name)


def op_events(plane: Plane) -> List[Event]:
    for line in OP_LINES:
        if line in plane.lines:
            return plane.lines[line]
    return []


def host_events(planes: List[Plane]) -> List[Event]:
    """Host spans with a length: the harness's own and the runtime's; the
    Python tracer's per-call events (named ``$...``) are left out."""
    return [e for p in planes if p.name.startswith("/host:")
            for evs in p.lines.values() for e in evs
            if e.dur_ns > 0 and not e.name.startswith("$")]


def window_of(planes: List[Plane]) -> Tuple[float, float]:
    spans = [e for e in host_events(planes) if e.name == WINDOW_SPAN]
    if spans:
        w = max(spans, key=lambda e: e.dur_ns)
        return w.start_ns, w.end_ns
    evs = [e for p in planes for line in p.lines.values() for e in line]
    return min(e.start_ns for e in evs), max(e.end_ns for e in evs)


def label_gaps(gaps: List[Tuple[float, float]], host: List[Event]
               ) -> Dict[str, float]:
    """Idle seconds per host label: the shortest host event (other than
    the window span itself) that covers the gap's midpoint. One sweep over
    midpoints in order, with the events begun so far in a heap by length
    (an event that has ended stays ended, so it is dropped lazily)."""
    events = sorted((e for e in host if e.name != WINDOW_SPAN),
                    key=lambda e: e.start_ns)
    out: Dict[str, float] = defaultdict(float)
    heap: List[Tuple[float, int, Event]] = []
    j = 0
    for s, e in sorted(gaps, key=lambda g: g[0] + g[1]):
        mid = (s + e) / 2
        while j < len(events) and events[j].start_ns <= mid:
            heapq.heappush(heap, (events[j].dur_ns, j, events[j]))
            j += 1
        while heap and heap[0][2].end_ns < mid:
            heapq.heappop(heap)
        out[heap[0][2].name if heap else "(no host event)"] += (e - s) / 1e9
    return dict(out)


def reduce_planes(planes: List[Plane], n_devices: int) -> Dict:
    lo, hi = window_of(planes)
    devs = device_planes(planes)[:n_devices]
    if not devs:
        raise ValueError("the trace has no device plane with an op line")
    busy, per_op = [], defaultdict(float)
    gaps0: List[Tuple[float, float]] = []
    for i, p in enumerate(devs):
        evs = [e for e in op_events(p) if e.end_ns > lo and e.start_ns < hi]
        merged = clip(union([(e.start_ns, e.end_ns) for e in evs]), lo, hi)
        busy.append(sum(e - s for s, e in merged) / 1e9)
        for e in evs:
            per_op[e.name[:NAME_CHARS]] += (min(e.end_ns, hi)
                                            - max(e.start_ns, lo)) / 1e9
        if i == 0:
            edges = [lo] + [x for iv in merged for x in iv] + [hi]
            gaps0 = [(edges[j], edges[j + 1]) for j in range(0, len(edges), 2)
                     if edges[j + 1] > edges[j]]
    n = len(devs)
    top_ops = sorted(((k, v / n) for k, v in per_op.items()),
                     key=lambda kv: -kv[1])[:TOP]
    idle = label_gaps(gaps0, host_events(planes))
    return dict(busy_s=sum(busy) / n, window_s=(hi - lo) / 1e9,
                devices=n, top_ops=[[k, v] for k, v in top_ops],
                idle_gaps=[[k, v] for k, v in sorted(
                    idle.items(), key=lambda kv: -kv[1])[:TOP]],
                op_seconds=dict(per_op))


def summarize(trace_dir: str, n_devices: int) -> Dict:
    return reduce_planes(load_planes(find_xplane(trace_dir)), n_devices)
