"""The program's own spans and named scopes in a JAX profiler trace.

The program writes host spans named ``ibmb/...``
(``jax.profiler.TraceAnnotation``, some with arguments such as the bytes a
loader stage put on the device) and wraps its device ops in
``jax.named_scope``s (``layer0/aggregate``, ``transpose(jvp(layer1))/
attention``, ...). This module reads both from the same trace that
``trace_reduce`` reduces, inside the same window:

* spans: for every host event named ``ibmb/...``, how many overlap the
  window and their seconds clipped to it; the sum of each numeric argument;
* scopes: device seconds per scope name (``SCOPES``, or ``(none)``),
  averaged over the devices used. An op's scope is the last component of
  its scope path that names one of ``SCOPES``; a fused op carries its
  root's path, which XLA keeps in the op's metadata (the ``tf_op`` stat,
  shown by TensorBoard as the op's framework name scope). A reader of a
  scope that ``SCOPES`` does not name asks ``for_record`` for it, which
  splits the device seconds per scope path that the summary keeps;
* the device's idle seconds whose gap lies inside some ``ibmb/`` span.

A trace of a program that has no such spans or scopes gives empty spans
and every device second under ``(none)``: ``for_record`` then gives None,
and so do the metric readers. The per-layer readers of ``metrics/`` reach
the trace through ``for_record``, since the record the harness hands them
carries the trace's reduction and not its path. A trace that cannot be
read, or that is not the run's, raises, as the harness's own reduction
does on the chip.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

from ibmb_bench import trace_reduce as tr

SPAN_PREFIX = "ibmb/"
SCOPES = ("aggregate", "attention", "transform", "norm", "loss", "optimizer")
NO_SCOPE = "(none)"
HARNESS_PREFIX = "bench/"    # the harness's own host spans
UNSCOPED_TOP = 8             # unscoped ops a summary names
SCOPE_STAT = "tf_op"         # the op's scope path, in the op's metadata
_SPLIT = re.compile(r"[/():]")


@dataclasses.dataclass
class Trace:
    planes: List[tr.Plane]
    # (event, its numeric arguments) of every host span named ibmb/...
    spans: List[Tuple[tr.Event, Dict[str, float]]]
    # device op name -> scope path, from the ops' metadata
    op_paths: Dict[str, str]


def scope_of(path: str, names=SCOPES) -> str:
    """The innermost component of ``path`` that names a scope of ``names``,
    looking through ``jvp(...)`` and ``transpose(...)``."""
    for part in reversed(_SPLIT.split(path or "")):
        if part in names:
            return part
    return NO_SCOPE


def _number(v):
    return float(v) if isinstance(v, (int, float)) \
        and not isinstance(v, bool) else None


def load(path: str) -> Trace:
    from jax.profiler import ProfileData
    with open(path, "rb") as f:
        raw = f.read()
    pd = ProfileData.from_serialized_xspace(raw)
    planes, spans = [], []
    for p in pd.planes:
        lines: Dict[str, List[tr.Event]] = defaultdict(list)
        host = p.name.startswith("/host:")
        for line in p.lines:
            for e in line.events:
                ev = tr.Event(e.name, float(e.start_ns),
                              float(e.duration_ns))
                lines[line.name].append(ev)
                if host and e.name.startswith(SPAN_PREFIX):
                    args = {k: _number(v) for k, v in e.stats}
                    spans.append((ev, {k: v for k, v in args.items()
                                       if v is not None}))
        planes.append(tr.Plane(p.name, dict(lines)))
    return Trace(planes, spans, metadata_scopes(raw))


def _xspace_class():
    """A reader for the part of ``xplane.proto`` (XSpace) that holds the
    device ops' metadata: each plane's name, event metadata and stat
    metadata. A proto ``map`` is read as its repeated key/value entries;
    every other field is skipped as unknown."""
    from google.protobuf import descriptor_pb2, descriptor_pool
    from google.protobuf import message_factory
    F = descriptor_pb2.FieldDescriptorProto
    fdp = descriptor_pb2.FileDescriptorProto(
        name="ibmb_bench_xplane_subset.proto", package="ibmb_bench_xplane",
        syntax="proto3")

    def msg(name, *fields):
        m = fdp.message_type.add(name=name)
        for fname, num, ftype, label, tname in fields:
            f = m.field.add(name=fname, number=num, type=ftype, label=label)
            if tname:
                f.type_name = ".ibmb_bench_xplane." + tname

    one, rep = F.LABEL_OPTIONAL, F.LABEL_REPEATED
    msg("XStat", ("metadata_id", 1, F.TYPE_INT64, one, None),
        ("double_value", 2, F.TYPE_DOUBLE, one, None),
        ("uint64_value", 3, F.TYPE_UINT64, one, None),
        ("int64_value", 4, F.TYPE_INT64, one, None),
        ("str_value", 5, F.TYPE_STRING, one, None),
        ("bytes_value", 6, F.TYPE_BYTES, one, None),
        ("ref_value", 7, F.TYPE_UINT64, one, None))
    msg("XEventMetadata", ("id", 1, F.TYPE_INT64, one, None),
        ("name", 2, F.TYPE_STRING, one, None),
        ("stats", 5, F.TYPE_MESSAGE, rep, "XStat"))
    msg("XStatMetadata", ("id", 1, F.TYPE_INT64, one, None),
        ("name", 2, F.TYPE_STRING, one, None))
    msg("EventMetadataEntry", ("key", 1, F.TYPE_INT64, one, None),
        ("value", 2, F.TYPE_MESSAGE, one, "XEventMetadata"))
    msg("StatMetadataEntry", ("key", 1, F.TYPE_INT64, one, None),
        ("value", 2, F.TYPE_MESSAGE, one, "XStatMetadata"))
    msg("XPlane", ("id", 1, F.TYPE_INT64, one, None),
        ("name", 2, F.TYPE_STRING, one, None),
        ("event_metadata", 4, F.TYPE_MESSAGE, rep, "EventMetadataEntry"),
        ("stat_metadata", 5, F.TYPE_MESSAGE, rep, "StatMetadataEntry"))
    msg("XSpace", ("planes", 1, F.TYPE_MESSAGE, rep, "XPlane"))
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fdp)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName("ibmb_bench_xplane.XSpace"))


def metadata_scopes(raw: bytes) -> Dict[str, str]:
    """Device op name -> the scope path that its metadata's ``tf_op`` stat
    holds, over every device plane. (A device op event carries the op's
    HLO text as its name and no scope of its own.)"""
    space = _xspace_class().FromString(raw)
    out: Dict[str, str] = {}
    for plane in space.planes:
        if not plane.name.startswith("/device:"):
            continue
        stat_names = {e.key: e.value.name for e in plane.stat_metadata}
        for entry in plane.event_metadata:
            md = entry.value
            for s in md.stats:
                if stat_names.get(s.metadata_id) != SCOPE_STAT:
                    continue
                # a string stat is inline, or a reference to the stat
                # metadata whose name is the string
                path = s.str_value or stat_names.get(s.ref_value, "")
                if path:
                    out.setdefault(md.name, path)
    return out


def _inside_s(ev: tr.Event, lo: float, hi: float) -> float:
    """Seconds of ``ev`` inside the window, 0 where it lies outside."""
    return max(0.0, min(ev.end_ns, hi) - max(ev.start_ns, lo)) / 1e9


def span_totals(trace: Trace, lo: float, hi: float, spans=None
                ) -> Dict[str, Dict]:
    """Per span name: ``count`` of the spans that overlap the window,
    ``total_s`` of their time inside it, and ``args``, each numeric
    argument summed over those spans. ``spans`` are (event, args) pairs,
    the trace's ``ibmb/`` spans by default."""
    out: Dict[str, Dict] = {}
    for ev, args in trace.spans if spans is None else spans:
        if ev.end_ns <= lo or ev.start_ns >= hi:
            continue
        d = out.setdefault(ev.name, dict(count=0, total_s=0.0, args={}))
        d["count"] += 1
        d["total_s"] += _inside_s(ev, lo, hi)
        for k, v in args.items():
            d["args"][k] = d["args"].get(k, 0.0) + v
    return out


def op_seconds(trace: Trace, n_devices: int, lo: float, hi: float
               ) -> Dict[str, float]:
    """Device seconds per op name inside the window, averaged over the
    first ``n_devices`` devices."""
    devs = tr.device_planes(trace.planes)[:n_devices]
    out: Dict[str, float] = defaultdict(float)
    for p in devs:
        for e in tr.op_events(p):
            out[e.name] += _inside_s(e, lo, hi)
    return {k: v / max(len(devs), 1) for k, v in out.items() if v > 0}


def scope_seconds(trace: Trace, n_devices: int, lo: float, hi: float,
                  ops: Optional[Dict[str, float]] = None
                  ) -> Dict[str, float]:
    """Device seconds per scope name inside the window, averaged over the
    first ``n_devices`` devices; ops with no known scope go to ``(none)``.
    ``ops`` is ``op_seconds``'s result where the caller has it."""
    if ops is None:
        ops = op_seconds(trace, n_devices, lo, hi)
    out: Dict[str, float] = defaultdict(float)
    for name, s in ops.items():
        out[scope_of(trace.op_paths.get(name, ""))] += s
    return dict(out)


def path_seconds(trace: Trace, ops: Dict[str, float]) -> Dict[str, float]:
    """Device seconds per scope path (``""`` for ops with none), from
    ``op_seconds``'s result."""
    out: Dict[str, float] = defaultdict(float)
    for name, s in ops.items():
        out[trace.op_paths.get(name, "")] += s
    return dict(out)


def scopes_by_path(paths: Dict[str, float], names) -> Dict[str, float]:
    """Device seconds per scope of ``names`` (or ``(none)``), from
    ``path_seconds``'s result: each path goes to its innermost name."""
    out: Dict[str, float] = defaultdict(float)
    for path, s in paths.items():
        out[scope_of(path, names)] += s
    return dict(out)


def idle_in_spans(trace: Trace, lo: float, hi: float) -> float:
    """Idle seconds of the first device whose gap's midpoint lies inside
    some ``ibmb/`` span: the idle time the program's spans account for."""
    devs = tr.device_planes(trace.planes)
    if not devs:
        return 0.0
    merged = tr.clip(tr.union([(e.start_ns, e.end_ns)
                               for e in tr.op_events(devs[0])]), lo, hi)
    edges = [lo] + [x for iv in merged for x in iv] + [hi]
    gaps = [(edges[j], edges[j + 1]) for j in range(0, len(edges), 2)
            if edges[j + 1] > edges[j]]
    spans = tr.union([(ev.start_ns, ev.end_ns) for ev, _ in trace.spans])
    total, k = 0.0, 0
    for s, e in sorted(gaps):
        mid = (s + e) / 2
        while k < len(spans) and spans[k][1] < mid:
            k += 1
        if k < len(spans) and spans[k][0] <= mid:
            total += (e - s) / 1e9
    return total


def summarize(path: str, n_devices: int) -> Dict:
    """Of the trace at ``path``, inside the window that ``trace_reduce``
    uses: ``spans`` (the ``ibmb/`` spans), ``scopes``, ``idle_in_spans_s``,
    ``harness_spans`` (the ``bench/`` spans, as ``spans``),
    ``unscoped_ops``, the ops with the most device time that carry no
    known scope, as [name, seconds] pairs, and ``path_seconds``."""
    trace = load(path)
    lo, hi = tr.window_of(trace.planes)
    ops = op_seconds(trace, n_devices, lo, hi)
    unscoped = sorted(((n, s) for n, s in ops.items() if scope_of(
        trace.op_paths.get(n, "")) == NO_SCOPE), key=lambda kv: -kv[1])
    harness = [(e, {}) for e in tr.host_events(trace.planes)
               if e.name.startswith(HARNESS_PREFIX)]
    return dict(window_s=(hi - lo) / 1e9,
                spans=span_totals(trace, lo, hi),
                scopes=scope_seconds(trace, n_devices, lo, hi, ops),
                idle_in_spans_s=idle_in_spans(trace, lo, hi),
                harness_spans=span_totals(trace, lo, hi, harness),
                unscoped_ops=[[n[:tr.NAME_CHARS], s]
                              for n, s in unscoped[:UNSCOPED_TOP]],
                path_seconds=path_seconds(trace, ops))


def for_record(record: Dict, metric_file: str, scopes=()
               ) -> Optional[Dict]:
    """The program's spans and scopes of the traced run that ``record``
    describes: the newest trace under the checkout's
    ``ibmb_bench/.cache/traces/`` (the harness writes one per traced run,
    and reads the metrics right after it). None where the run has no trace
    reduction (an untraced run, or the CPU's trace with no device) and
    where the program wrote no ``ibmb/`` span and no known scope. Raises
    where the trace cannot be read or its window is not the record's.
    ``metric_file`` is a reader's ``__file__``, which names the checkout.
    The summary is kept in the record, so the readers of one run read the
    trace once.

    ``scopes`` names scopes beyond ``SCOPES``: the result's ``scopes`` then
    splits device time over ``SCOPES`` and those names together, each op to
    the innermost of them on its path, so a new name inside a known scope
    takes its ops from that scope. The record's summary stays as it is."""
    t = record.get("trace")
    if not t:
        return None
    if "program_trace" not in record:
        bench = os.path.dirname(os.path.dirname(os.path.abspath(metric_file)))
        paths = glob.glob(os.path.join(bench, ".cache", "traces", "**",
                                       "*.xplane.pb"), recursive=True)
        if not paths:
            raise FileNotFoundError(f"no .xplane.pb under {bench}/.cache")
        path = max(paths, key=os.path.getmtime)
        got = summarize(path, int(t.get("devices", 1)))
        if abs(got["window_s"] - t["window_s"]) \
                > 1e-6 * max(t["window_s"], 1e-9):
            raise ValueError(f"{path}: window {got['window_s']} s, the "
                             f"run's {t['window_s']} s")
        record["program_trace"] = got
    got = record["program_trace"]
    if scopes:
        got = dict(got, scopes=scopes_by_path(got["path_seconds"],
                                              SCOPES + tuple(scopes)))
    if not got["spans"] and set(got["scopes"]) <= {NO_SCOPE}:
        return None
    return got
