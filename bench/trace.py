"""Reduction of a JAX profiler trace to the benchmark's device numbers.

The profiler writes ``<dir>/plugins/profile/<time>/<host>.xplane.pb``.
Each TPU is a plane named ``/device:TPU:<n>`` whose ``XLA Ops`` line
holds one event per operation the chip ran; the benchmark's own host
spans (``jax.profiler.TraceAnnotation``) are events on the host plane's
Python thread, on the same clock.  Everything is clipped to the span
named ``bench.window``.

* busy time of a chip: the union of its operation intervals;
* idle gaps: the window less that union, each labelled with the
  innermost benchmark span open at the gap's midpoint (``none`` if
  there is none);
* exposed collective time of a chip: the part of its collective
  operations' intervals during which none of its other operations runs.
"""
from __future__ import annotations

import gzip
import re
from collections import defaultdict
from dataclasses import dataclass, field
from pathlib import Path

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OP_NAME = re.compile(r'op_name="([^"]*)"')
OPS_LINE = "XLA Ops"
WINDOW = "bench.window"
SPAN_PREFIX = "bench."
COLLECTIVE = re.compile(r"^(all-reduce|all-gather|reduce-scatter|"
                        r"collective-permute|all-to-all)")


@dataclass
class Trace:
    """Events in nanoseconds: ``ops[device] = [(name, start, end)]``,
    ``spans = [(name, start, end)]``."""
    ops: dict = field(default_factory=dict)
    spans: list = field(default_factory=list)


def find_xplane(trace_dir) -> Path:
    found = sorted(Path(trace_dir).rglob("*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def op_label(text: str) -> str:
    """A TPU op event is named by its HLO instruction's text, which can
    run to kilobytes; keep the instruction's name, the JAX op_name of its
    metadata, and a mark on Mosaic kernel calls:
    ``%jvp__.1 jit(phase)/while/body/.../pallas_call tpu_custom_call``."""
    label = text.split(" = ", 1)[0]
    found = OP_NAME.search(text)
    if found:
        label += " " + found.group(1)
    if "tpu_custom_call" in text:
        label += " tpu_custom_call"
    return label


def load(path) -> Trace:
    """Read an ``.xplane.pb`` (or ``.xplane.pb.gz``)."""
    from jax.profiler import ProfileData
    path = Path(path)
    if path.suffix == ".gz":
        pd = ProfileData.from_serialized_xspace(gzip.decompress(
            path.read_bytes()))
    else:
        pd = ProfileData.from_file(str(path))
    tr = Trace()
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    tr.ops[plane.name] = [(op_label(e.name), e.start_ns,
                                           e.end_ns) for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                tr.spans += [(e.name, e.start_ns, e.end_ns)
                             for e in line.events
                             if e.name.startswith(SPAN_PREFIX)]
    return tr


def union(intervals) -> list:
    """Merge (start, end) intervals into disjoint sorted ones."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [tuple(x) for x in out]


def length(intervals) -> float:
    return float(sum(e - s for s, e in intervals))


def clip(intervals, lo, hi) -> list:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def window(tr: Trace) -> tuple:
    spans = [(s, e) for n, s, e in tr.spans if n == WINDOW]
    if len(spans) != 1:
        raise ValueError(f"expected one {WINDOW!r} span, found {len(spans)}")
    return spans[0]


def label_at(tr: Trace, t: float) -> str:
    """Innermost benchmark span other than the window open at ``t``."""
    open_ = [(s, n) for n, s, e in tr.spans if s <= t < e and n != WINDOW]
    return max(open_)[1] if open_ else "none"


def summarize(tr: Trace, top: int = 10) -> dict:
    """Busy, idle and collective seconds averaged over the chips, the
    operations that took the most time, and the longest idle gaps."""
    lo, hi = window(tr)
    if not tr.ops:
        raise ValueError("the trace holds no TPU operations")
    busy, exposed, gaps = [], [], []
    by_name = defaultdict(float)
    for events in tr.ops.values():
        coll, compute = [], []
        for name, s, e in events:
            if min(e, hi) > max(s, lo):
                by_name[name] += min(e, hi) - max(s, lo)
                (coll if COLLECTIVE.match(name) else compute).append(
                    (max(s, lo), min(e, hi)))
        merged = union(coll + compute)
        busy.append(length(merged))
        coll, compute = union(coll), union(compute)
        exposed.append(length(coll) - length(intersect(coll, compute)))
        edges = [lo] + [x for iv_ in merged for x in iv_] + [hi]
        gaps += [(e - s, s, e) for s, e in zip(edges[::2], edges[1::2])
                 if e > s]
    n = len(tr.ops)
    gaps.sort(reverse=True)
    ns = 1e-9
    return {
        "window_s": (hi - lo) * ns,
        "busy_s": sum(busy) / n * ns,
        "collective_exposed_s": sum(exposed) / n * ns,
        "device_ops": [[k, v / n * ns] for k, v in sorted(
            by_name.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[label_at(tr, (s + e) / 2), d * ns]
                      for d, s, e in gaps[:top]],
    }


def intersect(a, b) -> list:
    """Intersection of two disjoint sorted interval lists."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if s < e:
            out.append((s, e))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def op_seconds(tr: Trace, pattern: str) -> tuple:
    """(calls, seconds per chip) of operations whose name matches
    ``pattern``, inside the window."""
    lo, hi = window(tr)
    rx = re.compile(pattern)
    calls, total = 0, 0.0
    for events in tr.ops.values():
        for name, s, e in events:
            if rx.search(name) and e > lo and s < hi:
                calls += 1
                total += min(e, hi) - max(s, lo)
    n = max(len(tr.ops), 1)
    return calls / n, total / n * 1e-9
