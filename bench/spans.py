"""The program's host spans (``semisfl.``, ``src/repro/obs.py``) against
the device trace: why the chip sat idle, and how long each named phase
program ran.

    python3 bench/spans.py DIR      # DIR: a run's --trace-dir

prints, for the window of a kept benchmark trace, the idle time split by
span group and each phase program's device time per round (``metrics``).

* ``load`` reads what ``bench/trace.py::load`` reads, and besides keeps
  the ``bench.`` and ``semisfl.`` spans of each host line apart, line by
  line (every Python thread's line is named ``python``, so a line is
  known by its position), and each chip's ``XLA Modules`` line, whose
  events name the program that ran (``jit_supervised_phase(...)``).
* The driver thread is the host line that holds ``bench.window``.  Spans
  on other lines (the prefetch worker's ``semisfl.batch.*``) are host
  work that overlapped the driver; they take no idle time.
* On one line spans nest.  At each moment the innermost open span owns
  that moment; a span's self time is its interval less its children's.
* The idle intervals of a chip are the window less the union of its
  operations, as ``trace.summarize`` computes them; each is split over the
  driver thread's innermost span and averaged over the chips, so the
  parts sum to the idle time behind ``device.idle_share``.
* A phase program's device time is that of the events of the chip's
  ``XLA Modules`` line named ``jit_<name>``.
"""
from __future__ import annotations

import gzip
import json
import re
import sys
from collections import defaultdict
from dataclasses import dataclass, field
from pathlib import Path

if __name__ == "__main__":
    sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from bench import trace  # noqa: E402

MODULES_LINE = "XLA Modules"
PREFIXES = (trace.SPAN_PREFIX, "semisfl.")
NONE = "none"
ROUND = "semisfl.round"
# idle groups: the reading ``idle.<group>_share`` is the idle time whose
# innermost driver span is one of these; ``other`` is the rest
GROUPS = {
    "batch": ("semisfl.batch.labeled", "semisfl.batch.clients",
              "semisfl.prefetch.wait"),
    "fedavg": ("semisfl.broadcast", "semisfl.fedavg"),
    "sync": ("semisfl.sync",),
}


@dataclass
class Trace(trace.Trace):
    """``trace.Trace`` with ``threads = [[(name, start, end)]]``, one list
    per host line that holds a benchmark or program span, and
    ``modules[device] = [(name, start, end)]``."""
    threads: list = field(default_factory=list)
    modules: dict = field(default_factory=dict)


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
        if trace.DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                events = [(e.name, e.start_ns, e.end_ns)
                          for e in line.events]
                if line.name == trace.OPS_LINE:
                    tr.ops[plane.name] = [(trace.op_label(n), s, e)
                                          for n, s, e in events]
                elif line.name == MODULES_LINE:
                    tr.modules[plane.name] = events
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                ours = [(e.name, e.start_ns, e.end_ns) for e in line.events
                        if e.name.startswith(PREFIXES)]
                tr.spans += [v for v in ours
                             if v[0].startswith(trace.SPAN_PREFIX)]
                if ours:
                    tr.threads.append(ours)
    return tr


def driver_thread(tr: Trace) -> list:
    """The spans of the host line that holds the window."""
    found = [line for line in tr.threads
             if any(n == trace.WINDOW for n, _, _ in line)]
    if len(found) != 1:
        raise ValueError(f"expected one host line holding {trace.WINDOW!r}, "
                         f"found {len(found)}")
    return found[0]


def innermost(line, lo, hi) -> list:
    """``[(name, start, end)]``: [lo, hi) cut into disjoint pieces, each
    owned by the innermost span of ``line`` open there (``none`` where no
    span is).  The spans of one line nest."""
    out, stack, t = [], [], lo

    def upto(x):
        nonlocal t
        if x > t:
            out.append((stack[-1][0] if stack else NONE, t, x))
            t = x

    for name, s, e in sorted(line, key=lambda v: (v[1], -v[2])):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        while stack and stack[-1][1] <= s:
            upto(stack[-1][1])
            stack.pop()
        upto(s)
        stack.append((name, e))
    while stack:
        upto(stack[-1][1])
        stack.pop()
    upto(hi)
    return out


def self_seconds(line, lo, hi) -> dict:
    """Self time of each span name of ``line`` inside [lo, hi), seconds."""
    out = defaultdict(float)
    for name, s, e in innermost(line, lo, hi):
        if name != NONE:
            out[name] += (e - s) * 1e-9
    return dict(out)


def idle_by_span(tr: Trace) -> dict:
    """Idle seconds of the window per chip (averaged over the chips), by
    the driver thread's innermost span."""
    lo, hi = trace.window(tr)
    pieces = innermost(driver_thread(tr), lo, hi)
    out = defaultdict(float)
    for events in tr.ops.values():
        busy = trace.union(trace.clip([(s, e) for _, s, e in events],
                                      lo, hi))
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        idle = [(s, e) for s, e in zip(edges[::2], edges[1::2]) if e > s]
        i = j = 0
        while i < len(idle) and j < len(pieces):
            name, s2, e2 = pieces[j]
            s, e = max(idle[i][0], s2), min(idle[i][1], e2)
            if s < e:
                out[name] += e - s
            if idle[i][1] < e2:
                i += 1
            else:
                j += 1
    n = max(len(tr.ops), 1)
    return {k: v / n * 1e-9 for k, v in out.items()}


def rounds(tr: Trace) -> int:
    """``semisfl.round`` spans the driver thread started in the window."""
    lo, hi = trace.window(tr)
    return sum(1 for n, s, _ in driver_thread(tr)
               if n == ROUND and lo <= s < hi)


def idle_shares(tr: Trace) -> dict | None:
    """``{group: % of the window}`` for ``batch``, ``fedavg``, ``sync``
    and ``other``; they sum to ``device.idle_share``.  None where the
    program opened no ``semisfl.round`` span or the chip ran nothing."""
    if not tr.ops or not rounds(tr):
        return None
    lo, hi = trace.window(tr)
    window_s = (hi - lo) * 1e-9
    owner = {name: g for g, names in GROUPS.items() for name in names}
    out = dict.fromkeys([*GROUPS, "other"], 0.0)
    for name, secs in idle_by_span(tr).items():
        out[owner.get(name, "other")] += 100.0 * secs / window_s
    return out


def module_seconds(tr: Trace, name: str) -> tuple:
    """(calls, seconds) per chip of the program whose jitted function is
    named ``name`` (module ``jit_<name>``), inside the window."""
    lo, hi = trace.window(tr)
    rx = re.compile(rf"^jit_{re.escape(name)}(?!\w)")
    calls, total = 0, 0.0
    for events in tr.modules.values():
        hits = trace.clip([(s, e) for n, s, e in events if rx.match(n)],
                          lo, hi)
        calls += len(hits)
        total += trace.length(hits)
    n = max(len(tr.modules), 1)
    return calls / n, total / n * 1e-9


def phase_ms(tr: Trace, name: str) -> float | None:
    """Device milliseconds of the phase program ``name`` per
    ``semisfl.round`` span of the window; None where neither is there."""
    calls, secs = module_seconds(tr, name)
    n = rounds(tr)
    if calls <= 0 or n <= 0:
        return None
    return 1e3 * secs / n


def metrics(tr: Trace) -> dict:
    """The per-layer readings of a window, by the names a benchmark
    entry would give them; a reading with nothing to read is left out."""
    out = {f"idle.{g}_share": v
           for g, v in (idle_shares(tr) or {}).items()}
    for key, name in (("phase.supervised_ms", "supervised_phase"),
                      ("phase.cross_entity_ms", "cross_entity_phase")):
        v = phase_ms(tr, name)
        if v is not None:
            out[key] = v
    return out


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    if len(argv) != 1:
        print(__doc__.split("\n\n")[1], file=sys.stderr)
        return 2
    path = Path(argv[0])
    tr = load(path if path.is_file() else trace.find_xplane(path))
    s = trace.summarize(tr)
    out = metrics(tr)
    out["device.idle_share"] = 100.0 * (1.0 - s["busy_s"] / s["window_s"])
    out["rounds"] = rounds(tr)
    out["busy_s"], out["window_s"] = s["busy_s"], s["window_s"]
    lo, hi = trace.window(tr)
    out["self_s"] = self_seconds(driver_thread(tr), lo, hi)
    out["idle_s"] = idle_by_span(tr)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
