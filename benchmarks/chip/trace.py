"""From a profiler trace to device metrics.

``load`` reads the ``.xplane.pb`` the JAX profiler wrote into a small
dict: the op events of each device (the ``XLA Ops`` line of each
``/device:TPU:<n>`` plane) and the host spans the harness annotated.  The
rest works on that dict, so that a recorded trace checks the reduction:

- busy: the union of the device's op intervals inside the window;
- idle gaps: the holes in that union, cut at span boundaries, each piece
  named by the innermost harness span that covers it (what the host was
  doing then);
- device time inside the spans of one name (``judge_score``);
- the ops that took most time, named by the span they fell in (ops that
  hold other ops, as a ``while`` holds its body, are left out there);
- device idle inside the program's own spans (``idle_ms``).
"""

from __future__ import annotations

import bisect
import re
from collections import defaultdict
from typing import Dict, Iterable, List, Sequence, Tuple

#: the harness's host spans, as annotated (see harness.SPANS)
SPANS = ("step", "rollout", "reward", "judge_score", "update")
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OP_LINE = "XLA Ops"
#: largest disagreement of a traced step's clock offsets at its start and end
AGREE_NS = 500_000

Interval = Tuple[int, int]


def load(path: str) -> Dict:
    """``{"devices": {plane: [[op, start_ns, dur_ns], ...]},
    "spans": [[name, start_ns, dur_ns], ...]}`` from an xplane file."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices, spans = {}, []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            ops = []
            for line in plane.lines:
                if line.name == OP_LINE:
                    ops.extend([op_name(e.name), int(e.start_ns), int(e.duration_ns)]
                               for e in line.events)
            devices[plane.name] = ops
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend([e.name, int(e.start_ns), int(e.duration_ns)]
                             for e in line.events if e.name in SPANS)
    return {"devices": devices, "spans": spans}


def op_name(text: str) -> str:
    """``%fusion.84 = (f32[255]...) fusion(...)`` -> ``fusion.84``."""
    return text.split(" = ", 1)[0].lstrip("%")


def leaves(ops: Sequence) -> List:
    """Ops that hold no other op: a ``while`` or ``call`` event spans the ops
    of its body on the same line, and would count their time twice."""
    ordered = sorted(ops, key=lambda o: (o[1], -o[2]))
    out = []
    for i, (n, s, d) in enumerate(ordered):
        nxt = ordered[i + 1] if i + 1 < len(ordered) else None
        if nxt is None or not (nxt[1] < s + d and nxt[1] + nxt[2] <= s + d):
            out.append((n, s, d))
    return out


def union(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[List[int]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def clip(intervals: Iterable[Interval], lo: int, hi: int) -> List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals if b > lo and a < hi]


def total(intervals: Iterable[Interval]) -> int:
    return sum(b - a for a, b in intervals)


def _op_intervals(ops: Sequence) -> List[Interval]:
    return [(s, s + d) for _, s, d in ops]


def window(tr: Dict) -> Interval:
    """The traced window: the first ``step`` span's start to the last's end."""
    steps = [(s, s + d) for n, s, d in tr["spans"] if n == "step"]
    if not steps:
        raise ValueError("the trace holds no step span")
    return min(a for a, _ in steps), max(b for _, b in steps)


def busy_ns(ops: Sequence, win: Interval) -> int:
    return total(clip(union(_op_intervals(ops)), *win))


def intersect(a: List[Interval], b: List[Interval]) -> List[Interval]:
    """Intersection of two sorted, disjoint interval lists."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if lo < hi:
            out.append((lo, hi))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def time_in_spans(ops: Sequence, spans: Sequence, name: str) -> int:
    """Device busy time inside the host spans called ``name``."""
    inside = union((s, s + d) for n, s, d in spans if n == name)
    return total(intersect(union(_op_intervals(ops)), inside))


def _namer(spans: Sequence):
    """Innermost (shortest) span covering a time point; ``"other"`` if none.
    The span boundaries cut time into pieces, each named once, and a point
    is looked up by bisection.  Returns the lookup and the cuts."""
    cuts = sorted({t for _, s, d in spans for t in (s, s + d)})
    by_len = sorted(((s, s + d, n) for n, s, d in spans), key=lambda t: t[1] - t[0])
    names = []
    for a, b in zip(cuts, cuts[1:]):
        mid = (a + b) / 2
        names.append(next((n for s, e, n in by_len if s <= mid < e), "other"))

    def name(t: float) -> str:
        i = bisect.bisect_right(cuts, t) - 1
        return names[i] if 0 <= i < len(names) else "other"

    return name, cuts


def idle_gaps(ops: Sequence, spans: Sequence, win: Interval) -> List[Tuple[str, int, int]]:
    """Holes of the busy union inside ``win``, cut where a span begins or
    ends: ``(span, start_ns, len_ns)``, each piece named by what the host
    was doing in it."""
    busy = clip(union(_op_intervals(ops)), *win)
    name, cuts = _namer(spans)
    gaps, t = [], win[0]
    for a, b in busy + [(win[1], win[1])]:
        if a > t:
            lo = bisect.bisect_right(cuts, t)
            hi = bisect.bisect_left(cuts, a)
            edges = [t] + cuts[lo:hi] + [a]
            gaps.extend((name((x + y) / 2), x, y - x) for x, y in zip(edges, edges[1:]) if y > x)
        t = max(t, b)
    return gaps


def top_ops(ops: Sequence, spans: Sequence, win: Interval, k: int = 10) -> List[Tuple[str, float]]:
    """Device ops by total time in ``win``, as ``span/op``, in seconds."""
    name, _ = _namer(spans)
    acc: Dict[str, int] = defaultdict(int)
    for op, s, d in leaves(ops):
        if s + d > win[0] and s < win[1]:
            acc[f"{name(s + d / 2)}/{op}"] += d
    return [(n, v / 1e9) for n, v in sorted(acc.items(), key=lambda kv: -kv[1])[:k]]


def idle_by_span(gaps: Sequence, k: int = 10) -> List[Tuple[str, float]]:
    """Idle time summed by what the host was doing, longest first, in seconds."""
    acc: Dict[str, int] = defaultdict(int)
    for n, _, d in gaps:
        acc[n] += d
    return [(n, v / 1e9) for n, v in sorted(acc.items(), key=lambda kv: -kv[1])[:k]]


def summarize(tr: Dict) -> Dict:
    """The numbers the per-layer readers take, averaged over the devices."""
    win = window(tr)
    devs = sorted(tr["devices"])
    if not devs:
        raise ValueError("the trace holds no TPU device plane")
    ops_all = [tr["devices"][d] for d in devs]
    if not any(ops_all):
        raise ValueError("no operation ran on the device in the traced window")
    n = len(devs)
    gaps = idle_gaps(ops_all[0], tr["spans"], win)
    return {
        "window_s": (win[1] - win[0]) / 1e9,
        "busy_s": sum(busy_ns(o, win) for o in ops_all) / n / 1e9,
        "judge_device_s": sum(time_in_spans(o, tr["spans"], "judge_score") for o in ops_all) / n / 1e9,
        "judge_calls": sum(1 for s in tr["spans"] if s[0] == "judge_score"),
        "steps": sum(1 for s in tr["spans"] if s[0] == "step"),
        "device_ops": top_ops(ops_all[0], tr["spans"], win),
        "idle_gaps": idle_by_span(gaps),
    }


def traced_steps(run):
    """``(step record, offset ns)`` for each traced step of a harness run,
    or None.

    The program stamps its spans on ``time.perf_counter_ns`` and hands them
    out on the step's report (``report.spans``).  Each traced step's spans
    go on the trace's clock with one offset: the start of the step's
    ``step`` span in the trace less the harness's ``t0`` stamp of it.  The
    offset read at the step's end (``t1``) has to agree within
    ``AGREE_NS``, else this returns None; so it does for a program that
    records no spans."""
    if not run.trace or not run.events:
        return None
    n = run.trace["steps"]
    marks = sorted((s, s + d) for name, s, d in run.events["spans"] if name == "step")[:n]
    if len(marks) < n or len(run.steps) < n:
        return None
    out = []
    for rec, (a, b) in zip(run.steps, marks):
        if not getattr(rec.report, "spans", None):
            return None
        off = a - round(rec.t0 * 1e9)
        if abs(b - round(rec.t1 * 1e9) - off) > AGREE_NS:
            return None
        out.append((rec, off))
    return out or None


def idle_ms(run, name: str):
    """Device-0 idle inside the program spans called ``name``, the holes
    in the union of its op intervals, per traced step (ms); None where the
    steps cannot be put on the trace's clock or hold no such span."""
    steps = traced_steps(run)
    if steps is None:
        return None
    devices = run.events["devices"]
    busy = union((s, s + d) for _, s, d in devices[sorted(devices)[0]])
    idle, found = 0, False
    for rec, off in steps:
        inside = union((s.start_ns + off, s.end_ns + off)
                       for s in rec.report.spans if s.name == name)
        found = found or bool(inside)
        idle += total(inside) - total(intersect(busy, inside))
    return idle / len(steps) / 1e6 if found else None
