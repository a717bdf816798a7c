"""sample_idle_ms: device-0 idle time inside the program's engine.sample
spans, the rollout's eager sampling between decode programs, per traced
step (ms).

The program stamps its spans on ``time.perf_counter_ns`` and hands them
out on the step's report (``report.spans``).  Each traced step's spans are
put on the trace's clock with one offset: the start of the step's
``step`` span in the trace less the harness's ``t0`` stamp of it.  The
offset read at the step's end (``t1``) has to agree within 0.5 ms, else
the reader returns None; so does a program that records no spans."""

from chip import trace

#: largest disagreement of the offsets at a step's start and end
AGREE_NS = 500_000


def traced_steps(run):
    """``(step record, offset ns)`` for each traced step, or None."""
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
    in the union of its op intervals, per traced step (ms)."""
    steps = traced_steps(run)
    if steps is None:
        return None
    devices = run.events["devices"]
    busy = trace.union((s, s + d) for _, s, d in devices[sorted(devices)[0]])
    idle, found = 0, False
    for rec, off in steps:
        inside = trace.union((s.start_ns + off, s.end_ns + off)
                             for s in rec.report.spans if s.name == name)
        found = found or bool(inside)
        idle += trace.total(inside) - trace.total(trace.intersect(busy, inside))
    return idle / len(steps) / 1e6 if found else None


def read(run):
    return idle_ms(run, "engine.sample")
