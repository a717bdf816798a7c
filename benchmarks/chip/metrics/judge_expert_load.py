"""judge_expert_load: assignments that landed on each held expert, per
expert layer and per judge call, averaged over the window's calls, from
the judge's routing counters on ``LiveStepReport.counters`` (count).
None where the program reports no counters (a judge without routed
experts, or a program that does not count)."""


def read(run):
    assigned = slots = 0
    for rec in run.steps:
        c = getattr(rec.report, "counters", None)
        if not c:
            return None
        assigned += int(c["held_assignments"].sum())
        slots += c["judge_calls"] * len(c["held_assignments"]) * c["held_experts"]
    return assigned / slots if slots else None
