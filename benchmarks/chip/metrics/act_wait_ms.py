"""act_wait_ms: mean time a reward action waits between its submit and the
start of its payload (the judge call), over the window's actions, on the
program's own real-clock stamps (``report.actions``) (ms).  None where the
program stamps no actions."""


def read(run):
    waits = []
    for rec in run.steps:
        actions = getattr(rec.report, "actions", None)
        if not actions:
            return None
        waits.extend(start - submit for submit, start, _ in actions if start is not None)
    return sum(waits) / len(waits) / 1e6 if waits else None
