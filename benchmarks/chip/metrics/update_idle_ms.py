"""update_idle_ms: device-0 idle time inside the program's grpo.update
span (advantages, old log-probs, the mask, the GRPO step and its loss on
the host), per traced step (ms); spans put on the trace's clock as
``trace.traced_steps`` says."""

from chip.trace import idle_ms


def read(run):
    return idle_ms(run, "grpo.update")
