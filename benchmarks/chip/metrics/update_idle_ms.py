"""update_idle_ms: device-0 idle time inside the program's grpo.update
span (advantages, old log-probs, the mask, the GRPO step and its loss on
the host), per traced step (ms).  Spans are aligned as in sample_idle_ms."""

from chip.metrics.sample_idle_ms import idle_ms


def read(run):
    return idle_ms(run, "grpo.update")
