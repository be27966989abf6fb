"""device program: `dense_fallback_waves` counter growth over the window
(waves that overflowed the sparse budgets and re-encoded dense)."""

from tvtbench import evidence


def read(ev):
    return evidence.stage_delta(ev, "dense_fallback_waves")
