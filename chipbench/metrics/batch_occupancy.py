"""Tokens served per decode step over the engine's slots, in the traced
window: how full the engine keeps its batch."""


def read(m):
    if m.trace is None or m.trace_steps <= 0:
        return None
    return 100.0 * m.trace_generated / (m.trace_steps * m.sizes["max_batch"])
