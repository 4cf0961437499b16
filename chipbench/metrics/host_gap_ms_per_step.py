"""Device idle time in the traced window per batched decode step: the
host's share of a step (the client, the control plane's round trip and
the engine's own loop), as the device sees it."""
import tracereduce


def read(m):
    if m.trace is None or m.trace_steps <= 0:
        return None
    idle = (m.trace.hi - m.trace.lo) - tracereduce.busy_ns(m.trace)
    return idle * 1e-6 / m.trace_steps
