"""Share of the traced window in which no operation ran on the device:
1 - the union of the device's op intervals over the window."""
import tracereduce


def read(m):
    if m.trace is None or m.trace.hi <= m.trace.lo:
        return None
    busy = tracereduce.busy_ns(m.trace)
    return 100.0 * (1.0 - busy / (m.trace.hi - m.trace.lo))
