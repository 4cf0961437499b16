"""Device time of the prefill program per thousand real prompt tokens in
the traced window (padding to the engine's buckets is not counted as
tokens)."""
import tracereduce

PREFILL = "prefill_fn"


def read(m):
    if m.trace is None:
        return None
    runs = tracereduce.program_times(m.trace, PREFILL)
    tokens = sum(m.prefill_lens)
    if not runs or tokens <= 0:
        return None
    return sum(runs) * 1e-6 / tokens * 1000.0
