"""Mean device time of one run of the batched decode program in the
traced window."""
import tracereduce

DECODE = "decode_fn"


def read(m):
    if m.trace is None:
        return None
    runs = tracereduce.program_times(m.trace, DECODE)
    return sum(runs) * 1e-6 / len(runs) if runs else None
