"""Device time of the programs that are neither prefill nor decode (the
batch-1 cache made for each admission and the copy of its slot into the
batch cache) per admission, in the traced window.  An admission is a run
of the prefill program."""
import tracereduce

PREFILL, DECODE = "prefill_fn", "decode_fn"


def read(m):
    if m.trace is None:
        return None
    admissions = len(tracereduce.program_times(m.trace, PREFILL))
    if not admissions:
        return None
    other = tracereduce.other_program_times(m.trace, (PREFILL, DECODE))
    return sum(other) * 1e-6 / admissions
