"""The decode program's share of its roofline: the least time the chip
needs for a step (the bytes it must move over the peak bandwidth; at
these batch sizes bytes bound a decode step, not operations) over the
mean device time of the decode program, in the traced window.

Bytes per step: every weight read once, plus, for the tokens served in
the window spread over its steps, each sequence's live keys and values
read, its new ones written and its recurrent state read and written
(counts.py)."""
import counts
import tracereduce

DECODE = "decode_fn"


def read(m):
    if m.trace is None or m.trace_steps <= 0 or not m.decoded_ctx:
        return None
    runs = tracereduce.program_times(m.trace, DECODE)
    if not runs:
        return None
    step_s = sum(runs) * 1e-9 / len(runs)
    seq_bytes = sum(counts.decode_token_bytes(m.conf, c)
                    for c in m.decoded_ctx)
    step_bytes = counts.weight_bytes(m.conf) + seq_bytes / m.trace_steps
    flops = sum(counts.decode_flops(m.conf, c) for c in m.decoded_ctx)
    least = max(step_bytes / m.peaks["hbm_bytes_per_s"],
                flops / m.trace_steps / m.peaks["bf16_flops_per_s"])
    return 100.0 * least / step_s
