"""The whole serving step's share of the chip's peak bf16 rate: the model
operations of the real tokens prefilled and served in the traced window
(from shapes; padding, masked cache positions and recomputation are not
counted) over the window's length times the peak."""
import counts


def read(m):
    if m.trace is None:
        return None
    window_s = (m.trace.hi - m.trace.lo) * 1e-9
    flops = sum(counts.prefill_flops(m.conf, n) for n in m.prefill_lens)
    flops += sum(counts.decode_flops(m.conf, c) for c in m.decoded_ctx)
    if window_s <= 0 or flops <= 0:
        return None
    return 100.0 * flops / (window_s * m.peaks["bf16_flops_per_s"])
