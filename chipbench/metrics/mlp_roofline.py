"""The MLPs' share of their roofline in the decode program: the least time
the chip needs to read the MLP weights once a step (the family's
``mlp_weight_bytes`` over the peak bandwidth) over the device time under
the ``mlp`` scope per run of the decode program, in the traced window.

Bytes bound it: each weight takes part in one multiply-add per token, so
at the cells' at most 16 tokens a step the operations would take 16 x
819e9 / 197e12, a fifteenth, of the bytes' time at the v5e's peaks.  A
family whose counts have no ``mlp_weight_bytes`` reports nothing."""
import scopes
import spec

DECODE = "decode_fn"


def read(m):
    weight_bytes = getattr(spec.family(m.conf, "counts"), "mlp_weight_bytes",
                           None)
    if weight_bytes is None:
        return None
    ns, runs = scopes.ns_under(m, DECODE, "mlp")
    if not runs or ns <= 0:
        return None
    least_s = weight_bytes(m.conf) / m.peaks["hbm_bytes_per_s"]
    return 100.0 * least_s / (ns * 1e-9 / runs)
