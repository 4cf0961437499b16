"""Device time per run of the decode program of the operations that lie
under no scope but ``layers``, or under none, in the traced window: the
layer scan's slices of the stacked weights and cache, and the whole-cache
copies at the step's end.  Instructions that the compiler made and gave
no op name (the copies it inserts) count as under none."""
import scopes

DECODE = "decode_fn"


def read(m):
    names = m.op_names.get(DECODE)
    if m.trace is None or not names:
        return None
    by_scope, runs = scopes.scope_times(m.trace, names, DECODE)
    if not runs:
        return None
    return (by_scope[""] + by_scope["layers"]) * 1e-6 / runs
