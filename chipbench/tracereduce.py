"""From a profiler trace to device intervals, program times and idle gaps.

The trace is JAX's ``.xplane.pb``, read with ``jax.profiler.ProfileData``.
The benchmark marks the measured part of the trace with a host span named
``WINDOW``; everything is clipped to it.  On the device plane of the chip
(``/device:TPU:0``) the line ``XLA Ops`` holds each operation the device
ran and ``XLA Modules`` each run of a whole program, named after the jitted
function (``jit_decode_fn``).  Host threads are the lines of the host
plane; what they were running labels the gaps in which the device sat idle.
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import glob
import os

WINDOW = "chipbench-window"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


@dataclasses.dataclass
class Reduced:
    lo: int                      # the window, in the trace's nanoseconds
    hi: int
    ops: list                    # (start, end, name) on the device
    modules: list                # (start, end, name) on the device
    host: list                   # (start, end, name, thread)

    @property
    def window_s(self) -> float:
        return (self.hi - self.lo) * 1e-9


def find_xplane(log_dir: str) -> str:
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise FileNotFoundError(f"{len(paths)} traces under {log_dir}")
    return paths[0]


def _events(line):
    for e in line.events:
        s = int(e.start_ns)
        yield s, s + int(e.duration_ns), e.name


def reduce(path: str, device: str = "/device:TPU:0") -> Reduced:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    lo = hi = None
    ops, modules, host = [], [], []
    for plane in pd.planes:
        if plane.name == device:     # absent where the device ran nothing
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops.extend(_events(line))
                elif line.name == MODULES_LINE:
                    modules.extend(_events(line))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for s, e, name in _events(line):
                    if name == WINDOW:
                        lo, hi = s, e
                    else:
                        host.append((s, e, name, line.name))
    if lo is None:
        raise ValueError(f"no {WINDOW} span in {path}")
    clip = lambda evs: sorted((max(s, lo), min(e, hi), n) for s, e, n in evs
                              if e > lo and s < hi)
    host = [h for h in host if h[1] > lo and h[0] < hi]
    return Reduced(lo, hi, clip(ops), clip(modules), host)


def merged(intervals) -> list[tuple[int, int]]:
    """The union of (start, end, ...) intervals as sorted disjoint pairs."""
    out: list[list[int]] = []
    for s, e, *_ in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy_ns(red: Reduced) -> int:
    return sum(e - s for s, e in merged(red.ops))


def idle_gaps(red: Reduced) -> list[tuple[int, int]]:
    gaps, t = [], red.lo
    for s, e in merged(red.ops):
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if red.hi > t:
        gaps.append((t, red.hi))
    return gaps


def program_times(red: Reduced, pattern: str) -> list[int]:
    """Device nanoseconds of each run of the programs whose name holds
    ``pattern``."""
    return [e - s for s, e, n in red.modules if pattern in n]


def other_program_times(red: Reduced, patterns) -> list[int]:
    return [e - s for s, e, n in red.modules
            if not any(p in n for p in patterns)]


#: host spans longer than this (thread loops, the window itself) label
#: no gap; it also bounds how far back a gap looks for its spans
LONGEST_LABEL_NS = 100_000_000


def _labeller(red: Reduced):
    spans = sorted(h for h in red.host if h[1] - h[0] <= LONGEST_LABEL_NS)
    starts = [h[0] for h in spans]

    def label(lo: int, hi: int) -> str:
        """What the host was doing in a gap: the shortest host span that
        covers at least half of it, or else the one that covers most."""
        best, best_key = "no host span", None
        j = bisect.bisect_left(starts, hi) - 1
        while j >= 0 and starts[j] > lo - LONGEST_LABEL_NS:
            s, e, name, _thread = spans[j]
            j -= 1
            cover = min(e, hi) - max(s, lo)
            if cover <= 0:
                continue
            key = (0, e - s) if 2 * cover >= hi - lo else (1, -cover)
            if best_key is None or key < best_key:
                best, best_key = name, key
        return best
    return label


def short_name(name: str) -> str:
    """``jit_decode_fn(1808...)`` -> ``decode_fn``; an op's HLO text
    ``%fusion.3 = bf16[...] fusion(...)`` -> ``fusion.3``."""
    name = name.split(" = ", 1)[0].lstrip("%")
    if name.startswith("jit_"):
        name = name[4:].split("(", 1)[0]
    return name


def breakdown(red: Reduced, top: int = 10) -> dict:
    """The device ops that took most time (as ``program/op``) and the idle
    time by what the host was doing, each with its seconds."""
    starts = [s for s, _, _ in red.modules]
    by_op = collections.Counter()
    for s, e, n in red.ops:
        i = bisect.bisect_right(starts, s) - 1
        prog = (short_name(red.modules[i][2])
                if i >= 0 and red.modules[i][1] >= e else "?")
        by_op[f"{prog}/{short_name(n)}"] += e - s
    by_gap = collections.Counter()
    label = _labeller(red)
    for lo, hi in idle_gaps(red):
        by_gap[label(lo, hi)] += hi - lo
    return {
        "device_ops": [[n, t / 1e9] for n, t in by_op.most_common(top)],
        "idle_gaps": [[n, t / 1e9] for n, t in by_gap.most_common(top)],
    }
