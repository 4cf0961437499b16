"""The device plane, the profiler's host plane and the program's
``perf_counter`` on one clock, and the device's idle time put down to what
the host was doing.

A profiler trace (``.xplane.pb``) holds two clocks.  The host plane's holds
the benchmark's window span and the engine's ``serve.*`` spans; the device
plane's holds the chip's program runs (``XLA Modules``) and operations
(``XLA Ops``).  They are offset by a millisecond or two.  Every program run
carries a ``run_id``, and so do the two host events around it:
``DoEnqueueProgram``, the launching thread handing the run to the chip, and
``CompleteCallbacks``, the host learning that the run is done.  A run cannot
start before its enqueue ends nor end after its completion callback starts
(prefill runs and the engine's eager copies get no callback), so each run
bounds the offset from one side or both.  :func:`device_offset` takes the
least offset the enqueues allow, the min-delay estimate of
``repro.core.tracing.worker_offsets`` (the run that started soonest after
its enqueue is taken to have started the moment the enqueue ended), for as
long as no completion callback forbids it; the device clock then has
stepped, and a new stretch begins.

The program's task spans (``repro.core.tracing.TaskSpan``) are in
``time.perf_counter`` seconds.  Readings of ``perf_counter_ns`` taken just
inside the window span map them onto the window span's ends
(:class:`Anchor`).

:func:`split_idle` puts each instant of the device's idle time down to the
first of these that covers it: a task's control-plane segments, a task's
execution (``started->finished``: the worker's call into the jitted step,
which returns once the step is dispatched), a ``serve.*`` span, and
otherwise a remainder, inside a program run or under no span at all.
"""
from __future__ import annotations

import dataclasses

import tracereduce

ENQUEUE = "DoEnqueueProgram"
COMPLETE = "CompleteCallbacks"
SERVE = "serve."

#: a task span's boundaries around each of its control-plane segments
#: (every segment but ``started->finished``)
CONTROL = (("t_submit", "t_ingest"), ("t_ingest", "t_queued"),
           ("t_queued", "t_dispatched"), ("t_dispatched", "t_start"),
           ("t_end", "t_observed"))

#: the parts of :func:`split_idle`, in the order they claim idle time
PARTS = ("runtime", "dispatch", "engine", "in_program", "no_span")


class ClockError(ValueError):
    """The device plane cannot be put on the host plane's clock."""


@dataclasses.dataclass
class Planes:
    """What the clocks are read from, each time on its own plane's clock."""
    lo: int                      # the window span on the host plane
    hi: int
    runs: list                   # (start, end, name, run_id) on the device
    ops: list                    # (start, end, name) on the device
    enqueued: dict               # run_id -> end of its first enqueue
    completed: dict              # run_id -> start of its first callback
    serve: list                  # (start, end, name) of the serve.* spans


def _stat(event, key):
    return next((v for k, v in event.stats if k == key), None)


def read(path: str, device: str = "/device:TPU:0") -> Planes:
    from jax.profiler import ProfileData
    lo = hi = None
    runs, ops, serve = [], [], []
    enqueued, completed = {}, {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name == device:
            for line in plane.lines:
                if line.name == tracereduce.OPS_LINE:
                    ops.extend(tracereduce._events(line))
                elif line.name == tracereduce.MODULES_LINE:
                    for e in line.events:
                        s = int(e.start_ns)
                        runs.append((s, s + int(e.duration_ns), e.name,
                                     _stat(e, "run_id")))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    name, s = e.name, int(e.start_ns)
                    if name == tracereduce.WINDOW:
                        lo, hi = s, s + int(e.duration_ns)
                    elif name == ENQUEUE:
                        rid, end = _stat(e, "run_id"), s + int(e.duration_ns)
                        enqueued[rid] = min(end, enqueued.get(rid, end))
                    elif name == COMPLETE:
                        rid = _stat(e, "run_id")
                        completed[rid] = min(s, completed.get(rid, s))
                    elif name.startswith(SERVE):
                        serve.append((s, s + int(e.duration_ns), name))
    if lo is None:
        raise ValueError(f"no {tracereduce.WINDOW} span in {path}")
    return Planes(lo, hi, sorted(runs, key=lambda r: r[:2]), sorted(ops),
                  enqueued, completed, sorted(serve))


@dataclasses.dataclass
class Offset:
    """Nanoseconds to add to a device time to read it on the host plane.

    The device clock can step against the host's within one trace (by
    0.08-0.19 ms, once in most 3.3 s traces of the benchmark's cells on
    a v5e), so the offset holds in stretches: ``steps`` lists
    (device time from which a stretch holds, its offset), in time order."""
    steps: list
    linked: int                  # runs with an enqueue or a callback

    def at(self, t: int) -> int:
        """The offset of a device time."""
        d = self.steps[0][1]
        for start, off in self.steps:
            if start > t:
                break
            d = off
        return d


def device_offset(planes: Planes) -> Offset:
    """The least offset that puts each run after its enqueue, held until a
    run's completion callback forbids it; from that run on, a new stretch.
    A run whose own enqueue and callback cannot both hold stops it."""
    stretches = []               # [device start, least, most]
    linked = 0
    for s, e, name, rid in planes.runs:
        low = planes.enqueued[rid] - s if rid in planes.enqueued else None
        high = planes.completed[rid] - e if rid in planes.completed else None
        if low is None and high is None:
            continue
        linked += 1
        if low is not None and high is not None and low > high:
            raise ClockError(
                f"run {rid} ({name}) starts on the device {low} ns before "
                f"its enqueue ends on the host, but ends only {high} ns "
                f"before its completion callback starts")
        if stretches:
            _, least, most = stretches[-1]
            least = _bound(max, least, low)
            most = _bound(min, most, high)
            if least is None or most is None or least <= most:
                stretches[-1][1:] = least, most
                continue
        stretches.append([s, low, high])
    if not stretches:
        raise ClockError("no device run is linked to the host")
    return Offset([(s, least if least is not None else most)
                   for s, least, most in stretches], linked)


def _bound(pick, a, b):
    return a if b is None else b if a is None else pick(a, b)


def paired_runs(planes: Planes, off: Offset, patterns) -> dict:
    """Of the runs of the programs named by ``patterns`` that lie in the
    window once aligned: how many there are, how many lie after their
    enqueue and before their completion callback, how many have only an
    enqueue in the trace, and the run ids of any outside their pair."""
    out = {"runs": 0, "inside": 0, "enqueue_only": 0, "outside": []}
    for s, e, name, rid in planes.runs:
        d = off.at(s)
        s, e = s + d, e + d
        if not any(p in name for p in patterns) or e <= planes.lo \
                or s >= planes.hi:
            continue
        out["runs"] += 1
        enq, done = planes.enqueued.get(rid), planes.completed.get(rid)
        if (enq is not None and s < enq) or (done is not None and e > done):
            out["outside"].append(rid)
        elif enq is not None and done is not None:
            out["inside"] += 1
        elif enq is not None:
            out["enqueue_only"] += 1
    return out


def aligned(planes: Planes, off: Offset) -> tracereduce.Reduced:
    """The window with the device plane moved onto the host plane's clock;
    its host spans are the ``serve.*`` spans."""
    lo, hi = planes.lo, planes.hi

    def clip(events):
        out = []
        for s, e, n, *_ in events:
            d = off.at(s)
            if e + d > lo and s + d < hi:
                out.append((max(s + d, lo), min(e + d, hi), n))
        return sorted(out)
    serve = [(s, e, n, "serve") for s, e, n in planes.serve
             if e > lo and s < hi]
    return tracereduce.Reduced(lo, hi, clip(planes.ops), clip(planes.runs),
                               serve)


@dataclasses.dataclass
class Anchor:
    """``perf_counter_ns`` readings taken just inside the window span
    (``p0``, ``p1``) and the window span's ends on the trace's host clock."""
    p0: int
    p1: int
    lo: int
    hi: int

    def ns(self, t: float) -> int:
        """``perf_counter`` seconds on the trace's host clock."""
        scale = (self.hi - self.lo) / (self.p1 - self.p0)
        return self.lo + round((t * 1e9 - self.p0) * scale)

    def holds(self, t: float) -> bool:
        return self.p0 <= t * 1e9 <= self.p1


def task_intervals(spans, anchor: Anchor) -> tuple[list, list]:
    """The control-plane segments and the execution segments of task spans,
    as (start, end) on the trace's host clock."""
    control, execution = [], []

    def add(out, a, b):
        if a is not None and b is not None and b > a:
            out.append((anchor.ns(a), anchor.ns(b)))
    for sp in spans:
        for a, b in CONTROL:
            add(control, getattr(sp, a), getattr(sp, b))
        add(execution, sp.t_start, sp.t_end)
    return control, execution


def control_s(span) -> float:
    """A task span's time in its control-plane segments."""
    seg = span.segments()
    return sum(v for k, v in seg.items() if k != "started->finished")


def _overlap(a: list, b: list) -> list:
    """The intersection of two sorted lists of disjoint (start, end)."""
    out, j = [], 0
    for s, e in a:
        while j < len(b) and b[j][1] <= s:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            lo, hi = max(s, b[k][0]), min(e, b[k][1])
            if hi > lo:
                out.append((lo, hi))
            k += 1
    return out


def _minus(a: list, b: list) -> list:
    """``a`` without ``b``, both sorted lists of disjoint (start, end)."""
    out, j = [], 0
    for s, e in a:
        while j < len(b) and b[j][1] <= s:
            j += 1
        k, t = j, s
        while k < len(b) and b[k][0] < e:
            if b[k][0] > t:
                out.append((t, b[k][0]))
            t = max(t, b[k][1])
            k += 1
        if t < e:
            out.append((t, e))
    return out


def _length(intervals) -> int:
    return sum(e - s for s, e in intervals)


def split_idle(red: tracereduce.Reduced, control: list,
               execution: list) -> dict:
    """The device's idle nanoseconds in the (aligned) window by
    :data:`PARTS`; they sum to its idle time.  ``engine`` is what the
    ``serve.*`` spans (the window's host spans) cover."""
    left = tracereduce.idle_gaps(red)
    out = {}
    for part, spans in (("runtime", control), ("dispatch", execution),
                        ("engine", red.host)):
        cover = _overlap(left, tracereduce.merged(spans))
        out[part] = _length(cover)
        left = _minus(left, cover)
    out["in_program"] = _length(_overlap(left, tracereduce.merged(
        red.modules)))
    out["no_span"] = _length(left) - out["in_program"]
    return out
