"""Observability layer: event vocabulary, bus, JSONL log, replay.

Covers the PR-6 tentpole's correctness contract (docs/events.md):

* every recorded event carries the envelope + its type's required
  fields, with globally monotonic ``seq``,
* per task, ``task-dispatched`` precedes ``task-finished`` (and on the
  inproc driver ``task-started`` lands between them),
* ``replay`` over a recorded JSONL log agrees exactly with the
  recording run's ``RunResult.stats`` (tasks_per_worker, n_steals,
  spill/unspill bytes),
* ``events=None`` (the default) publishes nothing and adds zero
  entries anywhere,

parametrized over the inproc, selector and asyncio drivers — one
instrumentation pass in ServerCore must cover all three.
"""
import json
import os

import pytest

from repro.core import benchgraphs, run_graph
from repro.core.client import Cluster
from repro.core.events import (EVENT_TYPES, SCHEMA_VERSION, EventBus,
                               JsonlEventLog, load_jsonl, make_bus,
                               replay)

# (runtime, driver kwargs) triples: inproc threads, selector and
# asyncio process drivers.  Process cases fork so real callables stay
# picklable-free, matching test_server_core.py's convention.
CASES = [
    ("thread", {}),
    ("process", {"driver": "selector", "start_method": "fork"}),
    ("process", {"driver": "asyncio", "start_method": "fork"}),
]
CASE_IDS = ["inproc", "selector", "asyncio"]


def _record(tmp_path, runtime, kw, graph=None, **extra):
    log = os.path.join(str(tmp_path), f"ev-{runtime}.jsonl")
    g = graph if graph is not None else benchgraphs.merge(60)
    r = run_graph(g, server="rsds", runtime=runtime, n_workers=3,
                  simulate_durations=False, events=log, timeout=60.0,
                  **kw, **extra)
    assert not r.timed_out
    return r, load_jsonl(log)


# ---------------------------------------------------------------------------
# stream correctness across drivers
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("runtime,kw", CASES, ids=CASE_IDS)
def test_recorded_stream_is_well_formed(tmp_path, runtime, kw):
    """Envelope + required fields on every event; seq strictly
    increasing; stream-open anchors event zero; epochs open before they
    close."""
    r, evs = _record(tmp_path, runtime, kw)
    assert evs, "recorded log is empty"
    assert evs[0]["type"] == "stream-open"      # add_sink ring replay
    last_seq = -1
    open_eids = set()
    for ev in evs:
        assert ev["v"] == SCHEMA_VERSION
        assert ev["seq"] > last_seq
        last_seq = ev["seq"]
        assert isinstance(ev["t"], float)
        assert ev["type"] in EVENT_TYPES, f"undocumented {ev['type']}"
        for field in EVENT_TYPES[ev["type"]]:
            assert field in ev, f"{ev['type']} missing {field}"
        if ev["type"] == "epoch-open":
            open_eids.add(ev["eid"])
        elif ev["type"] == "epoch-close":
            assert ev["eid"] in open_eids
    # the bus saw at least everything the sink recorded
    assert 0 < len(evs) <= r.stats["n_events"]


@pytest.mark.parametrize("runtime,kw", CASES, ids=CASE_IDS)
def test_dispatched_precedes_finished(tmp_path, runtime, kw):
    """Per task: the (last) dispatch always carries a smaller seq than
    the finish it leads to — the ordering guarantee docs/events.md
    promises consumers."""
    _, evs = _record(tmp_path, runtime, kw)
    last_dispatch: dict = {}
    n_checked = 0
    for ev in evs:
        if ev["type"] == "task-dispatched":
            last_dispatch[ev["tid"]] = ev["seq"]
        elif ev["type"] == "task-finished":
            assert ev["tid"] in last_dispatch, \
                f"task {ev['tid']} finished without a dispatch"
            assert last_dispatch[ev["tid"]] < ev["seq"]
            n_checked += 1
    assert n_checked > 0


def test_inproc_started_between_dispatch_and_finish(tmp_path):
    """The thread workers report task-started; it must land strictly
    inside the dispatch..finish window even though it is published from
    a non-loop thread."""
    _, evs = _record(tmp_path, "thread", {})
    dispatch: dict = {}
    started: dict = {}
    n_checked = 0
    for ev in evs:
        if ev["type"] == "task-dispatched":
            dispatch[ev["tid"]] = ev["seq"]
        elif ev["type"] == "task-started":
            started[ev["tid"]] = ev["seq"]
        elif ev["type"] == "task-finished":
            tid = ev["tid"]
            if tid in started:
                assert dispatch[tid] < started[tid] < ev["seq"]
                n_checked += 1
    assert n_checked > 0


@pytest.mark.parametrize("runtime,kw", CASES, ids=CASE_IDS)
def test_replay_agrees_with_run_stats(tmp_path, runtime, kw):
    """The replay contract: reconstructing a recorded log reproduces
    the run's own counters exactly."""
    r, evs = _record(tmp_path, runtime, kw)
    s = replay(evs)
    assert s["schema"] == SCHEMA_VERSION
    assert s["tasks_per_worker"] == r.stats["tasks_per_worker"]
    assert s["n_finished"] == sum(r.stats["tasks_per_worker"].values())
    assert s["n_steals"] == r.stats["n_steals"]
    assert s["by_type"]["epoch-open"] == s["by_type"]["epoch-close"] == 1
    for e in s["epochs"].values():
        assert e["error"] is None
        assert e["makespan"] is not None and e["makespan"] >= 0
    # every worker that finished work has an occupancy span
    for wid, n in s["tasks_per_worker"].items():
        w = s["workers"][wid]
        assert w["n_finished"] == n
        assert not w["lost"]


def test_replay_reproduces_spill_meters(tmp_path):
    """Memory-pressure run on the process driver: spill/unspill events
    (derived from usage-record deltas) must sum to the run's
    spill_bytes/unspill_bytes meters."""
    elems, leaves, limit = 2048, 12, 40_000
    g = benchgraphs.array_reduction(leaves, elems=elems, fan=4)
    r, evs = _record(tmp_path, "process",
                     {"driver": "selector", "start_method": "fork"},
                     graph=g, memory_limit=limit)
    assert r.stats["spill_bytes"] > 0, "tiny limit did not spill"
    s = replay(evs)
    assert s["spill_bytes"] == r.stats["spill_bytes"]
    assert s["unspill_bytes"] == r.stats["unspill_bytes"]


@pytest.mark.parametrize("runtime,kw", CASES, ids=CASE_IDS)
def test_events_off_publishes_nothing(runtime, kw):
    """The default: no bus exists, the stats counter reads zero, and
    results are untouched."""
    g = benchgraphs.merge(60)
    r = run_graph(g, server="rsds", runtime=runtime, n_workers=3,
                  simulate_durations=False, timeout=60.0, **kw)
    assert not r.timed_out
    assert r.stats["n_events"] == 0


@pytest.mark.parametrize("runtime,kw", CASES, ids=CASE_IDS)
def test_events_off_with_tracing_publishes_nothing(runtime, kw):
    """tracing=True with the event feed off: the workers may stamp
    clocks and piggyback records, but no bus exists, so nothing is
    published anywhere — tracing rides the events knob, it never
    creates an output channel of its own."""
    g = benchgraphs.merge(60)
    r = run_graph(g, server="rsds", runtime=runtime, n_workers=3,
                  simulate_durations=False, timeout=60.0, tracing=True,
                  **kw)
    assert not r.timed_out
    assert r.stats["n_events"] == 0
    assert r.stats["n_timing"] == 61     # records folded, not published


def test_serving_engine_with_tracing_off_publishes_nothing():
    """ServingEngine's default: its Cluster has no bus and takes no timing
    records, and the engine marks no span."""
    import jax
    import numpy as np

    from repro import configs
    from repro.models import model as model_lib
    from repro.serve.engine import ServingEngine
    cfg = configs.get_config("llama3.2-1b", smoke=True)
    params = model_lib.init_params(jax.random.PRNGKey(1), cfg)
    eng = ServingEngine(cfg, params, max_batch=2, max_len=64)
    eng.start()
    req = eng.submit(np.arange(1, 9), max_new_tokens=3)
    assert req.done.wait(120) and req.error is None
    eng.stop()
    assert eng.events is None and not eng.tracing
    assert eng.observe()["n_events"] == 0
    assert eng._cluster.runtime.n_timing == 0
    with pytest.raises(RuntimeError):
        eng.trace_analysis()


def test_tracing_off_publishes_no_timing(tmp_path):
    """events= without tracing=: the recorded stream carries no
    task-timing events and no timing counters move — the tracing
    instrumentation is zero-cost until explicitly enabled."""
    r, evs = _record(tmp_path, "thread", {})
    assert r.stats["n_timing"] == 0
    assert not any(e["type"] == "task-timing" for e in evs)
    assert not any("deps" in e for e in evs
                   if e["type"] == "task-queued")


def test_cluster_live_surface(tmp_path):
    """events=True on a persistent Cluster: the bus is reachable while
    the pool runs, observe() snapshots agree with the ledger, and the
    ring stays readable after close."""
    g = benchgraphs.merge(40)
    with Cluster(server="rsds", runtime="thread", n_workers=3,
                 simulate_durations=False, events=True,
                 name="ev-live") as c:
        assert c.events is not None
        c.client.submit_graph(g).result(30)
        snap = c.observe()
        assert snap["n_finished"] == g.n_tasks
        assert sum(snap["tasks_per_worker"].values()) == g.n_tasks
        assert snap["n_events"] > 0
        assert snap["event_counts"].get("task-finished") == g.n_tasks
        assert snap["last_events"], "tail is empty with events on"
        seq0 = snap["last_events"][-1]["seq"]
    # closed bus: ring still readable, counters still coherent
    bus = c.events
    assert bus.n_published > 0
    assert bus.tail(5)[-1]["seq"] >= seq0
    assert bus.counts["task-finished"] == g.n_tasks


# ---------------------------------------------------------------------------
# bus / sink / make_bus units
# ---------------------------------------------------------------------------

def test_bus_ring_is_bounded():
    bus = EventBus(capacity=8)
    for i in range(20):
        bus.publish("release", n=i)
    assert bus.n_published == 21          # + stream-open
    assert bus.n_dropped == 13
    tail = bus.tail(100)
    assert len(tail) == 8
    assert [e["seq"] for e in tail] == list(range(13, 21))
    assert bus.since(18) == tail[-2:]


def test_add_sink_replays_ring():
    """A sink attached after construction still sees the stream-open
    anchor (the make_bus path) — recorded logs are complete from event
    zero."""
    bus = EventBus()
    bus.publish("release", n=1)
    seen: list = []
    bus.add_sink(seen.append)
    bus.publish("release", n=2)
    assert [e["type"] for e in seen] == ["stream-open", "release",
                                         "release"]
    assert [e["seq"] for e in seen] == [0, 1, 2]


def test_broken_sink_is_contained():
    bus = EventBus()
    bus.add_sink(lambda ev: 1 / 0)
    ev = bus.publish("release", n=1)     # must not raise
    assert ev["n"] == 1


def test_conformance_sink_is_crash_contained():
    """The online protocol checker is a sink like any other: a checker
    that blows up internally must never take the publish path down —
    it counts the error and keeps consuming the stream."""
    from repro.analysis.trace import ConformanceSink

    bus = EventBus()
    sink = ConformanceSink()
    bus.add_sink(sink)

    def boom(ev):
        raise RuntimeError("checker bug")
    sink._checker.feed = boom            # simulate an internal crash
    ev = bus.publish("release", n=1)     # must not raise
    assert ev["n"] == 1
    assert sink.n_internal_errors == 1   # counted, not swallowed
    # and even an unconfigured double-failure path stays contained:
    # the bus's own try/except is the second belt
    bus.add_sink(lambda ev: 1 / 0)
    bus.publish("release", n=2)


def test_conformance_sink_windowed_on_ring_overflow():
    """A sink attached after the ring dropped events sees a seq gap;
    the checker must downgrade to windowed checking (no false
    positives from the missing history) instead of flagging the
    replayed tail."""
    from repro.analysis.trace import ConformanceSink

    bus = EventBus(capacity=4)
    for tid in range(8):                 # dispatch history falls off
        bus.publish("task-queued", tid=tid, wid=0)
        bus.publish("task-dispatched", tid=tid, wid=0)
    assert bus.n_dropped > 0
    sink = ConformanceSink()
    bus.add_sink(sink)                   # ring replay starts mid-stream
    for tid in range(8):                 # finishes whose dispatches the
        bus.publish("task-finished", tid=tid, wid=0)   # sink never saw
    assert not sink.strict               # gap detected -> windowed
    assert sink.n_gaps >= 1
    assert sink.findings == []           # no false positives
    assert sink.n_internal_errors == 0


def test_make_bus_normalization(tmp_path):
    assert make_bus(None) is None
    assert make_bus(False) is None
    bus = make_bus(True)
    assert isinstance(bus, EventBus) and not bus._sinks
    shared = EventBus()
    assert make_bus(shared) is shared
    log_path = os.path.join(str(tmp_path), "x.jsonl")
    recorded = make_bus(log_path)
    recorded.publish("release", n=1)
    recorded.close()
    assert [e["type"] for e in load_jsonl(log_path)] == ["stream-open",
                                                         "release"]
    with pytest.raises(TypeError):
        make_bus(3.14)


def test_jsonl_rotation_roundtrip(tmp_path):
    """Rotation keeps the newest `keep+1` files and load_jsonl stitches
    the chain back oldest-first; a truncated line is skipped."""
    path = os.path.join(str(tmp_path), "rot.jsonl")
    log = JsonlEventLog(path, max_bytes=512, keep=2, flush_every=1)
    bus = EventBus()
    bus.add_sink(log)
    for i in range(200):
        bus.publish("release", n=i)
    bus.close()
    assert os.path.exists(f"{path}.1")   # rotated at least once
    with open(path, "a", encoding="utf-8") as fh:
        fh.write('{"v": 1, "seq": 99')   # crash mid-write
    evs = load_jsonl(path)
    seqs = [e["seq"] for e in evs]
    assert seqs == sorted(seqs)
    assert seqs[-1] == 200               # newest survives
    assert len(evs) <= 201               # oldest may have rotated away
    assert all(e["type"] in ("stream-open", "release") for e in evs)


def test_replay_synthetic_occupancy():
    """Hand-built stream: occupancy spans, epoch makespans, pressure
    and loss flags all reconstruct."""
    evs = [
        {"v": 1, "seq": 0, "t": 0.0, "type": "stream-open",
         "wall": 1000.0, "pid": 1},
        {"v": 1, "seq": 1, "t": 0.0, "type": "epoch-open", "eid": 0,
         "n_tasks": 2, "lo": 0, "hi": 2},
        {"v": 1, "seq": 2, "t": 0.1, "type": "task-dispatched",
         "tid": 0, "wid": 0},
        {"v": 1, "seq": 3, "t": 0.2, "type": "task-dispatched",
         "tid": 1, "wid": 1},
        {"v": 1, "seq": 4, "t": 0.6, "type": "task-finished",
         "tid": 0, "wid": 0},
        {"v": 1, "seq": 5, "t": 0.9, "type": "worker-pressure",
         "wid": 1, "pressured": True, "mem_bytes": 10},
        {"v": 1, "seq": 6, "t": 1.0, "type": "task-finished",
         "tid": 1, "wid": 1},
        {"v": 1, "seq": 7, "t": 1.0, "type": "epoch-close", "eid": 0,
         "error": None},
    ]
    s = replay(evs)
    assert s["n_events"] == 8
    assert s["wall_s"] == pytest.approx(1.0)
    assert s["wall_anchor"] == (1000.0, 0.0)
    assert s["tasks_per_worker"] == {0: 1, 1: 1}
    assert s["workers"][0]["busy_s"] == pytest.approx(0.5)
    assert s["workers"][0]["occupancy"] == pytest.approx(0.5)
    assert s["workers"][1]["busy_s"] == pytest.approx(0.8)
    assert s["workers"][1]["pressured"] and not s["workers"][0]["pressured"]
    assert s["epochs"][0]["makespan"] == pytest.approx(1.0)
    assert s["task_stream"][1] == [(1, 0.2, 1.0)]


def test_event_log_is_valid_jsonl(tmp_path):
    """Each recorded line parses standalone — the contract external
    ingestors (the ROADMAP scale harness) rely on."""
    _, _ = _record(tmp_path, "thread", {})
    path = os.path.join(str(tmp_path), "ev-thread.jsonl")
    with open(path, encoding="utf-8") as fh:
        for line in fh:
            ev = json.loads(line)
            assert {"v", "seq", "t", "type"} <= set(ev)
