"""Persistent Cluster/Client futures API.

Covers: run_graph ≡ Cluster+Client parity over the full
(server, scheduler, runtime) matrix, warm-pool amortization (the 2nd..Nth
graph on one Cluster beats a cold run_graph per graph), futures lifecycle
(submit/map/gather/release, cross-epoch dependencies, incremental
GraphBuilder chunks), gather-from-worker re-fetch on the process runtime,
zombie-free timeout termination, and the ElasticController process guard.
"""
import multiprocessing as mp
import time

import pytest

from repro.core import benchgraphs, run_graph
from repro.core.client import (Cluster, ClusterClosed, Future,
                               ReleasedKeyError)
from repro.core.graph import GraphBuilder, Task, TaskGraph

SERVERS = ["dask", "rsds"]
SCHEDS = ["ws", "random"]
RUNTIMES = ["thread", "process"]


def _leaf(v):
    return v


def _agg(*vals):
    return sum(vals)


def _sq(x):
    return x * x


def _fn_graph(n_leaves: int = 10) -> TaskGraph:
    tasks = [Task(i, (), fn=_leaf, args=(i * i,)) for i in range(n_leaves)]
    tasks.append(Task(n_leaves, tuple(range(n_leaves)), fn=_agg))
    return TaskGraph(tasks, name="fn-agg")


# ---------------------------------------------------------------------------
# satellite: run_graph ≡ Cluster + Client over the whole existing matrix
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("runtime", RUNTIMES)
@pytest.mark.parametrize("sched", SCHEDS)
@pytest.mark.parametrize("server", SERVERS)
def test_run_graph_equals_cluster_client(server, sched, runtime):
    g = _fn_graph()
    want = {i: i * i for i in range(10)}
    want[10] = sum(want.values())

    legacy = run_graph(g, server=server, scheduler=sched, runtime=runtime,
                       n_workers=3, timeout=60.0)
    assert not legacy.timed_out
    assert legacy.results == want
    assert legacy.n_tasks == g.n_tasks

    with Cluster(server=server, scheduler=sched, runtime=runtime,
                 n_workers=3, timeout=60.0) as c:
        futs = c.client.submit_graph(g)
        res = futs.result(60.0)
    assert res == legacy.results
    assert len(futs) == legacy.n_tasks


def test_run_graph_heft_through_cluster():
    """HEFT precomputes placement; the incremental path must recompute it
    on every epoch (SchedulerBase.on_graph_extended)."""
    g = _fn_graph()
    r = run_graph(g, server="rsds", scheduler="heft", runtime="thread",
                  n_workers=3, timeout=60.0)
    assert not r.timed_out and r.results[10] == sum(i * i
                                                    for i in range(10))
    with Cluster(server="rsds", scheduler="heft", n_workers=3) as c:
        a = c.client.submit_graph(g).result(30.0)
        b = c.client.submit_graph(g).result(30.0)
    assert a == b == r.results


# ---------------------------------------------------------------------------
# acceptance: warm-pool amortization is measurable
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("runtime", RUNTIMES)
def test_warm_cluster_beats_cold_run_graph(runtime):
    """The 2nd..Nth graph on a persistent Cluster pays no worker
    startup/teardown: per-graph wall time strictly below a cold
    run_graph call's (medians over several graphs, 8-worker pool so the
    startup component is not lost in scheduling noise)."""
    n_graphs = 4
    graphs = [benchgraphs.merge(150, seed=i) for i in range(n_graphs)]

    cold = []
    for g in graphs:
        t0 = time.perf_counter()
        r = run_graph(g, server="rsds", runtime=runtime, n_workers=8,
                      simulate_durations=False, timeout=60.0)
        cold.append(time.perf_counter() - t0)
        assert not r.timed_out

    warm = []
    with Cluster(server="rsds", runtime=runtime, n_workers=8,
                 simulate_durations=False, timeout=60.0) as c:
        c.client.submit_graph(benchgraphs.merge(150)).result(60.0)  # warm-up
        for g in graphs:
            t0 = time.perf_counter()
            c.client.submit_graph(g).result(60.0)
            warm.append(time.perf_counter() - t0)

    assert sorted(warm)[n_graphs // 2] < sorted(cold)[n_graphs // 2], \
        (warm, cold)


# ---------------------------------------------------------------------------
# futures lifecycle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("runtime", RUNTIMES)
def test_submit_map_gather_dependencies(runtime):
    with Cluster(server="rsds", runtime=runtime, n_workers=3,
                 timeout=60.0) as c:
        f = c.client.submit(_agg, 2, 3)
        assert f.result(30.0) == 5
        fs = c.client.map(_sq, range(6))
        assert c.client.gather(fs, 30.0) == [0, 1, 4, 9, 16, 25]
        # Future args become dependencies, spliced in place
        g = c.client.submit(_agg, f, 10, fs[3])
        assert g.result(30.0) == 5 + 10 + 9
        assert g.done()


@pytest.mark.parametrize("runtime", RUNTIMES)
def test_release_purges_results(runtime):
    with Cluster(server="rsds", runtime=runtime, n_workers=2,
                 timeout=60.0) as c:
        f = c.client.submit(_sq, 7)
        assert f.result(30.0) == 49
        f.release()
        with pytest.raises(ReleasedKeyError):
            f.result(1.0)
        # the release is processed on the server loop; the value must
        # disappear from the runtime's result store
        deadline = time.perf_counter() + 5.0
        while time.perf_counter() < deadline \
                and f.tid in c.runtime.results:
            time.sleep(0.01)
        assert f.tid not in c.runtime.results
        # releasing a key does not disturb unrelated submissions
        assert c.client.submit(_sq, 8).result(30.0) == 64


def test_duplicate_future_args_execute_once():
    """submit(fn, f, f): the duplicate consumer edge must not make the
    dask-style reactor assign/execute the task twice (and corrupt the
    scheduler's load accounting on a warm pool)."""
    import threading
    calls = []
    gate = threading.Event()

    def slow_leaf():
        gate.wait(5.0)
        return 3

    def mul2(a, b):
        calls.append(1)
        return a * b

    with Cluster(server="dask", runtime="thread", n_workers=2,
                 timeout=60.0) as c:
        f = c.client.submit(slow_leaf)
        g = c.client.submit(mul2, f, f)   # ingested while f is pending
        gate.set()
        assert g.result(30.0) == 9
        assert calls == [1]               # executed exactly once
        # scheduler load bookkeeping balanced out
        deadline = time.perf_counter() + 5.0
        sched = c.reactor.scheduler
        while time.perf_counter() < deadline and any(sched.loads):
            time.sleep(0.01)
        assert not any(sched.loads), sched.loads


@pytest.mark.parametrize("server", SERVERS)
def test_release_before_finish_reclaims_at_completion(server):
    """Dropping a future's hold while its task is still pending must not
    pin the value in runtime.results forever: the reactor reclaims the
    key when it reaches MEMORY."""
    import threading
    gate = threading.Event()

    def slow_val():
        gate.wait(5.0)
        return 123

    with Cluster(server=server, runtime="thread", n_workers=2,
                 timeout=60.0) as c:
        f = c.client.submit(slow_val)
        f.release()                       # before the task even runs
        gate.set()
        deadline = time.perf_counter() + 5.0
        while time.perf_counter() < deadline:
            if c.reactor.done() and f.tid not in c.runtime.results:
                break
            time.sleep(0.01)
        assert f.tid not in c.runtime.results
        with pytest.raises(ReleasedKeyError):
            f.result(1.0)


def test_epoch_depending_on_released_key_fails_cleanly():
    """Submitting work that depends on a released key must fail without
    corrupting the persistent graph/reactor: client-side guards catch it
    synchronously, and a raw epoch reaching the server is quarantined
    (its tid range filled with inert placeholders) so later submissions
    still align with the dense tid space."""
    with Cluster(server="rsds", runtime="thread", n_workers=2,
                 timeout=60.0) as c:
        gb = GraphBuilder("rel")
        gb.add("a", fn=_leaf, args=(5,))
        futs = c.client.submit_update(gb)
        assert futs["a"].result(30.0) == 5
        futs["a"].release()
        # client-side guard: the builder path refuses released deps
        gb.add("b", inputs=("a",), fn=_sq)
        with pytest.raises(ReleasedKeyError):
            c.client.submit_update(gb)
        # server-side quarantine: a raw epoch that slips past the client
        # checks fails its future but leaves the cluster submittable
        with c._lock:
            tid = c._next_tid
            eid = c.runtime.submit_tasks(
                [Task(tid, (futs["a"].tid,), fn=_sq)])
            c._next_tid += 1
        assert c.runtime.wait_epoch(eid, 30.0)
        assert isinstance(c.runtime.epoch(eid).error, ValueError)
        # the failed epoch must not have bricked the persistent state
        assert c.client.submit(_sq, 6).result(30.0) == 36
        assert c.client.submit_graph(_fn_graph()).result(30.0)[10] == \
            sum(i * i for i in range(10))


def _boom(*_):
    raise ValueError("boom")


@pytest.mark.parametrize("server", SERVERS)
def test_raising_task_fails_its_future_and_pool_lives(server):
    """A task that raises on a thread worker fails its epoch with that
    exception at once (not a timeout), and the worker runs the next
    task."""
    with Cluster(server=server, runtime="thread", n_workers=1,
                 timeout=60.0) as c:
        f = c.client.submit(_boom)
        t0 = time.perf_counter()
        with pytest.raises(ValueError, match="boom"):
            f.result(30.0)
        assert time.perf_counter() - t0 < 1.0
        assert c.client.submit(_sq, 6).result(30.0) == 36


def test_dependents_of_a_raising_task_err_unrun():
    ran = []

    def after(x):
        ran.append(x)
        return x

    with Cluster(server="rsds", runtime="thread", n_workers=2,
                 timeout=60.0) as c:
        tasks = [Task(0, (), fn=_boom), Task(1, (0,), fn=after),
                 Task(2, (), fn=_leaf, args=(7,))]
        futs = c.client.submit_graph(TaskGraph(tasks, name="erred"))
        with pytest.raises(ValueError, match="boom"):
            futs.result(30.0)
        # a later epoch that depends on the erred key errs the same way
        g = c.client.submit(after, futs[0])
        with pytest.raises(ValueError, match="boom"):
            g.result(30.0)
        assert ran == []
        assert c.client.submit_graph(_fn_graph()).result(30.0)[10] == \
            sum(i * i for i in range(10))


def test_submit_on_closed_cluster_raises():
    c = Cluster(server="rsds", runtime="thread", n_workers=2)
    c.close()
    with pytest.raises(ClusterClosed):
        c.client.submit(_sq, 2)


def test_graph_futures_indexing():
    g = _fn_graph()
    with Cluster(server="rsds", n_workers=3) as c:
        futs = c.client.submit_graph(g)
        f = futs[10]
        assert isinstance(f, Future)
        assert f.result(30.0) == sum(i * i for i in range(10))
        with pytest.raises(IndexError):
            futs[11]


@pytest.mark.parametrize("runtime", RUNTIMES)
def test_incremental_builder_chunks(runtime):
    """GraphBuilder chunks submitted out of order: forward references
    buffer until their dependencies arrive, and cross-epoch dependencies
    resolve against earlier flushes."""
    with Cluster(server="rsds", runtime=runtime, n_workers=3,
                 timeout=60.0) as c:
        gb = GraphBuilder("inc")
        futs = {}
        # chunk 1: the sink first (forward references) + two leaves
        gb.add("sum", inputs=("a", "b", "c"), fn=_agg)
        gb.add("a", fn=_leaf, args=(1,))
        gb.add("b", fn=_leaf, args=(2,))
        futs.update(c.client.submit_update(gb))
        assert set(futs) == {"a", "b"}       # "sum" still buffered
        assert gb.n_pending == 1
        # chunk 2: the missing leaf unblocks the sink
        gb.add("c", fn=_leaf, args=(4,))
        futs.update(c.client.submit_update(gb))
        assert set(futs) == {"a", "b", "c", "sum"}
        assert futs["sum"].result(30.0) == 7
        # chunk 3: depend on an earlier epoch's key
        gb.add("double", inputs=("sum",), fn=_sq)
        futs.update(c.client.submit_update(gb))
        assert futs["double"].result(30.0) == 49


def test_process_gather_refetches_from_worker_cache():
    """Worker-side result retention: after the server's copy is dropped,
    Future.result round-trips a gather frame and the worker re-sends the
    cached value."""
    with Cluster(server="rsds", runtime="process", n_workers=2,
                 timeout=60.0) as c:
        f = c.client.submit(_sq, 9)
        assert f.result(30.0) == 81
        c.runtime.results.pop(f.tid)         # simulate server-side drop
        assert f.result(30.0) == 81          # re-fetched over the wire


@pytest.mark.parametrize("runtime", RUNTIMES)
def test_epoch_stats_recorded(runtime):
    with Cluster(server="rsds", runtime=runtime, n_workers=2,
                 timeout=60.0) as c:
        g1 = c.client.submit_graph(benchgraphs.merge(
            40, dur_ms=0.0))
        g2 = c.client.submit_graph(benchgraphs.merge(
            40, dur_ms=0.0))
        g1.wait(30.0) and g2.wait(30.0)
        e1, e2 = g1.epoch, g2.epoch
    assert e1.n_tasks == e2.n_tasks == 41
    assert e1.makespan > 0 and e2.makespan > 0
    assert e1.error is None and e2.error is None


# ---------------------------------------------------------------------------
# satellite: timed-out process runs leave no zombie workers
# ---------------------------------------------------------------------------

def test_timeout_terminates_all_worker_processes():
    from repro.core.array_reactor import ArrayReactor
    from repro.core.runtime import ProcessRuntime
    from repro.core.schedulers import make_scheduler

    children_before = set(mp.active_children())
    g = benchgraphs.merge_slow(30, 2.0)      # 30 x 2 s tasks, 2 workers
    reactor = ArrayReactor(g, make_scheduler("rsds_ws"), 2,
                           simulate_codec=False)
    rt = ProcessRuntime(g, reactor, 2, timeout=0.5)
    r = rt.run()
    assert r.timed_out
    for p in rt.procs:
        assert not p.is_alive()
        assert p.exitcode is not None        # reaped, not a zombie
    assert set(mp.active_children()) <= children_before


def test_timeout_through_run_graph_kills_pool():
    children_before = set(mp.active_children())
    g = benchgraphs.merge_slow(30, 2.0)
    r = run_graph(g, server="rsds", runtime="process", n_workers=2,
                  timeout=0.5)
    assert r.timed_out
    assert set(mp.active_children()) <= children_before


# ---------------------------------------------------------------------------
# satellite: ElasticController is thread-runtime only
# ---------------------------------------------------------------------------

def test_elastic_controller_rejects_process_backing():
    from repro.ft.faults import ElasticController

    with Cluster(server="rsds", runtime="process", n_workers=2) as c:
        with pytest.raises(NotImplementedError, match="thread"):
            ElasticController(c)
        with pytest.raises(NotImplementedError):
            ElasticController(c.runtime)
    # thread-backed clusters still work
    with Cluster(server="rsds", runtime="thread", n_workers=2) as c:
        ec = ElasticController(c)
        assert ec.rt is c.runtime
