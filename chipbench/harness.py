"""One run of one cell through the program's serving path.

In one process: the compile cache is placed, the weights are made on the
device from the seed, the program's ``ServingEngine`` is built at the
cell's sizes and warmed on exactly the programs its traffic will use, the
mix's loop of clients (closed, or open at a fixed rate) drives it through
a lead-in and then the measured window, and the output check runs once the
window has closed.  With ``trace`` a profiler trace of a steady part of
the window gives the per-layer metrics.

Metrics are found by name: ``metrics/<name>.py`` holds a ``read(m)`` that
takes a :class:`Measured` and returns a number, or None where it finds
nothing to read.  Besides the trace, a reader gets the engine's counters
(every ``n_...`` attribute, over the window and over the traced part) and,
in traced runs, the decode program's op names by instruction, so that
``scopes.ns_under`` times any named scope: a new metric of a new scope or
counter is a new file.
"""
from __future__ import annotations

import dataclasses
import gc
import shutil
import sys
import tempfile
import threading
import time

import numpy as np

import check
import loadgen
import scopes
import spec
import tracereduce

POLL_S = 0.0005
TRACE_S = 4.0              # the traced part of the window, at most
LEAD_IN_LIMIT_S = 300.0


@dataclasses.dataclass
class Measured:
    """What a metric reader may read."""
    conf: dict
    sizes: dict
    peaks: dict
    window_s: float = 0.0
    generated: int = 0                 # tokens served in the window
    steps: int = 0                     # batched decode steps in the window
    completed: list = dataclasses.field(default_factory=list)
    # (seconds from when it was due to be sent to its finish, tokens
    # served) of each request finished in the window
    trace: tracereduce.Reduced | None = None
    trace_generated: int = 0
    trace_steps: int = 0
    decoded_ctx: list = dataclasses.field(default_factory=list)
    # positions attended by each token served in the traced window
    prefill_lens: list = dataclasses.field(default_factory=list)
    # real tokens of each prefill that ran in the traced window
    counters: dict = dataclasses.field(default_factory=dict)
    trace_counters: dict = dataclasses.field(default_factory=dict)
    # the change of each engine counter (engine_counters) over the window
    # and over the traced part
    op_names: dict = dataclasses.field(default_factory=dict)
    # traced runs only: program -> {HLO instruction: op_name} of the
    # compiled program that ran ("decode_fn")


class Clients:
    """A closed loop of callers on one thread: each sends its next request
    as soon as its last one is done."""

    def __init__(self, engine, loop: loadgen.ClosedLoop):
        self.engine, self.loop = engine, loop
        self.inflight = [None] * loop.clients
        self.all: list = []
        self.finished: list = []
        self.due: dict = {}        # request id -> when it was due to be sent
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._run, daemon=True)

    def _send(self, c: int) -> None:
        prompt, n_out = self.loop.next(c)
        req = self.engine.submit(prompt, max_new_tokens=n_out)
        self.inflight[c] = req
        self.due[req.rid] = req.submit_t
        self.all.append(req)

    def _run(self) -> None:
        for c in range(self.loop.clients):
            self._send(c)
        while not self._stop.is_set():
            for c, req in enumerate(self.inflight):
                if req.done.is_set():
                    self.finished.append(req)
                    self._send(c)
            time.sleep(POLL_S)

    def start(self) -> None:
        self._thread.start()

    def stop(self) -> None:
        self._stop.set()
        self._thread.join(timeout=30)
        if self._thread.is_alive():
            raise RuntimeError("client thread did not stop")

    def served(self) -> dict:
        """Tokens served so far, by request id."""
        return {r.rid: len(r.out_tokens) for r in list(self.all)}


class Arrivals(Clients):
    """An open loop on one thread: each request is sent when it is due,
    ``loop.gap`` after the one before, however many are unanswered; those
    that find every slot busy wait in the engine's inbox."""

    def _run(self) -> None:
        j, due, pending = 0, time.perf_counter(), []
        while not self._stop.is_set():
            while due <= time.perf_counter():
                prompt, n_out = self.loop.request(j)
                req = self.engine.submit(prompt, max_new_tokens=n_out)
                self.due[req.rid] = due
                self.all.append(req)
                pending.append(req)
                due += self.loop.gap(j)
                j += 1
            still = []
            for req in pending:
                (self.finished if req.done.is_set() else still).append(req)
            pending = still
            time.sleep(max(0.0, min(POLL_S, due - time.perf_counter())))


#: the requests and who sends them, by the mix's ``loop``
LOOPS = {"closed": (loadgen.ClosedLoop, Clients),
         "open": (loadgen.OpenLoop, Arrivals)}


def waiting(reqs: list, t: float) -> int:
    """Requests submitted by ``t`` and not yet taken into a slot then."""
    return sum(1 for r in reqs
               if r.submit_t <= t and not 0.0 < r.admit_t <= t)


def engine_counters(engine) -> dict[str, int]:
    """Every attribute of the engine whose name starts with ``n_`` and that
    holds a whole number (a 0-d integer array is read with ``int()``)."""
    out = {}
    for k, v in vars(engine).items():
        if not k.startswith("n_") or isinstance(v, bool):
            continue
        if isinstance(v, int):
            out[k] = v
        elif getattr(v, "shape", None) == () and np.issubdtype(
                getattr(v, "dtype", np.float32), np.integer):
            out[k] = int(v)
    return out


def deltas(c0: dict, c1: dict) -> dict[str, int]:
    return {k: c1[k] - c0[k] for k in c1 if k in c0}


def decode_call(engine) -> tuple:
    """The engine's jitted decode function and the shapes of its arguments
    (params, tokens, cache, positions).  Shapes with no placement lower to
    the very module the engine's calls on one device did, so its compile
    is found in the cache."""
    import jax

    def shape(a):
        return jax.ShapeDtypeStruct(a.shape, a.dtype)
    b = engine.max_batch
    return engine._decode, (jax.tree.map(shape, engine.params),
                            jax.ShapeDtypeStruct((b, 1), np.int32),
                            jax.tree.map(shape, engine.cache),
                            jax.ShapeDtypeStruct((b,), np.int32))


def _metric_reader(name: str):
    return spec.load_module(spec.HERE / "metrics" / f"{name}.py").read


def cell_metrics(bench: dict, cell: str, trace: bool) -> list[dict]:
    """The metric entries of BENCHMARK.json that this cell reports."""
    group = bench["per_layer"] if trace else bench["end_to_end"]
    return [m for m in group
            if cell in m.get("workloads", [cell]) and m["name"] != "setup_s"]


def warm_lengths(cfg, loop: loadgen.ClosedLoop, max_len: int) -> list[int]:
    """One prompt length for each prefill program the traffic can reach:
    every length where the prefill runs at exact length (recurrent state),
    else one per bucket of the engine."""
    from repro.serve import engine as engine_lib
    lengths = loop.distinct_prompt_lengths()
    if cfg.mamba is not None or cfg.xlstm is not None:
        return lengths
    seen = {}
    for n in lengths:
        seen.setdefault(min(engine_lib._bucket(n - 1), max_len), n)
    return sorted(seen.values())


def _device_peak_bytes(dev) -> int:
    stats = dev.memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", 0))


def _memory_note(dev, when: str) -> None:
    stats = dev.memory_stats() or {}
    keep = ("bytes_in_use", "peak_bytes_in_use", "bytes_reserved",
            "bytes_limit", "largest_free_block_bytes")
    print(f"device memory {when}: "
          + ", ".join(f"{k} {stats[k]}" for k in keep if k in stats),
          file=sys.stderr, flush=True)


def within(reading: dict, limit) -> bool:
    """The output check's verdict on one reading of ``check.compare``: a
    gap that is not a number is never within the limit."""
    return limit is not None and bool(reading["max_logit_gap"] <= limit)


def run(cell: spec.Cell, bench: dict, seed: int, seconds: float,
        trace: bool, t_start: float, device, peaks: dict,
        control: bool = False) -> tuple[dict, list]:
    """One run; returns the result line and the lines for standard error.
    ``control`` also reads the float8 control on the checked requests
    (``tests/calibrate.py``; the benchmark's own runs never do)."""
    import jax
    from repro.launch import compile_cache
    from repro.models import model as model_lib
    from repro.serve.engine import ServingEngine

    t_run = time.perf_counter()
    compile_cache.enable()
    conf, sizes, mix = cell.config, cell.sizes, cell.traffic
    max_batch, max_len = sizes["max_batch"], sizes["max_len"]
    cfg = spec.model_config(conf)

    weights = spec.weights_on_device(conf, seed)
    want = jax.tree.map(lambda a: (a.shape, a.dtype),
                        model_lib.abstract_params(cfg))
    got = jax.tree.map(lambda a: (a.shape, a.dtype), weights)
    if got != want:
        raise ValueError("the weights do not match the program's layout")
    _memory_note(device, "with the weights")
    t_weights = time.perf_counter()

    engine = ServingEngine(cfg, weights, max_batch=max_batch,
                           max_len=max_len)
    engine.start()
    requests, senders = LOOPS[mix["loop"]]
    loop = requests(mix, conf["vocab_size"], max_len, max_batch, seed)
    # warm every program the window will run, through the engine itself
    warm = [engine.submit(loop.prompt(i, n, stream=3), max_new_tokens=2)
            for i, n in enumerate(warm_lengths(cfg, loop, max_len))]
    for req in warm:
        req.done.wait(timeout=1200)
        if req.error is not None or not req.done.is_set():
            raise RuntimeError(f"warm-up failed: {req.error}")
    _memory_note(device, "after the warm-up")
    t_warm = time.perf_counter()

    clients = senders(engine, loop)
    clients.start()
    lead, t_lead = mix["lead_in_turns"] * loop.clients, time.perf_counter()
    while len(clients.finished) < lead and engine.error is None:
        if time.perf_counter() - t_lead > LEAD_IN_LIMIT_S:
            raise RuntimeError("the lead-in did not finish")
        time.sleep(0.01)

    m = Measured(conf=conf, sizes=sizes, peaks=peaks)
    cc0 = compile_cache.stats()
    c0 = engine_counters(engine)
    t0 = time.perf_counter()
    setup_s = t0 - t_start
    traced = _traced(m, engine, clients, seconds) if trace else None
    time.sleep(max(0.0, t0 + seconds - time.perf_counter()))
    t1 = time.perf_counter()
    c1 = engine_counters(engine)
    cc1 = compile_cache.stats()
    clients.stop()
    engine.stop()
    engine_error = engine.error

    m.window_s, m.counters = t1 - t0, deltas(c0, c1)
    m.generated = m.counters["n_generated"]
    m.steps = m.counters["n_decode_steps"]
    done = [r for r in clients.all if r.done.is_set() and r.error is None
            and t0 <= r.finish_t < t1]
    failed = [r for r in clients.all if r.error is not None]
    m.completed = [(r.finish_t - clients.due[r.rid], len(r.out_tokens))
                   for r in done]
    due = [r for r in clients.all if t0 <= clients.due[r.rid] < t1]
    late_ms = max(((r.submit_t - clients.due[r.rid]) * 1e3 for r in due),
                  default=0.0)
    load = {"due_in_window": len(due),
            "waiting_at_open": waiting(clients.all, t0),
            "waiting_at_close": waiting(clients.all, t1),
            "late_ms_max": late_ms}
    finished = [(r.prompt, list(r.out_tokens)) for r in done]
    decode = decode_call(engine) if trace else None
    peak_bytes = _device_peak_bytes(device)
    _memory_note(device, "when the window closed")
    # free the program's device state: everything but the weights, which
    # are the benchmark's own (the engine holds every admission's batch-1
    # cache in its task graph; see PERF.md)
    del engine, clients, done, due
    gc.collect()
    keep = {id(a) for a in jax.tree.leaves(weights)}
    for a in jax.live_arrays():
        if id(a) not in keep:
            a.delete()
    if decode is not None:
        # the compile cache hands back the very program that ran
        fn, args = decode
        misses = compile_cache.stats()["misses"]
        m.op_names = {"decode_fn": scopes.op_names(
            fn.lower(*args).compile().as_text())}
        misses = compile_cache.stats()["misses"] - misses
        del decode, fn, args
    if traced is not None:
        try:
            m.trace = tracereduce.reduce(tracereduce.find_xplane(traced))
        finally:
            shutil.rmtree(traced, ignore_errors=True)

    # the output check, on the device the program has let go of
    ck = sizes["check"]
    seqs = check.choose(finished, seed, ck["requests"])
    got = check.compare(weights, conf, seqs, max_len, ck["batch"])
    limit = sizes["limits"]["max_logit_gap"]
    readings = {"program": got}
    if control:
        ctl = check.compare(weights, conf, seqs, max_len, ck["batch"],
                            control=True)
        # the control judged by the very test the program is judged by
        readings["control"] = dict(ctl, correct=within(ctl, limit))
    compared = {"max_logit_gap": {"value": got["max_logit_gap"],
                                  "limit": limit}}
    engine_ok = engine_error is None and not failed
    correct = engine_ok and bool(seqs) and within(got, limit)

    metrics = {}
    if not trace:
        metrics["setup_s"] = {"value": setup_s, "unit": "s"}
    for entry in cell_metrics(bench, cell.name, trace):
        v = _metric_reader(entry["name"])(m)
        if v is not None:
            metrics[entry["name"]] = {"value": v, "unit": entry["unit"]}

    dev = {"platform": device.platform, "kind": device.device_kind,
           "count": len(jax.devices()), "memory_peak_bytes": peak_bytes}
    result = {"correct": bool(correct),
              "attempted": len(m.completed) + len(failed),
              "failed": len(failed), "metrics": metrics, "device": dev}
    if m.trace is not None:
        dev["busy_s"] = tracereduce.busy_ns(m.trace) * 1e-9
        dev["window_s"] = m.trace.window_s
        result["breakdown"] = tracereduce.breakdown(m.trace)
    result["load"] = load
    if control:
        result["readings"] = readings
    result["compared"] = compared
    loaded = (cc1["hits"] + cc1["misses"]) - (cc0["hits"] + cc0["misses"])
    notes = [
        f"window {m.window_s:.3f} s: {len(m.completed)} requests finished, "
        f"{m.generated} tokens, {m.steps} decode steps; "
        f"set-up {setup_s:.3f} s: {t_run - t_start:.3f} to the harness, "
        f"{t_weights - t_run:.3f} weights, {t_warm - t_weights:.3f} engine "
        f"and warm-up, {t0 - t_warm:.3f} lead-in",
        f"programs loaded or compiled inside the window: {loaded}",
        f"{load['due_in_window']} requests due in the window; waiting for "
        f"a slot: {load['waiting_at_open']} when it opened, "
        f"{load['waiting_at_close']} when it closed; those due in it were "
        f"sent late by at most {late_ms:.3f} ms",
        f"engine error: {engine_error!r}; failed requests: {len(failed)}",
        f"checked {got['requests']} requests, {got['tokens']} served "
        f"tokens; the reference's first choice differs at a share of "
        f"{got['mismatch_share']:.4f}",
    ]
    if m.trace is not None:
        names = m.op_names["decode_fn"]
        named = {k: v for k, v in names.items() if v}
        notes.append(
            f"decode_fn op names: {len(names)} instructions, {len(named)} "
            f"with an op name, compiled anew for them: {misses}; share of "
            f"its leaf device time on them: "
            f"{scopes.named_share(m.trace, names, 'decode_fn')!r}, on those "
            f"with an op name: "
            f"{scopes.named_share(m.trace, named, 'decode_fn')!r}")
    notes += [f"{k} {v['value']!r} limit {v['limit']!r}"
              for k, v in compared.items()]
    return result, notes


def _traced(m: Measured, engine, clients: Clients, seconds: float) -> str:
    """Trace the middle of the window and read its counters; returns the
    trace's directory, which the caller reduces and removes."""
    import jax
    trace_s = min(TRACE_S, seconds / 3)
    time.sleep((seconds - trace_s) / 2)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    log_dir = tempfile.mkdtemp(prefix="chipbench-trace-")
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation(tracereduce.WINDOW):
            served0 = clients.served()
            c0 = engine_counters(engine)
            time.sleep(trace_s)
            c1 = engine_counters(engine)
            served1 = clients.served()
    finally:
        jax.profiler.stop_trace()
    m.trace_counters = deltas(c0, c1)
    m.trace_generated = m.trace_counters["n_generated"]
    m.trace_steps = m.trace_counters["n_decode_steps"]
    prompts = {r.rid: len(r.prompt) for r in list(clients.all)}
    for rid, n1 in served1.items():
        n0 = served0.get(rid, 0)
        s = prompts[rid]
        # out[j] was served at position s - 1 + j, attending s + j positions
        m.decoded_ctx.extend(s + j for j in range(n0, n1))
        if n0 == 0 and n1 > 0:
            m.prefill_lens.append(s - 1)
    return log_dir
