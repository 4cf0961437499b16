"""Batched serving engine: continuous batching over the model's
prefill/decode paths.

Requests enter a queue; the engine admits them into free KV-cache slots
(prompt prefill, padded to bucket sizes to bound recompilation), then runs
one batched decode step per iteration for all active slots.  Slots free as
requests finish, new requests are admitted immediately — vLLM-style
continuous batching on top of this framework's cache layout.

The compute itself rides the persistent Cluster/Client futures API: the
engine owns one warm single-executor :class:`repro.core.client.Cluster`
and submits every prefill and batched decode step to it, so back-to-back
steps (and back-to-back requests) reuse the warm pool — the same
long-lived-server shape the paper's RSDS exposes to Dask clients.  The
pool is byte-bounded (``memory_limit``) like every other Cluster in the
repo, and with ``events=`` the engine publishes per-request
``request-enter``/``request-admit``/``request-exit`` events — keyed by
a caller-supplied ``tenant`` — into the same structured feed the
runtime's control-plane events ride (:mod:`repro.core.events`), so a
serving deployment's request streams are visible per tenant next to the
task stream serving them.

With ``tracing=True`` the engine's ``Cluster`` traces every prefill and
decode task (:mod:`repro.core.tracing`, :meth:`ServingEngine.trace_analysis`)
and the loop marks its phases with ``serve.*`` profiler spans, so a
``jax.profiler`` trace shows what the host was doing while the device
waited (``docs/tracing.md``, "Serving spans").  Every request carries
``perf_counter`` stamps of its admission and of each token it is served.
"""
from __future__ import annotations

import contextlib
import dataclasses
import queue
import threading
import time
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.client import Cluster
from repro.models import model as model_lib
from repro.models.config import ModelConfig


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray            # (S,) int32
    max_new_tokens: int = 16
    eos_id: int = -1              # -1: run to max_new_tokens
    out_tokens: list = dataclasses.field(default_factory=list)
    done: threading.Event = dataclasses.field(
        default_factory=threading.Event)
    submit_t: float = 0.0
    finish_t: float = 0.0
    admit_t: float = 0.0          # taken into a slot
    token_t: list = dataclasses.field(default_factory=list)  # per token
    tenant: str = "default"       # event-stream key (multi-tenant views)
    error: BaseException | None = None   # set (with done) if serving failed


def _bucket(n: int, buckets=(16, 32, 64, 128, 256, 512, 1024)) -> int:
    for b in buckets:
        if n <= b:
            return b
    return ((n + 1023) // 1024) * 1024


#: default byte bound on the serving pool's object store.  Engine
#: results are transient (every future is released after one read), so
#: a modest bound keeps a long-lived engine's footprint flat without
#: ever spilling in practice.
DEFAULT_MEMORY_LIMIT = 256 * 2**20

_NO_SPAN = contextlib.nullcontext()


class ServingEngine:
    def __init__(self, cfg: ModelConfig, params: Any, *, max_batch: int = 8,
                 max_len: int = 256,
                 memory_limit: int | None = DEFAULT_MEMORY_LIMIT,
                 events=None, tracing: bool = False):
        assert not cfg.vision_dim, "engine example supports pure-LM archs"
        self.cfg = cfg
        self.params = params
        self.max_batch = max_batch
        self.max_len = max_len
        self.cache = model_lib.init_cache(cfg, max_batch, max_len)
        self.pos = np.zeros(max_batch, dtype=np.int32)    # next position
        self._next_in = np.zeros(max_batch, dtype=np.int32)
        self.active: list[Request | None] = [None] * max_batch
        self.inbox: queue.Queue = queue.Queue()
        self.n_decode_steps = 0
        self.n_generated = 0
        self._stop = threading.Event()
        self._rid = 0
        self.error: BaseException | None = None   # why the loop stopped
        self.tracing = tracing

        def prefill_fn(params, tokens, cache):
            return model_lib.prefill(params, cfg, tokens, cache)

        def decode_fn(params, tokens, cache, pos):
            logits, cache = model_lib.decode_step(params, cfg, tokens,
                                                  cache, pos)
            return jnp.argmax(logits[:, 0], axis=-1), cache

        self._prefill = jax.jit(prefill_fn)
        self._decode = jax.jit(decode_fn, donate_argnums=(2,))
        # warm single-executor pool: every prefill/decode is a client
        # submission, reused across steps and requests.  memory_limit
        # bounds its store like every other Cluster (ROADMAP PR-5
        # follow-up); events= threads the request stream into the same
        # observability feed the runtime's control plane publishes to;
        # tracing= needs a bus for its task spans: the caller's or a fresh
        # one
        trace_kw = ({"events": events or True, "tracing": True} if tracing
                    else {"events": events})
        self._cluster = Cluster(server="rsds", scheduler="ws",
                                n_workers=1, runtime="thread",
                                name="serving", memory_limit=memory_limit,
                                **trace_kw)
        self._thread = threading.Thread(target=self._loop, daemon=True)

    @property
    def events(self):
        """The engine's event bus (None unless built with ``events=``)."""
        return self._cluster.events

    def observe(self) -> dict:
        """Live snapshot of the pool serving this engine (see
        :meth:`repro.core.server.ServerCore.observe`)."""
        return self._cluster.observe()

    def trace_analysis(self):
        """The per-task spans of every prefill and decode task so far (a
        :class:`repro.core.tracing.TraceAnalysis`; needs ``tracing=True``)."""
        return self._cluster.trace_analysis()

    def _span(self, name: str, **args):
        """A profiler span named ``name`` around one phase of the loop,
        with ``args`` as its arguments; nothing when tracing is off."""
        if not self.tracing:
            return _NO_SPAN
        return jax.profiler.TraceAnnotation(name, **args)

    def _call(self, fn, *args):
        """Run one compute on the warm pool and free its key."""
        fut = self._cluster.client.submit(fn, *args)
        out = fut.result(timeout=300.0)
        fut.release()
        return out

    # ------------------------------------------------------------------
    def start(self) -> None:
        self._thread.start()

    def stop(self) -> None:
        self._stop.set()
        self._thread.join(timeout=10)
        self._cluster.close()

    def submit(self, prompt: np.ndarray, max_new_tokens: int = 16,
               eos_id: int = -1, tenant: str = "default") -> Request:
        self._rid += 1
        req = Request(self._rid, np.asarray(prompt, np.int32),
                      max_new_tokens, eos_id,
                      submit_t=time.perf_counter(), tenant=tenant)
        ev = self._cluster.events
        if ev is not None:
            ev.publish("request-enter", rid=req.rid, tenant=tenant)
        self.inbox.put(req)
        if self.error is not None:
            self._fail_waiting(self.error)
        return req

    def _fail(self, req: Request, error: BaseException) -> None:
        req.error = error
        req.finish_t = time.perf_counter()
        req.done.set()

    def _fail_waiting(self, error: BaseException) -> None:
        while True:
            try:
                self._fail(self.inbox.get_nowait(), error)
            except queue.Empty:
                return

    # ------------------------------------------------------------------
    def _admit(self) -> None:
        for slot in range(self.max_batch):
            if self.active[slot] is not None:
                continue
            try:
                req = self.inbox.get_nowait()
            except queue.Empty:
                return
            # the slot is taken before the prefill, so a failing prefill
            # fails this request with the rest
            self.active[slot] = req
            req.admit_t = time.perf_counter()
            # prefill prompt[:-1]; the last prompt token goes through the
            # normal decode path, yielding the first generated token with a
            # correctly positioned cache write.
            s = len(req.prompt)
            recurrent = (self.cfg.mamba is not None
                         or self.cfg.xlstm is not None)
            # recurrent state must not see padding; attention caches
            # mask by length so bucketed padding is safe
            bucket = (0 if s <= 1 else s - 1 if recurrent
                      else min(_bucket(s - 1), self.max_len))
            with self._span("serve.admit", rid=req.rid, slot=slot,
                            bucket=bucket):
                if s > 1:
                    toks = np.zeros((1, bucket), np.int32)
                    toks[0, :s - 1] = req.prompt[:-1]  # right-pad
                    with self._span("serve.init_cache"):
                        one_cache = model_lib.init_cache(self.cfg, 1,
                                                         self.max_len)
                    with self._span("serve.prefill"):
                        _, one_cache = self._call(self._prefill, self.params,
                                                  jnp.asarray(toks),
                                                  one_cache)
                    with self._span("serve.slot_copy"):
                        self.cache = jax.tree.map(
                            lambda g, p: g.at[:, slot].set(p[:, 0])
                            if hasattr(g, "at") else g, self.cache,
                            one_cache)
                self.pos[slot] = s - 1
                self._next_in[slot] = int(req.prompt[-1])
                ev = self._cluster.events
                if ev is not None:
                    ev.publish("request-admit", rid=req.rid,
                               tenant=req.tenant, slot=slot)

    def _loop(self) -> None:
        try:
            self._serve()
        except Exception as exc:
            # a failed prefill or decode must not leave callers waiting:
            # every active and queued request fails with the error
            self.error = exc
            for i, req in enumerate(self.active):
                if req is not None:
                    self._fail(req, exc)
                    self.active[i] = None
            self._fail_waiting(exc)

    def _serve(self) -> None:
        step = 0
        while not self._stop.is_set():
            with (jax.profiler.StepTraceAnnotation("serve.step",
                                                   step_num=step)
                  if self.tracing else _NO_SPAN):
                self._iterate()
            step += 1

    def _iterate(self) -> None:
        """One pass of the loop: admit what the free slots take, then one
        batched decode step over the live slots (a short sleep if none)."""
        self._admit()
        live = [i for i, r in enumerate(self.active) if r is not None]
        if not live:
            with self._span("serve.idle"):
                time.sleep(0.002)
            return
        tokens = np.zeros((self.max_batch, 1), np.int32)
        for i in live:
            tokens[i, 0] = self._next_in[i]
        with self._span("serve.decode"):
            nxt, self.cache = self._call(
                self._decode, self.params, jnp.asarray(tokens), self.cache,
                jnp.asarray(self.pos))
        with self._span("serve.sync"):
            nxt = np.asarray(nxt)
        t_token = time.perf_counter()
        self.n_decode_steps += 1
        with self._span("serve.emit"):
            for i in live:
                req = self.active[i]
                self.pos[i] += 1
                req.out_tokens.append(int(nxt[i]))
                req.token_t.append(t_token)
                self._next_in[i] = int(nxt[i])
                self.n_generated += 1
                done = (len(req.out_tokens) >= req.max_new_tokens
                        or int(nxt[i]) == req.eos_id
                        or self.pos[i] >= self.max_len - 1)
                if done:
                    req.finish_t = time.perf_counter()
                    ev = self._cluster.events
                    if ev is not None:
                        ev.publish("request-exit", rid=req.rid,
                                   tenant=req.tenant,
                                   n_tokens=len(req.out_tokens),
                                   latency_s=req.finish_t - req.submit_t)
                    req.done.set()
                    self.active[i] = None
