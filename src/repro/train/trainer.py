"""Training drivers.

:class:`Trainer` — the standard single-controller loop: jitted train step,
prefetched data, periodic async checkpoints and evals, exact restart from
the latest checkpoint (data pipeline included, since batches are a pure
function of step).

:class:`MicrobatchCoordinator` — the paper-integration path: each global
step becomes a task graph (M microbatch-gradient tasks -> 1 reduce+update
task) submitted as an epoch to one persistent :class:`repro.core.client.
Cluster`, so back-to-back steps reuse the warm executor pool instead of
restarting it.  The work-stealing scheduler rebalances microbatches away
from stragglers, and executor failure mid-step resubmits the lost
microbatches — the paper's mechanisms doing real training work.
"""
from __future__ import annotations

import dataclasses
import functools
import threading
import time
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.client import Cluster
from repro.core.graph import Task, TaskGraph
from repro.ckpt import checkpoint as ckpt_lib
from repro.data.pipeline import PrefetchPipeline, SyntheticDataset
from repro.models import model as model_lib
from repro.models.config import ModelConfig
from repro.train.optimizer import Optimizer, make_optimizer
from repro.train.train_step import make_loss_fn, make_train_step


@dataclasses.dataclass
class TrainerConfig:
    steps: int = 100
    global_batch: int = 8
    seq_len: int = 128
    ckpt_every: int = 50
    eval_every: int = 50
    ckpt_dir: str = ""
    keep_ckpts: int = 3
    log_every: int = 10
    seed: int = 0


class Trainer:
    def __init__(self, cfg: ModelConfig, tc: TrainerConfig,
                 optimizer: Optimizer | None = None):
        self.cfg = cfg
        self.tc = tc
        self.opt = optimizer or make_optimizer("adamw")
        key = jax.random.PRNGKey(tc.seed)
        self.params = model_lib.init_params(key, cfg)
        self.opt_state = self.opt.init(self.params)
        self.step = 0
        self.dataset = SyntheticDataset(cfg, tc.global_batch, tc.seq_len,
                                        tc.seed)
        self._train_step = jax.jit(make_train_step(cfg, self.opt))
        self._eval_step = jax.jit(
            lambda p, b: make_loss_fn(cfg)(p, b)[1]["loss"])
        self.ckptr = (ckpt_lib.AsyncCheckpointer(tc.ckpt_dir, tc.keep_ckpts)
                      if tc.ckpt_dir else None)
        self.history: list[dict] = []

    # ------------------------------------------------------------------
    def maybe_restore(self) -> bool:
        if not self.tc.ckpt_dir:
            return False
        step = ckpt_lib.latest_step(self.tc.ckpt_dir)
        if step is None:
            return False
        tree = {"params": self.params, "opt": self.opt_state}
        restored, step, _ = ckpt_lib.restore(self.tc.ckpt_dir, tree, step)
        self.params = restored["params"]
        self.opt_state = restored["opt"]
        self.step = step
        return True

    def train(self, steps: int | None = None) -> list[dict]:
        steps = steps or self.tc.steps
        pipe = PrefetchPipeline(self.dataset, depth=2, n_loaders=2,
                                start_step=self.step)
        try:
            while self.step < steps:
                step_id, batch = pipe.get()
                batch = {k: jnp.asarray(v) for k, v in batch.items()}
                t0 = time.perf_counter()
                self.params, self.opt_state, metrics = self._train_step(
                    self.params, self.opt_state, batch)
                loss = float(metrics["loss"])
                self.step = step_id + 1
                rec = {"step": self.step, "loss": loss,
                       "grad_norm": float(metrics["grad_norm"]),
                       "time_s": time.perf_counter() - t0}
                self.history.append(rec)
                if self.ckptr and self.step % self.tc.ckpt_every == 0:
                    self.ckptr.save(self.step,
                                    {"params": self.params,
                                     "opt": self.opt_state},
                                    meta={"config": self.cfg.name})
                if self.step % self.tc.eval_every == 0:
                    eb = {k: jnp.asarray(v) for k, v in
                          self.dataset.batch_at(10_000_000 + self.step
                                                ).items()}
                    rec["eval_loss"] = float(self._eval_step(self.params,
                                                             eb))
                if self.step % self.tc.log_every == 0:
                    print(f"step {self.step:5d} loss {loss:.4f} "
                          f"({rec['time_s']*1e3:.0f} ms)")
        finally:
            pipe.stop()
            if self.ckptr:
                self.ckptr.wait()
        return self.history


# ---------------------------------------------------------------------------
# Microbatch dispatch through the paper's runtime
# ---------------------------------------------------------------------------

def mean_and_apply(opt: Optimizer, params, opt_state, grads: list):
    """Average the microbatch gradients and apply one optimizer update;
    returns ``(params, opt_state)``.  Run as one jitted program that
    donates ``params`` and ``opt_state``: leaf by leaf and eager, old and
    new moments and the f32 temporaries are alive at once, which a
    full-width 2-layer llama3.2-1b step cannot fit on a 16 GB chip."""
    gsum = grads[0]
    for g in grads[1:]:
        gsum = jax.tree.map(jnp.add, gsum, g)
    gmean = jax.tree.map(lambda x: x / len(grads), gsum)
    return opt.apply(params, gmean, opt_state)[:2]


class MicrobatchCoordinator:
    """One training step = one graph epoch on a persistent Cluster.

    Executors are runtime workers (stand-ins for pods); each microbatch
    gradient is a task; the final task averages gradients and applies the
    optimizer.  The Cluster outlives the step loop, so the 2nd..Nth step
    submit onto warm executors (no pool restart between steps — the whole
    point of the paper's long-lived server).  ``slow_workers`` makes
    chosen executors straggle so the work-stealing scheduler's
    rebalancing is observable.

    Because the pool is shared across steps, an executor killed via
    ``fail_worker`` stays dead for the coordinator's lifetime (later
    steps run on the surviving executors) — a real long-lived deployment
    would replace it; elastic replacement of process/thread executors is
    a ROADMAP item.
    """

    #: default byte bound on the coordinator's pool (ROADMAP PR-5
    #: follow-up: trainer/serving pools are bounded like everyone
    #: else's).  Microbatch tasks return small ints (gradients ride the
    #: closure), so the bound is slack in practice.
    DEFAULT_MEMORY_LIMIT = 256 * 2**20

    def __init__(self, cfg: ModelConfig, *, n_executors: int = 4,
                 n_microbatches: int = 8, scheduler: str = "rsds_ws",
                 slow_workers: dict[int, float] | None = None,
                 seed: int = 0,
                 memory_limit: int | None = DEFAULT_MEMORY_LIMIT,
                 events=None, optimizer: Optimizer | None = None):
        self.cfg = cfg
        self.n_executors = n_executors
        self.n_micro = n_microbatches
        self.scheduler_name = scheduler
        self.slow = slow_workers or {}
        self.memory_limit = memory_limit
        self._events = events
        self.opt = optimizer or make_optimizer("adamw")
        key = jax.random.PRNGKey(seed)
        self.params = model_lib.init_params(key, cfg)
        self.opt_state = self.opt.init(self.params)
        loss_fn = make_loss_fn(cfg)
        self._grad = jax.jit(
            lambda p, b: jax.value_and_grad(
                lambda q: loss_fn(q, b)[0])(p))
        # parameters and optimizer state are updated in place, so a step
        # holds one copy of each
        self._update = jax.jit(functools.partial(mean_and_apply, self.opt),
                               donate_argnums=(0, 1))
        self.step = 0
        self.steal_count = 0
        self._cluster: Cluster | None = None

    # ------------------------------------------------------------------
    def _ensure_cluster(self) -> Cluster:
        if self._cluster is not None:
            return self._cluster
        server = "dask" if self.scheduler_name.startswith("dask") else \
            "rsds"
        sched = {"rsds_ws": "ws", "dask_ws": "ws", "ws": "ws",
                 "random": "random", "heft": "heft"}[self.scheduler_name]
        c = Cluster(server=server, scheduler=sched,
                    n_workers=self.n_executors, runtime="thread",
                    name="microbatch", balance_interval=0.002,
                    timeout=120.0, autostart=False,
                    memory_limit=self.memory_limit,
                    events=self._events)
        rt = c.runtime
        if self.slow:
            orig = rt._worker_loop

            def slow_loop(wid):
                if wid not in self.slow:
                    return orig(wid)
                inbox = rt.worker_inbox[wid]
                while True:
                    item = inbox.get()
                    if item is None:
                        return
                    if wid in rt.dead:
                        continue
                    with rt._lock:
                        if item in rt.queued.get(wid, []):
                            rt.queued[wid].remove(item)
                        else:
                            # retracted (stolen) while waiting in the
                            # inbox: skip without paying the straggler
                            # delay, or ghosts of a previous epoch's
                            # stolen tasks would stall the next one
                            continue
                        rt.running[wid] = item
                    time.sleep(self.slow[wid])
                    rt._execute(wid, item)

            rt._worker_loop = slow_loop
        c.start()
        self._cluster = c
        return c

    def close(self) -> None:
        if self._cluster is not None:
            self._cluster.close()
            self._cluster = None

    def _make_step_graph(self, batch: dict) -> TaskGraph:
        mb = {k: np.array_split(v, self.n_micro) for k, v in batch.items()}
        tasks = []
        losses = [0.0] * self.n_micro
        grads: list = [None] * self.n_micro

        def run_micro(i):
            def fn():
                # straggler injection happens per-executor in the runtime
                loss, g = self._grad(self.params,
                                     {k: jnp.asarray(v[i])
                                      for k, v in mb.items()})
                losses[i] = float(loss)
                grads[i] = g
                return i
            return fn

        for i in range(self.n_micro):
            tasks.append(Task(i, (), duration=1e-3, output_size=1024,
                              fn=run_micro(i), name=f"micro-{i}"))

        def reduce_fn(*_):
            self.params, self.opt_state = self._update(
                self.params, self.opt_state, grads)
            grads[:] = [None] * self.n_micro
            return float(np.mean(losses))

        tasks.append(Task(self.n_micro, tuple(range(self.n_micro)),
                          duration=1e-3, output_size=8, fn=reduce_fn,
                          name="reduce"))
        return TaskGraph(tasks, name=f"train-step-{self.step}")

    def train_step(self, batch: dict, *, fail_worker: int | None = None
                   ) -> dict:
        cluster = self._ensure_cluster()
        graph = self._make_step_graph(batch)
        if fail_worker is not None:
            def _killer():
                time.sleep(0.01)
                cluster.runtime.fail_worker(fail_worker)
            threading.Thread(target=_killer, daemon=True).start()
        futs = cluster.client.submit_graph(graph)
        ok = futs.wait(120.0)
        epoch = futs.epoch
        loss = futs.raw_results().get(self.n_micro) if ok else None
        futs.release()   # per-step values are consumed; free the keys
        if epoch.error is not None:
            raise epoch.error
        self.step += 1
        ev = cluster.events
        if ev is not None:
            ev.publish("train-step", step=self.step,
                       makespan=epoch.makespan)
        return {"step": self.step, "loss": loss,
                "makespan": epoch.makespan, "timed_out": not ok,
                "server_busy": epoch.server_busy}
