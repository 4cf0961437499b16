"""The harness rehearsed on the CPU at smoke widths.

Each cell's mix runs through the whole run (weights, engine, warm-up,
lead-in, window, output check, metric readers) with ``JAX_PLATFORMS=cpu``;
only the look for a chip is skipped.  The output check must pass on the
program as it is, and fail when a served token is altered where the
engine produces it, and the float8 control must come out as not correct
against the same limit, reading far above the program.

    JAX_PLATFORMS=cpu python -m pytest chipbench/tests -q
"""
import json
import os
import shutil
import subprocess
import sys
import time

import jax
import pytest

import smoke
import spec

CELLS = list(smoke.CELLS)
RUN = [sys.executable, str(smoke.BENCH_DIR / "run.py"), "--workload",
       CELLS[0], "--seed", "3", "--seconds", "1", "--trace", "0"]


def _run(cell, seconds=3.0, **kw):
    import harness
    bench = spec.load_json(smoke.ROOT / "BENCHMARK.json")
    return harness.run(cell, bench, 2**31 + 11, seconds, False,
                       time.perf_counter(), jax.devices()[0], smoke.PEAKS,
                       **kw)


@pytest.mark.parametrize("name", CELLS)
def test_each_mix_runs_and_checks(name):
    result, notes = _run(smoke.smoke_cell(name))
    json.dumps(result)
    assert result["correct"], notes
    assert result["failed"] == 0 and result["attempted"] > 0
    assert set(result["metrics"]) == {"setup_s", "tokens_per_s",
                                      "tpot_p50_ms"}
    assert list(result)[-1] == "compared"
    assert notes[-1].startswith("max_logit_gap ")


@pytest.mark.parametrize("trace", [False, True], ids=["trace0", "trace1"])
def test_counters_and_op_names_reach_the_readers(monkeypatch, trace):
    """Every ``n_...`` whole number of the engine reaches the readers as its
    change over the window (and over the traced part); the decode
    program's op names only in a traced run, with the model's scopes."""
    import numpy as np
    import harness
    import scopes
    from repro.serve import engine as engine_lib
    iterate = engine_lib.ServingEngine._iterate

    def probed(self):
        if not hasattr(self, "n_probe"):
            self.n_probe, self.n_ratio, self.n_flag = 10**6, 0.5, True
            self.n_probe_0d = np.asarray(10**6, np.int32)
        iterate(self)
        self.n_probe += 2
        self.n_probe_0d = self.n_probe_0d + 3
    monkeypatch.setattr(engine_lib.ServingEngine, "_iterate", probed)
    made = smoke.kept_measured(monkeypatch)
    bench = spec.load_json(smoke.ROOT / "BENCHMARK.json")
    result, notes = harness.run(smoke.smoke_cell(CELLS[0]), bench,
                                2**31 + 11, 3.0, trace, time.perf_counter(),
                                jax.devices()[0], smoke.PEAKS)
    assert result["correct"], notes
    (m,) = made
    parts = [(m.counters, m.steps)]
    if trace:
        parts.append((m.trace_counters, m.trace_steps))
    else:
        assert m.trace_counters == {}
    for counted, steps in parts:
        assert set(counted) == {"n_decode_steps", "n_generated", "n_probe",
                                "n_probe_0d"}
        assert counted["n_decode_steps"] == steps > 0
        # changes, not readings; the loop passes at least once a step, and
        # a reading may fall between two counts of one pass
        passes = counted["n_probe"] // 2
        assert counted["n_probe"] == 2 * passes and passes < 10**6
        assert abs(counted["n_probe_0d"] - 3 * passes) <= 3
        assert counted["n_probe_0d"] % 3 == 0 and passes >= steps - 1
    assert m.counters["n_generated"] == m.generated
    if not trace:
        assert m.op_names == {}
        return
    found = {p for n in m.op_names["decode_fn"].values()
             for p in scopes.scope(n).split("/")}
    assert {"layers", "attn", "mlp"} <= found
    assert any(line.startswith("decode_fn op names: ") for line in notes)


def test_hybrid_runs_and_checks():
    """mamba2 layers beside a shared attention block, as the program's
    hybrids have them: the reference's recurrent path, and warm-up of the
    exact-length prefills a ladder of prompt lengths reaches.  No cell runs
    a hybrid until the engine stops keeping a cache per admission."""
    base = smoke.smoke_cell(CELLS[0])
    conf = smoke.hybrid_config(base.config)
    mix = dict(base.traffic,
               prompt=dict(base.traffic["prompt"], snap_to=[32, 64, 128]))
    cell = spec.Cell(name=base.name, chips=1, config=conf, traffic=mix,
                     sizes=base.sizes)
    result, notes = _run(cell)
    assert result["correct"], notes
    assert result["failed"] == 0 and result["attempted"] > 0


def test_altered_token_fails_the_check(monkeypatch):
    from repro.serve import engine as engine_lib
    init = engine_lib.ServingEngine.__init__

    def broken_init(self, cfg, *a, **kw):
        init(self, cfg, *a, **kw)
        decode = self._decode

        def altered(*args):
            nxt, cache = decode(*args)
            return (nxt + 1) % cfg.vocab_size, cache
        self._decode = altered

    monkeypatch.setattr(engine_lib.ServingEngine, "__init__", broken_init)
    result, notes = _run(smoke.smoke_cell(CELLS[0]))
    assert not result["correct"]
    gap = result["compared"]["max_logit_gap"]
    assert gap["value"] > gap["limit"]


@pytest.mark.parametrize("name", CELLS)
def test_control_is_not_correct(name):
    sys.path.insert(0, str(smoke.HERE))
    import calibrate
    cell = smoke.smoke_cell(name, dtype="bfloat16")
    (row,) = calibrate.calibrate(cell, [5], 2.0, jax.devices()[0],
                                 smoke.PEAKS)
    assert row["correct"] and not row["control_correct"], row
    assert row["control_gap"] >= 3 * row["program_gap"], row


def test_no_tpu_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(RUN, cwd=smoke.ROOT, env=env, capture_output=True,
                       text=True, timeout=300)
    assert p.returncode != 0 and p.stdout == ""
    assert "no TPU" in p.stderr


def test_without_the_program_no_result(tmp_path):
    shutil.copy(smoke.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(smoke.BENCH_DIR, tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    cmd = [sys.executable, "chipbench/run.py"] + RUN[2:]
    p = subprocess.run(cmd, cwd=tmp_path, capture_output=True, text=True,
                       timeout=300)
    assert p.returncode != 0 and p.stdout == ""


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        spec.load_peaks("TPU v9 imaginary")
    assert spec.load_peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
