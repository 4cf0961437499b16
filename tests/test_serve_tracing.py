"""ServingEngine(tracing=True): the loop's ``serve.*`` profiler spans, a
six-segment task span for every prefill and decode task, and the request
stamps.  The served tokens are the same either way (docs/tracing.md,
"Serving spans"); that tracing off publishes nothing is in
``test_events.py``, which runs without the suite's conformance bus."""
import glob
import time

import jax
import numpy as np
import pytest

from repro import configs
from repro.core.tracing import SEGMENTS
from repro.models import model as model_lib
from repro.serve.engine import ServingEngine

CFG = configs.get_config("llama3.2-1b", smoke=True)
SPANS = ("serve.step", "serve.admit", "serve.init_cache", "serve.prefill",
         "serve.slot_copy", "serve.decode", "serve.sync", "serve.emit",
         "serve.idle")
LENGTHS = (5, 9, 17, 1, 12)       # one prompt of one token: no prefill


def _serve(tracing: bool, log_dir=None) -> dict:
    params = model_lib.init_params(jax.random.PRNGKey(1), CFG)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, CFG.vocab_size, size=n) for n in LENGTHS]
    eng = ServingEngine(CFG, params, max_batch=2, max_len=64,
                        tracing=tracing)
    eng.start()
    if log_dir is not None:
        jax.profiler.start_trace(str(log_dir))
    time.sleep(0.05)              # the loop idles before the first request
    reqs = [eng.submit(p, max_new_tokens=4) for p in prompts]
    for r in reqs:
        assert r.done.wait(120)
    if log_dir is not None:
        jax.profiler.stop_trace()
    eng.stop()
    spans = eng.trace_analysis().spans if tracing else None
    return {"engine": eng, "reqs": reqs, "spans": spans}


@pytest.fixture(scope="module")
def off():
    return _serve(False)


@pytest.fixture(scope="module")
def on(tmp_path_factory):
    log_dir = tmp_path_factory.mktemp("trace")
    out = _serve(True, log_dir)
    out["log_dir"] = log_dir
    return out


def test_profiler_trace_holds_each_serve_span_by_its_name(on):
    from jax.profiler import ProfileData
    paths = glob.glob(str(on["log_dir"] / "**" / "*.xplane.pb"),
                      recursive=True)
    assert len(paths) == 1
    names, admit_args = set(), []
    for plane in ProfileData.from_file(paths[0]).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("serve"):
                    names.add(e.name)
                if e.name == "serve.admit":
                    admit_args.append(dict(e.stats))
    # the names are the fixed strings: a request's id, slot and bucket
    # ride as the span's arguments
    assert names == set(SPANS)
    assert sorted(a["rid"] for a in admit_args) == [1, 2, 3, 4, 5]
    assert {a["bucket"] for a in admit_args} == {0, 16}


def test_every_task_has_a_span_with_six_segments(on):
    eng, spans = on["engine"], on["spans"]
    prefills = sum(1 for n in LENGTHS if n > 1)
    assert len(spans) == prefills + eng.n_decode_steps
    for sp in spans:
        assert sp.status == "ok"
        assert tuple(sp.segments()) == SEGMENTS


@pytest.mark.parametrize("side", ["off", "on"])
def test_request_stamps_are_ordered(side, request):
    for r in request.getfixturevalue(side)["reqs"]:
        assert len(r.token_t) == len(r.out_tokens) == 4
        stamps = [r.submit_t, r.admit_t, *r.token_t, r.finish_t]
        assert stamps == sorted(stamps) and r.submit_t > 0


def test_served_tokens_are_the_same_with_tracing_on_and_off(off, on):
    assert [r.out_tokens for r in off["reqs"]] == \
        [r.out_tokens for r in on["reqs"]]
    assert all(r.error is None for r in off["reqs"] + on["reqs"])
