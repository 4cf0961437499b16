import os

# Tests run on the single real CPU device.
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import pytest  # noqa: E402


@pytest.fixture(scope="session")
def rng():
    import numpy as np
    return np.random.default_rng(0)


# ---------------------------------------------------------------------------
# live protocol conformance: every ServerCore the suite builds WITHOUT its
# own events= spec gets a bus + ConformanceSink, and the protocol checker
# (repro.analysis.trace) validates the full event stream at teardown — the
# whole parity matrix (thread/selector/asyncio x dask/rsds) is spec-checked
# for free.  test_events.py is exempt: it asserts the events-off default
# (n_events == 0), which this fixture would defeat.
# ---------------------------------------------------------------------------

@pytest.fixture(autouse=True)
def _conformance_sink(request, monkeypatch):
    if request.module.__name__ == "tests.test_events" \
            or request.module.__name__.endswith("test_events"):
        yield
        return
    from repro.analysis.trace import ConformanceSink
    from repro.core.events import EventBus
    from repro.core.server import ServerCore

    sinks: list[ConformanceSink] = []
    orig_init = ServerCore.__init__

    def patched(self, *args, **kw):
        if not kw.get("events"):
            bus = EventBus()
            sink = ConformanceSink(path=f"<live:{request.node.name}>")
            bus.add_sink(sink)
            sinks.append(sink)
            kw["events"] = bus
        orig_init(self, *args, **kw)

    monkeypatch.setattr(ServerCore, "__init__", patched)
    yield
    problems = [f for s in sinks for f in s.findings]
    errors = sum(s.n_internal_errors for s in sinks)
    assert not problems, (
        "protocol conformance violations in live event stream:\n"
        + "\n".join(f"  {f.key} @ {f.where}: {f.message}"
                    for f in problems[:20]))
    assert errors == 0, f"{errors} internal checker error(s)"
