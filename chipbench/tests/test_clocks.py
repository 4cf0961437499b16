"""One clock for the device plane, the host plane and ``perf_counter``
(``clocks.py``), and device time by named scope (``scopes.py``): on the
recorded v5e trace of ``test_trace.py`` and on small hand-made inputs."""
import types
from pathlib import Path

import pytest

import smoke  # noqa: F401
import clocks
import scopes
import spec
import tracereduce as tr

SMALL = str(Path(__file__).resolve().parent / "data" / "small.xplane.pb")


def test_device_plane_reads_early_by_the_enqueue_bound():
    planes = clocks.read(SMALL)
    assert [r[3] for r in planes.runs] == [4, 5, 6]
    off = clocks.device_offset(planes)
    # runs 4-6 start 1.574-1.582 ms before their enqueues end and end
    # 1.941-2.207 ms before their completion callbacks start
    assert off.steps == [(48_124_864, 1_581_771)] and off.linked == 3
    assert 1.58e6 <= off.at(0) <= 1.94e6
    assert clocks.paired_runs(planes, off, ("decode_fn",)) == {
        "runs": 3, "inside": 3, "enqueue_only": 0, "outside": []}
    for s, e, _, rid in planes.runs:
        assert planes.enqueued[rid] <= s + off.at(s)
        assert e + off.at(s) <= planes.completed[rid]


def test_flow_ids_link_the_same_runs_as_run_ids():
    from jax.profiler import ProfileData
    by_flow = {}
    for plane in ProfileData.from_file(SMALL).planes:
        for line in plane.lines:
            for e in line.events:
                st = dict(e.stats)
                if "run_id" not in st:
                    continue
                flow = st.get("_c", st.get("_p"))
                by_flow.setdefault(flow, set()).add((e.name, st["run_id"]))
    names = {"jit_decode_fn(18084989565708003084)", clocks.ENQUEUE,
             clocks.COMPLETE}
    assert len(by_flow) == 3
    for linked in by_flow.values():
        assert {n for n, _ in linked} == names
        assert len({rid for _, rid in linked}) == 1


def test_a_stepped_device_clock_starts_a_new_stretch():
    # runs 7 and 8 allow +20..+30; run 9 allows +5..+12 (the device clock
    # stepped); run 10 has no callback, run 11 no enqueue
    planes = clocks.Planes(
        lo=0, hi=1000,
        runs=[(10, 20, "jit_a(1)", 7), (40, 50, "jit_b(2)", 8),
              (100, 110, "jit_a(1)", 9), (130, 140, "jit_p(3)", 10),
              (150, 160, "jit_a(1)", 11), (170, 180, "jit_a(1)", 12)],
        ops=[], enqueued={7: 30, 8: 60, 9: 105, 10: 136},
        completed={7: 45, 8: 80, 9: 122, 11: 175}, serve=[])
    off = clocks.device_offset(planes)
    assert off.steps == [(10, 20), (100, 6)] and off.linked == 5
    assert [off.at(t) for t in (0, 99, 100, 500)] == [20, 20, 6, 6]
    assert clocks.paired_runs(planes, off, ("jit_",)) == {
        "runs": 6, "inside": 3, "enqueue_only": 1, "outside": []}
    red = clocks.aligned(planes, off)
    assert red.modules[:3] == [(30, 40, "jit_a(1)"), (60, 70, "jit_b(2)"),
                               (106, 116, "jit_a(1)")]


def test_a_run_whose_own_bounds_cross_stops_and_is_named():
    planes = clocks.Planes(lo=0, hi=100, runs=[(10, 20, "jit_a(1)", 7)],
                           ops=[], enqueued={7: 30}, completed={7: 35},
                           serve=[])
    with pytest.raises(clocks.ClockError, match=r"run 7 \(jit_a\(1\)\)"):
        clocks.device_offset(planes)
    with pytest.raises(clocks.ClockError):
        clocks.device_offset(clocks.Planes(0, 1, [(0, 1, "x", 1)], [], {},
                                           {}, []))


def test_aligned_trace_moves_the_device_and_clips_to_the_window():
    planes = clocks.Planes(lo=100, hi=200,
                           runs=[(60, 90, "jit_p(1)", 1),
                                 (170, 190, "jit_p(1)", 2)],
                           ops=[(60, 90, "%a = f32[] add()"),
                                (170, 190, "%b = f32[] add()")],
                           enqueued={}, completed={},
                           serve=[(50, 95, "serve.step"),
                                  (120, 150, "serve.sync")])
    red = clocks.aligned(planes, clocks.Offset([(0, 20)], 0))
    assert red.modules == [(100, 110, "jit_p(1)"), (190, 200, "jit_p(1)")]
    assert red.ops == [(100, 110, "%a = f32[] add()"),
                       (190, 200, "%b = f32[] add()")]
    assert [h[2] for h in red.host] == ["serve.sync"]


def test_anchor_maps_perf_counter_onto_the_window_span():
    # perf_counter 1000.000 s .. 1000.004 s read just inside a window span
    # that lies at 5 ms .. 9.000004 ms on the trace (a 1 ppm longer span)
    a = clocks.Anchor(p0=1_000_000_000_000, p1=1_000_004_000_000,
                      lo=5_000_000, hi=9_000_004)
    assert a.ns(1000.0) == 5_000_000
    assert a.ns(1000.004) == 9_000_004
    assert a.ns(1000.002) == 7_000_002
    assert a.ns(999.999) == 3_999_999
    assert a.holds(1000.001) and not a.holds(1000.0041)


class _Span:
    def __init__(self, *ts):
        names = ("t_submit", "t_ingest", "t_queued", "t_dispatched",
                 "t_start", "t_end", "t_observed")
        for n, t in zip(names, ts):
            setattr(self, n, t)

    def segments(self):
        from repro.core.tracing import TaskSpan
        return TaskSpan(0, **vars(self)).segments()


def test_task_intervals_on_the_trace_clock():
    a = clocks.Anchor(p0=0, p1=1_000, lo=0, hi=1_000)     # 1 ns per ns
    sp = _Span(1e-7, 1.1e-7, 1.1e-7, 1.5e-7, 2e-7, 3e-7, 3.5e-7)
    control, execution = clocks.task_intervals([sp], a)
    # the empty ingest->schedulable segment is dropped
    assert control == [(100, 110), (110, 150), (150, 200), (300, 350)]
    assert execution == [(200, 300)]
    assert clocks.control_s(sp) == pytest.approx(1.5e-7)


def test_the_parts_tile_the_idle_time():
    # device busy 0-10, 40-50, 90-100 (aligned) in a window 0-120
    red = tr.Reduced(lo=0, hi=120,
                     ops=[(0, 10, "a"), (40, 45, "b"), (47, 50, "c"),
                          (90, 100, "d")],
                     modules=[(0, 10, "p"), (40, 50, "p"), (90, 100, "p")],
                     host=[(5, 44, "serve.step", "serve"),
                           (100, 105, "serve.sync", "serve")])
    control = [(10, 15), (12, 20), (50, 55)]
    execution = [(18, 30)]
    parts = clocks.split_idle(red, control, execution)
    idle = sum(e - s for s, e in tr.idle_gaps(red))
    assert idle == 120 - 28
    assert sum(parts.values()) == idle
    assert parts == {"runtime": 15, "dispatch": 10, "engine": 15,
                     "in_program": 2, "no_span": 50}
    assert tuple(parts) == clocks.PARTS


HLO = """\
HloModule jit_decode_fn, entry_computation_layout={...}

%body (p: (s32[], bf16[8])) -> (s32[], bf16[8]) {
  %fusion.3 = bf16[8]{0} fusion(bf16[8]{0} %g), kind=kLoop, calls=%f3, metadata={op_name="jit(decode_fn)/while/body/closed_call/attn/decode_attention/dot_general" source_file="x.py" source_line=3}
  %fusion.4 = bf16[8]{0} fusion(bf16[8]{0} %fusion.3), kind=kLoop, calls=%f4, metadata={op_name="jit(decode_fn)/while/body/closed_call/mlp/dot_general"}
  ROOT %copy.1 = bf16[8]{0} copy(bf16[8]{0} %fusion.4), metadata={op_name="jit(decode_fn)/while/body/dynamic_update_slice"}
}

ENTRY %main (x: bf16[8]) -> bf16[8] {
  %while.4 = (s32[], bf16[8]{0}) while((s32[], bf16[8]{0}) %t), condition=%cond, body=%body, metadata={op_name="jit(decode_fn)/while"}
  ROOT %fusion.9 = s32[16]{0} fusion(bf16[8]{0} %w), kind=kLoop, metadata={op_name="jit(decode_fn)/head/dot_general"}
}
"""


def test_op_names_and_scopes_from_hlo_text():
    names = scopes.op_names(HLO)
    assert names["fusion.3"].endswith("decode_attention/dot_general")
    assert set(names) == {"fusion.3", "fusion.4", "copy.1", "while.4",
                          "fusion.9"}
    assert scopes.scope(names["fusion.3"]) == "attn/decode_attention"
    assert scopes.scope(names["copy.1"]) == ""
    assert scopes.opcode("%while.4 = (s32[], (bf16[8]{0:T(8)})) "
                         "while((s32[]) %t), body=%b") == "while"
    assert scopes.opcode("%copy-start = (bf16[4]{0:T(8,128)S(1)}) "
                         "copy-start(bf16[4] %x)") == "copy-start"


def test_scope_times_skip_containers_and_other_programs():
    names = scopes.op_names(HLO)
    red = tr.Reduced(lo=0, hi=1000, modules=[
        (0, 100, "jit_decode_fn(1)"), (100, 300, "jit_prefill_fn(2)"),
        (300, 400, "jit_decode_fn(1)")], host=[], ops=[
        (0, 90, "%while.4 = (s32[]) while((s32[]) %t), body=%body"),
        (5, 45, "%fusion.3 = bf16[8]{0} fusion(bf16[8]{0} %g)"),
        (45, 80, "%fusion.4 = bf16[8]{0} fusion(bf16[8]{0} %fusion.3)"),
        (80, 85, "%copy.1 = bf16[8]{0} copy(bf16[8]{0} %fusion.4)"),
        (90, 100, "%fusion.9 = s32[16]{0} fusion(bf16[8]{0} %w)"),
        (100, 300, "%fusion.3 = bf16[8]{0} fusion(bf16[8]{0} %g)"),
        (305, 345, "%fusion.3 = bf16[8]{0} fusion(bf16[8]{0} %g)"),
        (350, 360, "%unknown.1 = f32[] add(f32[] %a, f32[] %b)")])
    by_scope, runs = scopes.scope_times(red, names, "decode_fn")
    assert runs == 2
    assert dict(by_scope) == {"attn/decode_attention": 80, "mlp": 35,
                              "": 15, "head": 10}
    m = types.SimpleNamespace(trace=red, op_names={"decode_fn": names})
    assert scopes.ns_under(m, "decode_fn", "decode_attention") == (80, 2)
    assert scopes.ns_under(m, "decode_fn", "attn") == (80, 2)
    # leaves only: the loop's 90 ns enclose its body's 80
    assert sum(by_scope.values()) <= sum(
        tr.program_times(red, "decode_fn"))


#: a decode program whose layer scan holds a scope that ``SCOPES`` does not
#: list (``probe_gate``, inside ``mlp``), a slice under ``layers`` alone, a
#: copy under no scope, a copy the compiler made with no op name, and the
#: scan's loop itself under ``layers``
SCAN_HLO = """\
HloModule jit_decode_fn, entry_computation_layout={...}

%body (p: (s32[], bf16[8])) -> (s32[], bf16[8]) {
  %fusion.4 = bf16[8]{0} fusion(bf16[8]{0} %g), kind=kLoop, calls=%f4, metadata={op_name="jit(decode_fn)/layers/while/body/closed_call/mlp/dot_general"}
  %fusion.5 = bf16[8]{0} fusion(bf16[8]{0} %fusion.4), kind=kLoop, calls=%f5, metadata={op_name="jit(decode_fn)/layers/while/body/closed_call/mlp/probe_gate/mul"}
  ROOT %dynamic-slice.2 = bf16[8]{0} dynamic-slice(bf16[8,8]{1,0} %w, s32[] %i), metadata={op_name="jit(decode_fn)/layers/while/body/dynamic_slice"}
}

ENTRY %main (x: bf16[8]) -> bf16[8] {
  %while.4 = (s32[], bf16[8]{0}) while((s32[], bf16[8]{0}) %t), condition=%cond, body=%body, metadata={op_name="jit(decode_fn)/layers/while"}
  %copy.1 = bf16[8]{0} copy(bf16[8]{0} %c), metadata={op_name="jit(decode_fn)/copy"}
  %copy.7 = bf16[8]{0} copy(bf16[8]{0} %copy.1)
  ROOT %fusion.9 = s32[16]{0} fusion(bf16[8]{0} %w), kind=kLoop, metadata={op_name="jit(decode_fn)/head/dot_general"}
}
"""

MS = 1_000_000


def _op(start_ms, end_ms, name, opcode="fusion"):
    return (start_ms * MS, end_ms * MS,
            f"%{name} = bf16[8]{{0}} {opcode}(bf16[8]{{0}} %x)")


#: two decode runs of 30 ms around a prefill run; in each, 7 ms of
#: ``mlp`` and 3 of ``mlp/probe_gate``, 2 of the slice and 2 of the copy,
#: 1 of the head; the first also 1 ms of an instruction the program does
#: not hold, and the loop enclosing its body; the second 1 ms of the copy
#: with no op name
SCAN_TRACE = tr.Reduced(lo=0, hi=70 * MS, host=[], modules=[
    (0, 30 * MS, "jit_decode_fn(1)"), (30 * MS, 40 * MS, "jit_prefill_fn(2)"),
    (40 * MS, 70 * MS, "jit_decode_fn(1)")], ops=sorted([
        _op(0, 29, "while.4", "while"), _op(0, 7, "fusion.4"),
        _op(7, 10, "fusion.5"), _op(10, 12, "dynamic-slice.2",
                                    "dynamic-slice"),
        _op(12, 14, "copy.1", "copy"), _op(14, 15, "unknown.1", "add"),
        _op(29, 30, "fusion.9"), _op(30, 40, "fusion.4"),
        _op(40, 47, "fusion.4"), _op(47, 50, "fusion.5"),
        _op(50, 52, "dynamic-slice.2", "dynamic-slice"),
        _op(52, 54, "copy.1", "copy"), _op(54, 55, "copy.7", "copy"),
        _op(69, 70, "fusion.9")]))


def _measured(trace=SCAN_TRACE, names=True):
    import harness
    conf = spec.load_json(spec.HERE / "configs" / "deepseek-coder-33b-8L.json")
    # a bandwidth at which the MLP weights (6,606,028,800 bytes) take 8 ms
    peaks = {"hbm_bytes_per_s": 6_606_028_800 / 0.008,
             "bf16_flops_per_s": 1e15}
    return harness.Measured(
        conf=conf, sizes={}, peaks=peaks, trace=trace,
        op_names={"decode_fn": scopes.op_names(SCAN_HLO)} if names else {})


@pytest.mark.parametrize("name,ns", [
    ("probe_gate", 6 * MS),          # not in SCOPES
    ("mlp", 20 * MS),
    ("layers", 24 * MS),             # without the 29 ms of the loop
    ("head", 2 * MS),
    ("attn", 0),
])
def test_ns_under_reads_any_named_scope(name, ns):
    assert "probe_gate" not in scopes.SCOPES
    assert scopes.ns_under(_measured(), "decode_fn", name) == (ns, 2)


@pytest.mark.parametrize("metric,value", [
    # (2 + 2 ms of the slice) + (2 + 2 + 1 of the copies + 1 unknown) / 2
    ("decode_copy_ms", 5.0),
    # 8 ms of weights at the peak over (14 + 6 ms of mlp) / 2 runs
    ("mlp_roofline", 80.0),
])
def test_scope_readers_by_hand(metric, value):
    import harness
    read = harness._metric_reader(metric)
    assert read(_measured()) == pytest.approx(value, rel=1e-12)
    # nothing to read: no trace, or no op names of the decode program
    assert read(_measured(trace=None)) is None
    assert read(_measured(names=False)) is None


def test_named_share_counts_unnamed_instructions():
    names = scopes.op_names(SCAN_HLO)
    assert names["copy.7"] == "" and scopes.scope(names["copy.7"]) == ""
    # 32 ms of leaves in the decode runs: 1 on unknown.1, which the program
    # does not hold, and 1 on copy.7, which has no op name
    assert scopes.named_share(SCAN_TRACE, names, "decode_fn") == 31 / 32
    with_name = {k: v for k, v in names.items() if v}
    assert scopes.named_share(SCAN_TRACE, with_name, "decode_fn") == 30 / 32
    assert scopes.named_share(SCAN_TRACE, names, "absent_fn") is None
