"""The trace reduction, on a small trace recorded on a TPU v5 lite chip
(``record_trace.py``): three runs of a jitted ``decode_fn`` inside the
benchmark's window span, each after 2 ms of host sleep and inside a host
span ``host-work``.
"""
from pathlib import Path

import smoke  # noqa: F401
import tracereduce as tr

SMALL = str(Path(__file__).resolve().parent / "data" / "small.xplane.pb")


def test_window_and_programs():
    red = tr.reduce(SMALL)
    assert red.hi - red.lo == 9_458_619
    runs = tr.program_times(red, "decode_fn")
    assert runs == [24_215, 24_217, 24_215]
    assert tr.other_program_times(red, ("decode_fn",)) == []
    # the device ran only inside those three programs
    assert tr.busy_ns(red) == 72_613
    assert tr.busy_ns(red) <= sum(runs)


def test_busy_and_gaps_tile_the_window():
    red = tr.reduce(SMALL)
    gaps = tr.idle_gaps(red)
    assert tr.busy_ns(red) + sum(e - s for s, e in gaps) == red.hi - red.lo
    assert all(red.lo <= s < e <= red.hi for s, e in gaps)


def test_breakdown_names_programs_and_host_work():
    bd = tr.breakdown(tr.reduce(SMALL))
    assert len(bd["device_ops"]) <= 10 and len(bd["idle_gaps"]) <= 10
    assert all(n.startswith("decode_fn/") for n, _ in bd["device_ops"])
    assert bd["device_ops"][0] == ["decode_fn/fusion", 3.7846e-05]
    # most of the idle time lies in the host's own spans
    assert bd["idle_gaps"][0][0] == "host-work"
    total = sum(t for _, t in bd["idle_gaps"])
    red = tr.reduce(SMALL)
    assert abs(total - (red.window_s - tr.busy_ns(red) * 1e-9)) < 1e-12


def test_merged_and_gaps_by_hand():
    red = tr.Reduced(lo=0, hi=100, ops=[(10, 20, "a"), (15, 30, "b"),
                                        (50, 60, "c")],
                     modules=[(10, 30, "jit_x(1)"), (50, 60, "jit_y(2)")],
                     host=[(30, 50, "wait", "main"), (0, 100, "loop", "t")])
    assert tr.merged(red.ops) == [(10, 30), (50, 60)]
    assert tr.busy_ns(red) == 30
    assert tr.idle_gaps(red) == [(0, 10), (30, 50), (60, 100)]
    bd = tr.breakdown(red)
    assert bd["device_ops"][0] == ["x/b", 15e-9]
    # the gap 30-50 is the host's "wait"; the others only the long "loop"
    assert dict(bd["idle_gaps"]) == {"loop": 50e-9, "wait": 20e-9}


def test_short_names():
    assert tr.short_name("jit_decode_fn(18084989565708003084)") == \
        "decode_fn"
    assert tr.short_name("%fusion.3 = bf16[8]{0} fusion(%p)") == "fusion.3"
