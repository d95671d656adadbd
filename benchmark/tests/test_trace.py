"""The trace reduction on a small recorded trace.

``fixtures/matmuls.xplane.bin`` was recorded on one ``TPU v5 lite`` (PR 25's
attempt, 2026-09-27; the tree ignores ``*.xplane.pb``, hence the suffix):
one jitted 2048^2 bf16 matmul run three times, a sleep after the first. Its
device plane holds 3 module runs and 9 ops. Expected numbers are worked out
by hand from these events (name, start ns, duration ns):

    copy-start 45562484 13   copy-done 45562499 2      fusion 45562501 90874
    copy-start 67371285 14   copy-done 67371300 3      fusion 67371304 90899
    copy-start 67628401 13   copy-done 67628415 11637  fusion 67640053 90842
    modules jit__lambda: 45562481 90896, 67371283 90921, 67628398 102498
"""

import pathlib

import pytest

from benchmark import trace as tr

FIXTURE = pathlib.Path(__file__).parent / "fixtures" / "matmuls.xplane.bin"


@pytest.fixture(scope="module")
def plane():
    planes = [p for p in tr.load_planes(str(FIXTURE)) if p.ops]
    assert [p.name for p in planes] == ["/device:TPU:0"]
    return planes[0]


def test_events_read(plane):
    assert len(plane.modules) == 3 and len(plane.ops) == 9
    assert {n for n, _, _ in plane.modules} == {"jit__lambda"}
    assert [n for n, _, _ in plane.ops[:3]] == ["copy-start", "copy-done", "fusion"]


def test_busy_union_and_idle_share(plane):
    # No two ops overlap: the union is the sum.
    busy = (13 + 2 + 90874) + (14 + 3 + 90899) + (13 + 11637 + 90842)
    assert busy == 284297
    assert tr.busy_ns(plane.ops) == busy
    span = (67640053 + 90842) - 45562484
    assert tr.span_ns(plane.ops) == span == 22168411
    red = tr.reduce_trace(str(FIXTURE))
    assert red.busy_s == pytest.approx(busy / 1e9)
    assert red.window_s == pytest.approx(span / 1e9)
    assert red.idle_share == pytest.approx(1 - 284297 / 22168411)   # 0.98718


def test_busy_union_merges_overlaps():
    events = [("a", 0.0, 10.0), ("b", 5.0, 10.0), ("c", 30.0, 5.0), ("d", 31.0, 1.0)]
    assert tr.busy_ns(events) == 15.0 + 5.0


def test_op_time_by_prefix_within_module(plane):
    assert tr.op_time_ns(plane, "fusion") == (90874 + 90899 + 90842, 3)
    assert tr.op_time_ns(plane, "copy-done", "jit__lambda") == (2 + 3 + 11637, 3)
    assert tr.op_time_ns(plane, "copy", "jit__lambda") == (40 + 11642, 6)
    assert tr.op_time_ns(plane, "fusion", "jit_other") == (0, 0)
    assert tr.module_runs(plane, "jit__lambda") == [90896, 90921, 102498]


def test_names():
    assert tr.op_name("%fusion.12 = bf16[8]{0} fusion(bf16[8]{0} %p), kind=kLoop") == "fusion.12"
    assert tr.op_name("jit_step(123456)") == "jit_step"
    assert tr.op_name("%attn._blocked_cached_attention.546 = (bf16[1]) custom-call()") == (
        "attn._blocked_cached_attention.546"
    )
    assert tr.strip_n("attn.136") == "attn"
    assert tr.strip_n("copy.1448") == "copy"
    assert tr.strip_n("fusion.3.1") == "fusion"
    assert tr.strip_n("copy-start") == "copy-start"


def test_top_ops_sums_by_stripped_name(plane):
    top = tr.top_ops(plane, 2)
    assert top[0][0] == "fusion" and top[0][1] == pytest.approx(272615 / 1e9)
    assert top[1][0] == "copy-done" and top[1][1] == pytest.approx(11642 / 1e9)


def test_idle_gaps_and_their_host_events(plane):
    gaps = tr.idle_gaps(plane.ops)
    # 7 gaps between 9 ops (copy-done and fusion of the first run touch):
    # two long ones (the sleep, and the wait between the second and third
    # run) and five of 1-2 ns inside the programs.
    assert len(gaps) == 7
    long = sorted(d for _, d in gaps if d > 1000)
    assert long == [67628401 - 67462203, 67371285 - 45653375]
    out = dict(map(tuple, tr.gaps_by_host_event(plane, tr.load_trace(str(FIXTURE))[1])))
    assert out["gaps_under_20us"] == pytest.approx(6e-9)            # 2+1+1+1+1
    assert out["gaps_20us_to_5ms"] == pytest.approx(166198e-9)
    # the 21.7 ms gap is the fixture's ``time.sleep`` (python tracer was on)
    assert out["$time sleep"] == pytest.approx(21717910e-9)
