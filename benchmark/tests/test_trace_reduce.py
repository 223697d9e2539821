"""The trace reduction against numbers worked out by hand from a small
recorded trace: three calls of a toy jit on the CPU (`wrapped_sine`,
then `dot_general.1`), each inside a `bench.step` annotation and
followed by a `bench.wait` sleep, all inside `bench.trace_window`.
On the CPU the executed ops sit on the `tf_XLAPjRtCpuClient` line of
the host plane; on a TPU the reduction reads the device plane's
`XLA Ops` line instead."""

import os

import pytest

from trace_reduce import events_of, idle_gaps, label_gap, reduce_events, union_length

TOY = os.path.join(os.path.dirname(__file__), "data", "toy.xplane.pb")


@pytest.fixture(scope="module")
def reduction():
    return reduce_events(
        *events_of(TOY, device_plane_prefix="/host:CPU", ops_line="tf_XLAPjRtCpuClient")
    )


def test_window_and_busy_union(reduction):
    # window: bench.trace_window, 14,406,323 ns. Busy, per call: the
    # sine, the dot and the executor's wait, with the zero-length and
    # the nested `end:`/`Await` events adding nothing:
    # (381027 + 458565 + 33) + (414511 + 366147 + 33) + (349400 + 412843 + 32)
    assert reduction.window_s == pytest.approx(14_406_323e-9)
    assert reduction.busy_s == pytest.approx(2_382_591e-9)
    assert reduction.devices == 1


def test_per_op_sums(reduction):
    assert reduction.op_s["wrapped_sine"] == pytest.approx((381027 + 414511 + 349400) * 1e-9)
    assert reduction.op_s["dot_general.1"] == pytest.approx((458565 + 366147 + 412843) * 1e-9)
    assert reduction.op_n["wrapped_sine"] == 3
    assert [n for n, _ in reduction.top_ops(2)] == ["dot_general.1", "wrapped_sine"]


def test_idle_gaps_labelled(reduction):
    # the three sleeps between calls and after the last, each covered by
    # a bench.wait, then the stretch before the first call
    got = [(label, round(s * 1e9)) for label, s in reduction.gaps[:4]]
    assert got == [
        ("bench.wait", 6382900 - 3113337),
        ("bench.wait", 14419297 - 11167740),
        ("bench.wait", 10404312 - 7164933),
        # a bench.step covers only 124,622 ns of this gap: under half
        ("none", 2272259 - 12974),
    ]


@pytest.mark.parametrize(
    "intervals, expect_union, expect_gaps",
    [
        ([], 0, [(0, 10)]),
        ([(2, 4), (3, 6), (8, 12)], 6, [(0, 2), (6, 8)]),
        ([(-5, 1), (9, 9)], 1, [(1, 10)]),
    ],
)
def test_union_and_gaps_clip_to_window(intervals, expect_union, expect_gaps):
    assert union_length(intervals, 0, 10) == expect_union
    assert idle_gaps(intervals, 0, 10) == expect_gaps


def test_gap_without_annotation_is_none():
    assert label_gap((0, 5), [("bench.x", 6, 9)]) == "none"
    assert label_gap((0, 5), [("bench.x", 0, 1), ("bench.y", 1, 4)]) == "bench.y"
    both = [("bench.x", 0, 3), ("bench.y", 1, 5), ("bench.z", 0, 1)]
    assert label_gap((0, 5), both) == "bench.y+bench.x"


def test_program_order_and_modules_inside_window():
    from trace_reduce import program_order

    modules = [("jit_b(2)", 5, 6), ("jit_a(1)", 1, 2), ("jit_b(2)", 3, 4), ("other", 0, 1)]
    assert program_order(modules, "jit_") == ["jit_a(1)", "jit_b(2)"]
    r = reduce_events(
        [[("op", 2, 3)]],
        [("bench.trace_window", 1, 5)],
        modules,
        {"jit_a(1)": "first"},
    )
    assert r.modules == [("first", 1, 2), ("jit_b(2)", 3, 4)]
