"""The trace reduction on a synthesised trace: busy union, idle share,
kernel matching, panel-span attribution and the breakdown."""
import pytest

from yardstick.trace import Op, Span, Trace, idle_gaps, merge, union_length

MS = 1_000_000  # ns


def _trace():
    dev = "/device:TPU:0"
    ops = [
        # panel 1: encode, scan (with an op nested in it), merge
        Op("fusion.1", 11 * MS, 12 * MS, dev, "fusion.1 tf_op=jit(_serve)"),
        Op("enc", 12 * MS, 13 * MS, dev, "enc long_name=fused_encode_pallas"),
        Op("scan", 13 * MS, 40 * MS, dev, "scan long_name=fused_retrieve_q"),
        Op("inner", 20 * MS, 30 * MS, dev, "inner"),
        # panel 2
        Op("enc", 52 * MS, 53 * MS, dev, "enc long_name=fused_encode_pallas"),
        Op("scan", 53 * MS, 80 * MS, dev, "scan long_name=fused_retrieve_q"),
        # outside the window
        Op("scan", 120 * MS, 150 * MS, dev, "scan long_name=fused_retrieve_q"),
    ]
    spans = [
        Span("bench.window", 0, 100 * MS),
        Span("bench.panel", 10 * MS, 42 * MS, {"rows": 64}),
        Span("bench.panel", 50 * MS, 85 * MS, {"rows": 8}),
        Span("bench.panel", 110 * MS, 155 * MS, {"rows": 64}),
    ]
    return Trace(ops=ops, spans=spans)


def test_interval_helpers():
    assert merge([(0, 5), (3, 8), (10, 12), (12, 13), (20, 20)]) == [
        (0, 8), (10, 13)]
    assert union_length([(5, 10), (0, 6), (20, 21)]) == 11
    assert list(idle_gaps([(2, 4), (6, 9)], 0, 10)) == [(0, 2), (4, 6),
                                                        (9, 10)]


def test_busy_is_a_union_and_idle_share_its_complement():
    tr = _trace()
    # window [0, 100) ms: busy 11-40 and 52-80 -> 29 + 28 = 57 ms
    assert tr.window() == (0, 100 * MS)
    assert tr.window_busy_s() == pytest.approx(0.057)
    assert tr.idle_share() == pytest.approx(0.43)
    assert tr.busy_s(20 * MS, 60 * MS) == pytest.approx(0.028)


def test_kernel_matching_by_pattern_and_window():
    tr = _trace()
    a, b = tr.window()
    assert [o.start for o in tr.ops_within(a, b, "fused_encode")] == [
        12 * MS, 52 * MS]
    # the scan outside the window is not a call of the window
    assert len(tr.ops_within(a, b, "fused_retrieve")) == 2
    assert tr.ops_within(a, b, "no_such_kernel") == []


def test_panel_span_attribution():
    tr = _trace()
    panels = tr.spans_named("panel")
    assert [p.stats["rows"] for p in panels] == [64, 8]
    p1, p2 = panels
    assert [o.name for o in tr.ops_within(p1.start, p1.end, "fused_")] == [
        "enc", "scan"]
    # host time of panel 1: 32 ms span, 29 ms device-busy inside
    assert (p1.end - p1.start) * 1e-9 - tr.busy_s(p1.start, p1.end) == \
        pytest.approx(0.003)


def test_breakdown_names_ops_and_gaps():
    bd = _trace().breakdown()
    names = [n for n, _ in bd["device_ops"]]
    assert names[0] == "scan" and bd["device_ops"][0][1] == pytest.approx(
        0.054)
    # idle 0-11, 40-52 and 80-100 ms: every gap's middle lies outside the
    # panels; the total comes first, then the longest gaps
    assert bd["idle_gaps"][0][0] == "outside bench.panel: all 3 gaps"
    assert bd["idle_gaps"][0][1] == pytest.approx(0.043)
    assert [round(t, 6) for _, t in bd["idle_gaps"][1:]] == [0.02, 0.012,
                                                            0.011]


def test_gap_inside_a_panel_is_named_by_it():
    dev = "/device:TPU:0"
    tr = Trace(ops=[Op("%a.1 = f32[8]{0} fusion(f32[8]{0} %p)", 0, 10, dev),
                    Op("b", 90, 100, dev)],
               spans=[Span("bench.window", 0, 100),
                      Span("bench.panel", 5, 95, {"rows": 8})])
    bd = tr.breakdown()
    assert bd["idle_gaps"] == [
        ["bench.panel: all 1 gaps", 80e-9], ["bench.panel: one gap", 80e-9]]
    # an op's HLO text is named by its instruction name
    assert [n for n, _ in bd["device_ops"]] == ["%a.1", "b"]
