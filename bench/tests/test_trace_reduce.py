"""The reduction from a profiler trace to busy time, op kinds and idle
gaps: its parts on hand-made input, and the whole on a short trace of a
training run (39-field rows on a 2^24 table) recorded on a v5e chip
(data/small_trace)."""
import os

import numpy as np

from bench import trace_reduce as tr

HLO = """HloModule jit_train_step

%fused_computation.5 (p0: f32[16], p1: s32[4]) -> f32[4] {
  %p0 = f32[16]{0} parameter(0)
  ROOT %gather.1 = f32[4]{0} gather(f32[16]{0} %p0, s32[4]{0} %p1)
}

%fused_computation.6 (p0: s32[4], p1: f32[4]) -> f32[16] {
  %c = f32[] constant(0)
  %b = f32[16]{0} broadcast(f32[] %c), dimensions={}
  ROOT %scatter.2 = f32[16]{0} scatter(f32[16]{0} %b, s32[4]{0} %p0), to_apply=%add
}

ENTRY %main.9 (a: f32[16], i: s32[4]) -> f32[16] {
  %fusion.5 = f32[4]{0} fusion(f32[16]{0} %a, s32[4]{0} %i), kind=kCustom, calls=%fused_computation.5
  ROOT %fusion.6 = f32[16]{0} fusion(s32[4]{0} %i, f32[4]{0} %fusion.5), kind=kCustom, calls=%fused_computation.6
}
"""


def test_parse_instruction_text():
    assert tr.parse("%fusion.5 = f32[4]{0:T(1024)} fusion(f32[16]{0} %a), "
                    "kind=kCustom, calls=%fused_computation.5") == (
        "fusion.5", "fusion", "fused_computation.5")
    assert tr.parse("%sort.0 = (s32[8]{0}, s32[8]{0}) sort(s32[8]{0} %x), "
                    "dimensions={0}")[:2] == ("sort.0", "sort")
    assert tr.parse("%while.2 = (s32[], s32[8]) while((s32[], s32[8]) %t),"
                    " condition=%c, body=%b.3")[1:] == ("while", "b.3")


def test_fusions_take_the_kind_of_what_they_hold():
    contents = tr.computations(HLO)
    assert "scatter" in contents["fused_computation.6"]
    assert tr.op_kind("%fusion.6 = f32[16]{0} fusion(s32[4]{0} %i), "
                      "kind=kCustom, calls=%fused_computation.6",
                      contents) == ("fusion.6 [scatter]", "scatter")
    assert tr.op_kind("%fusion.5 = f32[4]{0} fusion(f32[16]{0} %a), "
                      "calls=%fused_computation.5", contents)[1] == "gather"
    assert tr.op_kind("%fusion.5 = f32[4]{0} fusion(f32[16]{0} %a), "
                      "calls=%fused_computation.5")[1] == "fusion"
    assert tr.op_kind("%all-to-all.3 = s32[8]{0} all-to-all(s32[8]{0} %x)"
                      )[1] == "all-to-all"


def test_union_and_cover():
    iv = np.array([[5, 7], [0, 2], [1, 3], [6, 9]], np.float64)
    np.testing.assert_array_equal(tr._union(iv), [[0, 3], [5, 9]])
    assert tr._covered(np.array([[2.0, 6.0]]), tr._union(iv)) == 2.0


DATA = os.path.join(os.path.dirname(__file__), "data", "small_trace")


def test_reduce_a_recorded_chip_trace():
    r = tr.reduce(tr.find_xplane(DATA))
    assert r["devices"] == 1
    assert 0 < r["busy_s"] <= r["window_s"] < 1.0
    assert r["kinds"]["sort"] > 0
    assert sum(r["kinds"].values()) >= r["busy_s"] * 0.999
    assert len(r["top_ops"]) == 10
    assert {g[0] for g in r["idle_gaps"]} <= {
        "bench.loader_next", "bench.train_step", "no span"}
