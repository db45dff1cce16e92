"""The program's own names in a trace: the stage scopes reach the
compiled step's op metadata, the host spans and counters of the engine
and the loader nest as documented, and `bench/program_trace.py` reduces
device ops and host spans by those names."""
import glob
import os
import shutil
import sys
import threading
import types

import jax
import numpy as np
import pytest

from repro.api import DPMREngine, ShardedLoader
from repro.configs.base import DPMRConfig
from repro.data import get_source
from repro.launch.mesh import make_host_mesh
from repro.runtime import spans

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from bench import program_trace as pt  # noqa: E402

F, K, B = 1 << 12, 8, 64


def _cfg():
    return DPMRConfig(num_features=F, max_features_per_sample=K, max_hot=16,
                      optimizer="adagrad", distribution="a2a")


def _loader(mesh, prefetch=2):
    src = get_source("zipf_sparse", batch_size=B, num_batches=8,
                     num_features=F, features_per_sample=K, seed=0)
    return ShardedLoader(src, mesh, prefetch=prefetch, host_index=0,
                         num_hosts=1)


# ---------------------------------------------------------------------------
# the compiled step
# ---------------------------------------------------------------------------


def test_stage_scopes_reach_the_compiled_step():
    mesh = make_host_mesh(1, 1)
    engine = DPMREngine(_cfg(), mesh)
    batch = engine.put_batch(next(iter(_loader(mesh, 0).batches(1))))
    text = engine.step_fns(B).train_step.lower(
        engine.state, batch).compile().as_text()
    names = set()
    for op_name in pt._OP_NAME.findall(text):
        names.update(op_name.split("/"))
    assert {f"dpmr.{s}" for s in pt.STAGES} <= names
    assert set(pt.SUBSTAGES) - {"exchange"} <= names
    stage_of, sub_of = pt.instruction_scopes([text])
    assert set(stage_of.values()) == set(pt.STAGES)
    assert set(sub_of.values()) >= set(pt.SUBSTAGES) - {"exchange"}


# ---------------------------------------------------------------------------
# host spans and counters
# ---------------------------------------------------------------------------


def test_fit_sgd_spans_nest_and_count(tmp_path):
    mesh = make_host_mesh(1, 1)
    engine = DPMREngine(_cfg(), mesh)
    engine.fit_sgd(_loader(mesh), steps=1)         # compile outside
    spans.reset()
    steps = 5
    jax.profiler.start_trace(str(tmp_path))
    try:
        engine.fit_sgd(_loader(mesh), steps=steps)
    finally:
        jax.profiler.stop_trace()
    t = spans.totals()
    assert t["spans"]["dpmr.train_step"]["n"] == steps
    assert t["spans"]["dpmr.dispatch"]["n"] == steps
    assert t["spans"]["dpmr.metrics_sync"]["n"] == steps
    # one wait per batch, and the one that finds the plan's end
    assert t["spans"]["loader.wait"]["n"] == steps + 1
    assert t["spans"]["loader.place"]["n"] >= steps
    assert t["counts"]["dpmr.steps"] == steps
    assert t["counts"]["loader.batches"] == steps
    assert t["counts"]["dpmr.overflow"] == 0
    assert "dpmr.step_fns_built" not in t["counts"]    # no compile here

    path = glob.glob(os.path.join(tmp_path, "**", "*.xplane.pb"),
                     recursive=True)[0]
    found, thread, window = pt.host_spans(pt.trace_reduce.load(path))
    assert window is None and thread is not None
    by = {}
    for name, s, e, th in found:
        by.setdefault(name, []).append((s, e, th))
    trains = by["dpmr.train_step"]
    assert len(trains) == steps
    assert {th for _, _, th in trains} == {thread}
    for child in ("dpmr.dispatch", "dpmr.metrics_sync"):
        assert len(by[child]) == steps
        for s, e, th in by[child]:
            assert th == thread
            assert any(ts <= s and e <= te for ts, te, _ in trains), child
    assert len(by["loader.wait"]) == steps + 1
    for s, e, th in by["loader.wait"]:
        assert th == thread
        assert not any(ts < e and s < te for ts, te, _ in trains)
    assert {th for _, _, th in by["loader.place"]} - {thread}


def test_place_runs_on_the_consumer_without_prefetch():
    mesh = make_host_mesh(1, 1)
    spans.reset()
    got = list(_loader(mesh, prefetch=0).batches(3))
    t = spans.totals()
    assert len(got) == 3
    assert t["spans"]["loader.place"]["n"] == 3
    assert "loader.wait" not in t["spans"]
    assert t["counts"] == {"loader.batches": 3}


def test_totals_lose_no_update_across_threads():
    spans.reset()
    n, per = 8, 500
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        def work():
            for _ in range(per):
                with spans.span("t.span"):
                    spans.count("t.count", 2)

        threads = [threading.Thread(target=work) for _ in range(n)]
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=60)
            assert not th.is_alive()
    finally:
        sys.setswitchinterval(old)
    t = spans.totals()
    assert t["spans"]["t.span"]["n"] == n * per
    assert t["counts"]["t.count"] == 2 * n * per
    spans.reset()
    assert spans.totals() == {"spans": {}, "counts": {}}


# ---------------------------------------------------------------------------
# bench/program_trace.py on hand-made input
# ---------------------------------------------------------------------------

HLO = """HloModule jit_train_step

%fused_computation.1 (p0: s32[8]) -> s32[8] {
  %p0 = s32[8]{0} parameter(0)
  %gather.1 = s32[8]{0} gather(s32[8]{0} %p0), metadata={op_name="jit(train_step)/dpmr.split_hot/gather"}
  %add.1 = s32[8]{0} add(s32[8]{0} %gather.1, s32[8]{0} %p0), metadata={op_name="jit(train_step)/dpmr.split_hot/add"}
  ROOT %sub.1 = s32[8]{0} subtract(s32[8]{0} %add.1, s32[8]{0} %p0), metadata={op_name="jit(train_step)/dpmr.distribute/sub"}
}

%fused_computation.3 (p0: f32[8]) -> f32[8] {
  %p0 = f32[8]{0} parameter(0)
  ROOT %negate.3 = f32[8]{0} negate(f32[8]{0} %p0), metadata={op_name="jit(train_step)/dpmr.reduce/combine_grads/neg"}
}

%fused_computation.2 (p0: f32[8]) -> f32[8] {
  %p0 = f32[8]{0} parameter(0)
  ROOT %fusion.20 = f32[8]{0} fusion(f32[8]{0} %p0), kind=kLoop, calls=%fused_computation.3
}

ENTRY %main.9 (a: s32[8]) -> f32[8] {
  %a = s32[8]{0} parameter(0)
  %fusion.1 = s32[8]{0} fusion(s32[8]{0} %a), kind=kLoop, calls=%fused_computation.1
  %reduce-window.8 = s32[8]{0} reduce-window(s32[8]{0} %fusion.1, s32[] %a), window={size=8}, to_apply=%add, metadata={op_name="reduce_window_sum" stack_frame_id=9}
  %sort.2 = s32[8]{0} sort(s32[8]{0} %reduce-window.8), dimensions={0}, metadata={op_name="jit(train_step)/jit(main)/dpmr.distribute/route_build/sort" stack_frame_id=3}
  %all-to-all.3 = s32[8]{0} all-to-all(s32[8]{0} %sort.2), metadata={op_name="jit(train_step)/dpmr.distribute/exchange/all_to_all"}
  %scatter.4 = f32[8]{0} scatter(s32[8]{0} %all-to-all.3), metadata={op_name="jit(train_step)/dpmr.reduce/owner_accumulate/scatter-add"}
  %fusion.7 = f32[8]{0} fusion(f32[8]{0} %scatter.4), kind=kLoop, calls=%fused_computation.2
  %multiply.5 = f32[8]{0} multiply(f32[8]{0} %fusion.7, f32[8]{0} %fusion.7), metadata={op_name="jit(train_step)/dpmr.optimize/mul"}
  ROOT %copy.6 = f32[8]{0} copy(f32[8]{0} %multiply.5)
}
"""


def test_instruction_scopes_with_the_fusion_fallback():
    stage, sub = pt.instruction_scopes([HLO])
    assert stage["fusion.1"] == "split_hot"       # 2 of its 3 say so
    assert stage["sort.2"] == "distribute"
    assert sub["sort.2"] == "route_build"
    assert sub["all-to-all.3"] == "exchange"
    assert stage["scatter.4"] == "reduce"
    assert sub["scatter.4"] == "owner_accumulate"
    assert stage["multiply.5"] == "optimize"
    # a nested fusion's stage reaches the fusion that holds it
    assert (stage["fusion.7"], sub["fusion.7"]) == ("reduce",
                                                    "combine_grads")
    # cumsum's reduce-window lost the caller's scope: it takes its user's
    assert (stage["reduce-window.8"], sub["reduce-window.8"]) == (
        "distribute", "route_build")
    # a compiler's copy that no stage uses stays unscoped
    assert "copy.6" not in stage and "fusion.1" not in sub


def _op(name, kind, s, e):
    return (f"{name} [{kind}]", kind, float(s), float(e))


def test_attribute_stages_window_and_idle():
    # window [100, 1000); two steps on thread 1, the producer on thread 2
    devices = {"/device:TPU:0": [
        _op("fusion.1", "gather", 50, 150),      # clipped to [100, 150)
        _op("sort.2", "sort", 150, 250),
        _op("scatter.4", "scatter", 300, 400),   # idle [250, 300) before
        _op("copy.6", "copy", 400, 420),         # unscoped
        _op("multiply.5", "multiply", 600, 700),  # idle [420, 600) before
        _op("sort.2", "sort", 900, 1100),        # clipped to [900, 1000)
    ]}
    spans_ = [
        ("loader.wait", 100, 120, 1),
        ("dpmr.train_step", 120, 500, 1),
        ("dpmr.dispatch", 120, 260, 1),
        ("dpmr.metrics_sync", 260, 500, 1),
        ("loader.wait", 520, 650, 1),
        ("dpmr.train_step", 650, 880, 1),
        ("dpmr.dispatch", 650, 700, 1),
        ("dpmr.metrics_sync", 700, 880, 1),
        ("loader.place", 0, 280, 2),             # clipped to [100, 280)
        ("loader.place", 550, 620, 2),
    ]
    stage, sub = pt.instruction_scopes([HLO])
    programs = {"/device:TPU:0": [("jit_train_step", 100, 450),
                                  ("jit_train_step", 590, 1100)]}
    r = pt.attribute(devices, spans_, 1, (100.0, 1000.0), stage, sub,
                     programs)
    ns = 1e-9
    assert r["window_s"] == pytest.approx(900 * ns)
    assert r["stages"] == pytest.approx({
        "split_hot": 50 * ns, "distribute": 200 * ns, "reduce": 100 * ns,
        "optimize": 100 * ns, "unscoped": 20 * ns})
    assert r["substages"] == pytest.approx({
        "route_build": 200 * ns, "owner_accumulate": 100 * ns})
    assert r["busy_s"] == pytest.approx(470 * ns)
    # idle: [250, 300) dispatch 10 + metrics_sync 40; [420, 600)
    # metrics_sync 80 + between steps 20 + loader.wait 80; [700, 900)
    # metrics_sync 180 + after the last step 20
    assert r["idle"] == pytest.approx({
        "dpmr.dispatch": 10 * ns, "dpmr.metrics_sync": 300 * ns,
        "between steps": 20 * ns, "loader.wait": 80 * ns,
        "no span": 20 * ns})
    # inside the programs: [250, 300) 50, [420, 450) 30, [590, 600) 10,
    # [700, 900) 200
    assert r["idle_in_program_s"] == pytest.approx(290 * ns)
    assert r["programs"]["jit_train_step"] == pytest.approx(
        {"s": 760 * ns, "n": 2})
    # programs start at 100 and 590, their dispatches at 120 and 650
    assert r["launch_ms"] == pytest.approx([-60e-6, -40e-6])
    assert sum(r["idle"].values()) == pytest.approx(
        r["window_s"] - r["busy_s"])
    # loader.place covers [250, 280) and [550, 600) of the idle time
    assert r["idle_place"] == pytest.approx({
        "dpmr.dispatch": 10 * ns, "dpmr.metrics_sync": 20 * ns,
        "loader.wait": 50 * ns})
    assert r["host"]["loader.place"] == pytest.approx(
        {"s": 250 * ns, "n": 2})
    assert r["host"]["dpmr.train_step"]["n"] == 2


def test_idle_inside_a_program_and_launch():
    # the step's program [30, 60) with a gap [40, 50) between its ops,
    # then another program [65, 70); the dispatch began at 25
    devices = {"/device:TPU:0": [_op("multiply.5", "multiply", 30, 40),
                                 _op("multiply.5", "multiply", 50, 60),
                                 _op("copy.1", "copy", 65, 70)]}
    programs = {"/device:TPU:0": [("jit_train_step", 30, 60),
                                  ("jit_copy", 65, 70)]}
    r = pt.attribute(devices, [("dpmr.train_step", 10, 80, 1),
                               ("dpmr.dispatch", 25, 28, 1),
                               ("dpmr.metrics_sync", 28, 80, 1)], 1,
                     (10.0, 80.0), {}, {}, programs)
    assert r["idle"] == pytest.approx({
        "dpmr.train_step": 15e-9, "dpmr.dispatch": 3e-9,
        "dpmr.metrics_sync": 27e-9})
    assert r["idle_in_program_s"] == pytest.approx(10e-9)
    assert r["programs"]["jit_copy"] == pytest.approx({"s": 5e-9, "n": 1})
    assert r["launch_ms"] == pytest.approx([5e-6, 5e-6])


def test_idle_outside_the_steps_has_no_span():
    devices = {"/device:TPU:0": [_op("multiply.5", "multiply", 40, 60)]}
    r = pt.attribute(devices, [("dpmr.train_step", 30, 70, 1)], 1,
                     (0.0, 100.0), {}, {}, {})
    assert r["stages"] == pytest.approx({"unscoped": 20e-9})
    assert r["idle"] == pytest.approx({"no span": 60e-9,
                                       "dpmr.train_step": 20e-9})


def test_host_spans_find_the_window_thread():
    def ev(name, s, d):
        return types.SimpleNamespace(name=name, start_ns=s, duration_ns=d)

    def line(*evs):
        return types.SimpleNamespace(name="python3", events=list(evs))

    pd = types.SimpleNamespace(planes=[
        types.SimpleNamespace(name="/device:TPU:0", lines=[]),
        types.SimpleNamespace(name="/host:CPU", lines=[
            line(ev("loader.place", 5, 3), ev("other", 0, 1)),
            line(ev("bench.window", 10, 90), ev("bench.train_step", 12, 9),
                 ev("dpmr.train_step", 12, 8))])])
    found, thread, window = pt.host_spans(pd)
    assert window == (10, 100) and thread == 2
    assert found == [("loader.place", 5, 8, 1),
                     ("dpmr.train_step", 12, 20, 2)]


def test_readers_are_silent_on_a_program_without_names(tmp_path,
                                                      monkeypatch):
    """A chip trace of a program with no scopes and no spans (the
    committed `bench/tests/data/small_trace`): everything is `unscoped`,
    idle falls in `no span`, and each new reader returns None."""
    trace = tmp_path / "trace"
    shutil.copytree(os.path.join(ROOT, "bench", "tests", "data",
                                 "small_trace"), trace)
    monkeypatch.setattr(pt, "TRACE", str(trace))
    record = {"trace": {"busy_s": 1.0}, "steps": 8, "hlo": []}
    r = pt.read_record(record)
    assert set(r["stages"]) == {"unscoped"}
    assert set(r["idle"]) == {"no span"}
    assert r["host"] == {}
    from bench import common

    for name in ("split_hot_ms.train", "distribute_ms.train",
                 "map_ms.train", "reduce_ms.train", "optimize_ms.train",
                 "dispatch_ms.train", "metrics_sync_ms.train",
                 "loader_queue_wait_ms.train", "loader_place_ms.train",
                 "exchange_ms.train", "owner_ms.train"):
        mod = common.load_module("metrics", name)
        assert mod.read(record) is None, name
        assert mod.read({"steps": 8}) is None, name


@pytest.mark.parametrize("name,subs,want", [
    ("exchange_ms.train", {"exchange": 0.5, "owner_apply": 2.0}, 1.0),
    ("exchange_ms.train", {"owner_apply": 2.0}, None),
    ("owner_ms.train", {"owner_apply": 2.0, "owner_accumulate": 1.5,
                        "exchange": 0.5}, 7.0),
    ("owner_ms.train", {"owner_accumulate": 1.5}, 3.0),
    ("owner_ms.train", {"exchange": 0.5}, None),
])
def test_sub_stage_readers(monkeypatch, name, subs, want):
    """`exchange_ms.train` and `owner_ms.train` read device ms a step from
    the sub-stages (already averaged over the chips), and return None
    where their scopes are absent."""
    from bench import common

    monkeypatch.setattr(pt, "read_record", lambda record: {
        "substages": subs} if record.get("trace") else None)
    mod = common.load_module("metrics", name)
    got = mod.read({"trace": {"busy_s": 1.0}, "steps": 500})
    assert got == (None if want is None else pytest.approx(want))
    assert mod.read({"steps": 500}) is None


def test_intervals_helpers():
    a = np.array([[0.0, 2.0], [5.0, 9.0]])
    b = np.array([[1.0, 6.0], [8.0, 10.0]])
    np.testing.assert_array_equal(pt._intersect(a, b),
                                  [[1, 2], [5, 6], [8, 9]])
    f = pt._integral(a)
    np.testing.assert_allclose(f([-1, 1, 3, 6, 20]), [0, 1, 2, 3, 6])
    assert pt._innermost([("a", 2, 8), ("b", 3, 4)], 0, 10) == [
        (None, 0, 2), ("a", 2, 3), ("b", 3, 4), ("a", 4, 8), (None, 8, 10)]
