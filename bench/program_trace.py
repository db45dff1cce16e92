"""Reduce a profiler trace by the program's own names: device time by the
stage scopes of the training step, host time by the program's spans, and
device idle time by the span the training loop was in.

The step's stages are `jax.named_scope`s (`core/dpmr.py`): `dpmr.split_hot`,
`dpmr.distribute`, `dpmr.map`, `dpmr.reduce`, `dpmr.optimize`,
`dpmr.metrics`; below them the routing helpers of `core/sparse.py`
(`route_build`, `owner_apply`, `route_return`, `combine_grads`,
`owner_accumulate`) and `exchange` around the all-to-alls. A scope reaches
the compiled module only as `metadata={op_name="jit(train_step)/dpmr.
distribute/route_build/gather" ...}` in its HLO text, so each op of the
trace's `XLA Ops` line is looked up by its instruction name there: its
stage is the first `dpmr.<stage>` component of its `op_name`. A fusion
whose own `op_name` names no stage takes the stage most of its fused
instructions name; what is left is `unscoped`. The ops are those
`trace_reduce.collect` counts, so the stages and `unscoped` add up to the
sum of `trace_reduce`'s `kinds`.

The host spans (`repro.runtime.spans`: `dpmr.*`, `loader.*`) are read from
the host plane, clipped to the benchmark's `bench.window`. Each device-idle
interval is put down to the innermost program span of the thread that
holds `bench.window`; time inside no span goes to `between steps` from the
first `dpmr.train_step` to the last, and to `no span` outside them. How
much of each overlaps the producer thread's `loader.place` is given
apart, and how
much of all idle lies inside a program of the `XLA Modules` line, a
program running with no op. The attribution is as good as the agreement
of the host and device clocks: the k-th step program of the window
cannot start before the k-th `dpmr.dispatch` does, so `launch_ms` below
0 measures a disagreement.

    python3 bench/program_trace.py .bench_trace [step.hlo.txt ...]

prints the stage table, the sub-stage table, the host spans, the idle
attribution and the programs run of the newest trace there; the HLO
texts default to the `*.hlo.txt` files beside it, which `read_record`
writes.

`read_record(record)` gives, for a `--trace 1` run, per window and
averaged over the chips (None where the run was not traced):
  stages      device seconds by stage, `unscoped` included
  substages   device seconds by sub-stage
  top_unscoped  the 10 unscoped ops that took most time, [name [kind], s]
  host        program spans by name: {"s": seconds in the window, "n"}
  idle        device idle seconds by the window thread's span
  idle_place  the part of each that overlaps `loader.place`
  idle_in_program_s  idle inside the `XLA Modules` intervals
  programs    the modules that ran: {name: {"s": seconds, "n": runs}}
  launch_ms   [min, median] of a step program's start - its dispatch's
  busy_s, window_s, devices
"""
from __future__ import annotations

import collections
import functools
import glob
import os
import re
import sys

import numpy as np

if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

from bench import common, trace_reduce  # noqa: E402

TRACE = os.path.join(common.ROOT, ".bench_trace")   # as bench/run.py's
STAGES = ("split_hot", "distribute", "map", "reduce", "optimize",
          "metrics")
SUBSTAGES = ("route_build", "owner_apply", "route_return", "combine_grads",
             "owner_accumulate", "exchange")
UNSCOPED = "unscoped"
PROGRAM = ("dpmr.", "loader.")
STEP = "dpmr.train_step"
PLACE = "loader.place"
DISPATCH = "dpmr.dispatch"
MODULES_LINE = "XLA Modules"
STEP_PROGRAM = "jit_train_step"      # `core/dpmr.py`'s jitted `train_step`
BETWEEN, NO_SPAN = "between steps", "no span"
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_REF = re.compile(r"%([\w.\-]+)")


def _scope(op_name: str, names: set[str]) -> str | None:
    return next((c for c in op_name.split("/") if c in names), None)


def _majority(stages) -> str | None:
    votes = collections.Counter(s for s in stages if s)
    return votes.most_common(1)[0][0] if votes else None


def instruction_scopes(hlo_texts) -> tuple[dict, dict]:
    """(instruction -> stage, instruction -> sub-stage) over the modules'
    HLO texts. An instruction's own `op_name` decides. A fusion without a
    stage of its own takes the one most of its fused instructions, nested
    fusions' included, name. An instruction outside the step's name stack
    (its `op_name` does not start with `jit(`, or it has none) was made by
    a lowering that dropped the caller's scope, as cumsum's reduce-window
    is, or added by the compiler: without a stage, it takes the stage most
    of its users take, and their sub-stage."""
    stage_names = {f"dpmr.{s}" for s in STAGES}
    sub_names = set(SUBSTAGES)
    own, outside, fused, operands = {}, set(), {}, {}
    members, cur = collections.defaultdict(list), None
    for text in hlo_texts:
        for line in text.splitlines():
            if line.endswith("{") and not line.startswith(" "):
                cur = line.split()[1 if line.startswith("ENTRY") else 0]
                cur = cur.lstrip("%")
            elif cur and " = " in line:
                body = line.strip().removeprefix("ROOT ")
                name, opcode, called = trace_reduce.parse(body)
                m = _OP_NAME.search(body)
                op_name = m.group(1) if m else ""
                own[name] = (_scope(op_name, stage_names),
                             _scope(op_name, sub_names))
                if not op_name.startswith("jit("):
                    outside.add(name)
                if opcode == "fusion" and called:
                    fused[name] = called
                operands[name] = set(_REF.findall(body.partition(" = ")[2]))
                members[cur].append(name)

    def inside(comp: str, seen: set):
        for n in members[comp]:
            yield n
            if fused.get(n) and fused[n] not in seen:
                seen.add(fused[n])
                yield from inside(fused[n], seen)

    found = {}
    for name, scopes in own.items():
        found[name] = list(scopes)
        if name in fused:
            for i in (0, 1):
                found[name][i] = found[name][i] or _majority(
                    own[n][i] for n in inside(fused[name], {fused[name]}))
    for names in members.values():
        local, users = set(names), collections.defaultdict(list)
        for n in names:
            for o in operands[n] & local:
                users[o].append(n)
        for n in reversed(names):
            if n in outside and not found[n][0]:
                st = found[n][0] = _majority(found[u][0] for u in users[n])
                found[n][1] = found[n][1] or _majority(
                    found[u][1] for u in users[n] if found[u][0] == st)
    stage = {n: st.removeprefix("dpmr.") for n, (st, _) in found.items()
             if st}
    sub = {n: sb for n, (_, sb) in found.items() if sb}
    return stage, sub


def _integral(iv: np.ndarray):
    """t -> length of the sorted, disjoint intervals `iv` before t."""
    starts, ends = iv[:, 0], iv[:, 1]
    cum = np.concatenate([[0.0], np.cumsum(ends - starts)])

    def f(t):
        t = np.asarray(t, np.float64)
        if not len(starts):
            return np.zeros_like(t)
        k = np.searchsorted(starts, t, side="right") - 1
        kk = np.clip(k, 0, None)
        part = np.clip(t - starts[kk], 0.0, ends[kk] - starts[kk])
        return np.where(k >= 0, cum[kk] + part, 0.0)

    return f


def _intersect(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """Intersection of two lists of sorted, disjoint intervals."""
    if not len(a) or not len(b):
        return np.zeros((0, 2))
    pts = np.unique(np.concatenate([a.reshape(-1), b.reshape(-1)]))
    mid = (pts[:-1] + pts[1:]) / 2

    def inside(iv):
        k = np.searchsorted(iv[:, 0], mid, side="right") - 1
        return (k >= 0) & (mid < iv[np.clip(k, 0, None), 1])

    both = inside(a) & inside(b)
    return trace_reduce._union(np.stack([pts[:-1][both], pts[1:][both]], 1))


def _innermost(spans: list, ws: float, we: float) -> list:
    """[ws, we) cut into (span name or None, start, end) by the innermost
    of the properly nested `spans` (name, start, end) of one thread."""
    out, stack, t = [], [], ws

    def upto(x):
        nonlocal t
        if x > t:
            out.append((stack[-1][0] if stack else None, t, x))
            t = x

    def close():
        upto(stack[-1][2])
        stack.pop()

    for name, s, e in sorted(spans, key=lambda x: (x[1], -x[2])):
        while stack and stack[-1][2] <= s:
            close()
        upto(s)
        stack.append((name, s, e))
    while stack:
        close()
    upto(we)
    return out


def modules(pd) -> dict:
    """The programs that ran, from each TPU plane's `XLA Modules` line:
    {plane: [(name, start_ns, end_ns)]}. Idle inside them is a program
    running with no op; idle outside them, the device waiting for the
    host."""
    out = {}
    for plane in pd.planes:
        for line in plane.lines:
            if line.name == MODULES_LINE:
                out[plane.name] = [(ev.name.partition("(")[0], ev.start_ns,
                                    ev.start_ns + ev.duration_ns)
                                   for ev in line.events]
    return out


def host_spans(pd) -> tuple[list, int | None, tuple | None]:
    """The program's spans on the host planes, as (name, start, end,
    thread), the thread that holds the window, and the window."""
    spans, window, lines = [], None, 0
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            lines += 1
            for ev in line.events:
                end = ev.start_ns + ev.duration_ns
                if ev.name == trace_reduce.WINDOW:
                    window = (ev.start_ns, end, lines)
                elif ev.name.startswith(PROGRAM):
                    spans.append((ev.name, ev.start_ns, end, lines))
    if window:
        return spans, window[2], window[:2]
    steps = collections.Counter(s[3] for s in spans if s[0] == STEP)
    return spans, (steps.most_common(1)[0][0] if steps else None), None


def reduce(path: str, hlo_texts=()) -> dict:
    contents = {}
    for text in hlo_texts:
        contents.update(trace_reduce.computations(text))
    pd = trace_reduce.load(path)
    devices = trace_reduce.collect(pd, contents)["devices"]
    if not devices:
        raise ValueError(f"no TPU device plane in {path}")
    return attribute(devices, *host_spans(pd),
                     *instruction_scopes(hlo_texts), modules(pd))


def attribute(devices: dict, spans: list, thread, window: tuple | None,
              stage_of: dict, sub_of: dict, programs: dict) -> dict:
    """The reduction of `trace_reduce.collect`'s device ops and of
    `host_spans`, given `instruction_scopes` and `modules`."""
    if window is None:
        ends = [(o[2], o[3]) for ops in devices.values() for o in ops]
        window = (min(e[0] for e in ends), max(e[1] for e in ends))
    ws, we = window
    clipped = [(n, max(s, ws), min(e, we), th) for n, s, e, th in spans
               if e > ws and s < we]
    host = collections.defaultdict(lambda: {"s": 0.0, "n": 0})
    for n, s, e, _ in clipped:
        host[n]["s"] += (e - s) * 1e-9
        host[n]["n"] += 1
    steps = [(s, e) for n, s, e, th in clipped if n == STEP and th == thread]
    first = min((s for s, _ in steps), default=we)
    last = max((e for _, e in steps), default=ws)
    pieces = _innermost([(n, s, e) for n, s, e, th in clipped
                         if th == thread], ws, we)
    labels = [n or (BETWEEN if first <= s and e <= last else NO_SPAN)
              for n, s, e in pieces]
    bounds = np.asarray([(s, e) for _, s, e in pieces], np.float64)
    place = trace_reduce._union(np.asarray(
        [(s, e) for n, s, e, th in clipped if n == PLACE and th != thread],
        np.float64).reshape(-1, 2))

    n_dev = len(devices)
    stages, subs = collections.Counter(), collections.Counter()
    unscoped = collections.Counter()
    idle, idle_place = collections.Counter(), collections.Counter()
    busy = idle_in_program = 0.0
    launch = []        # each step's program start - its dispatch's start
    dispatched = sorted(s for n, s, e, th in clipped
                        if n == DISPATCH and th == thread)
    ran = collections.defaultdict(lambda: {"s": 0.0, "n": 0.0})
    for dev, ops in devices.items():
        live = [o for o in ops if o[3] > ws and o[2] < we]
        iv = np.asarray([(max(o[2], ws), min(o[3], we)) for o in live],
                        np.float64).reshape(-1, 2)
        for o, (s, e) in zip(live, iv, strict=True):
            name = o[0].rsplit(" [", 1)[0]
            stages[stage_of.get(name, UNSCOPED)] += (e - s) / n_dev
            if name not in stage_of:
                unscoped[o[0]] += (e - s) / n_dev
            if name in sub_of:
                subs[sub_of[name]] += (e - s) / n_dev
        merged = trace_reduce._union(iv)
        busy += float(np.sum(merged[:, 1] - merged[:, 0])) / n_dev
        edges = np.concatenate([[ws], merged.reshape(-1), [we]])
        gaps = edges.reshape(-1, 2)
        gaps = gaps[gaps[:, 1] > gaps[:, 0]]
        runs = [(n, max(s, ws), min(e, we))
                for n, s, e in programs.get(dev, ()) if e > ws and s < we]
        for name, s, e in runs:
            ran[name]["s"] += (e - s) / n_dev
            ran[name]["n"] += 1 / n_dev

        inside = _intersect(gaps, trace_reduce._union(np.asarray(
            [(s, e) for _, s, e in runs], np.float64).reshape(-1, 2)))
        idle_in_program += float(np.sum(inside[:, 1] - inside[:, 0])) / n_dev
        starts = sorted(s for n, s, _ in runs if n == STEP_PROGRAM)
        launch += [p - d for p, d in zip(starts, dispatched)]
        for into, gap_iv in ((idle, gaps), (idle_place,
                                            _intersect(gaps, place))):
            f = _integral(gap_iv)
            per = f(bounds[:, 1]) - f(bounds[:, 0])
            for label, v in zip(labels, per, strict=True):
                into[label] += float(v) / n_dev
    ns = 1e-9
    return {"devices": n_dev, "window_s": (we - ws) * ns, "busy_s": busy * ns,
            "stages": {k: v * ns for k, v in stages.items()},
            "substages": {k: v * ns for k, v in subs.items()},
            "top_unscoped": [[k, v * ns]
                             for k, v in unscoped.most_common(10)],
            "host": dict(host),
            "idle": {k: v * ns for k, v in idle.items() if v},
            "idle_in_program_s": idle_in_program * ns,
            "launch_ms": ([float(np.min(launch)) * 1e-6,
                           float(np.median(launch)) * 1e-6]
                          if launch else None),
            "programs": {k: {"s": v["s"] * ns, "n": v["n"]}
                         for k, v in ran.items()},
            "idle_place": {k: v * ns for k, v in idle_place.items() if v}}


@functools.lru_cache(maxsize=1)
def _reduce_cached(path: str, mtime: float, hlo_texts: tuple) -> dict:
    return reduce(path, hlo_texts)


def read_record(record: dict) -> dict | None:
    """The reduction of a traced run's trace, made once per trace file;
    the step's HLO texts are left beside it for `__main__`. None where
    the run was not traced."""
    if not record.get("trace") or not os.path.isdir(TRACE):
        return None
    path = trace_reduce.find_xplane(TRACE)
    hlo = tuple(record.get("hlo", ()))
    for i, text in enumerate(hlo):
        keep = os.path.join(os.path.dirname(path), f"step.{i}.hlo.txt")
        if not os.path.exists(keep):
            with open(keep, "w") as f:
                f.write(text)
    return _reduce_cached(path, os.path.getmtime(path), hlo)


def stage_ms(record: dict, stage: str) -> float | None:
    """Device ms per step under `dpmr.<stage>`; None where no op of the
    trace lies under any stage (a program without the scopes)."""
    r = read_record(record)
    if not r or not record.get("steps") or \
            not set(r["stages"]) - {UNSCOPED}:
        return None
    return r["stages"].get(stage, 0.0) / record["steps"] * 1e3


def span_ms(record: dict, name: str) -> float | None:
    """Host ms per step in the program span `name` within the window;
    None where the trace holds no such span."""
    r = read_record(record)
    if not r or not record.get("steps") or name not in r["host"]:
        return None
    return r["host"][name]["s"] / record["steps"] * 1e3


def _table(title: str, rows: dict, total: float | None = None) -> str:
    out = [title]
    for k, v in sorted(rows.items(), key=lambda kv: -kv[1]):
        share = f"  {100 * v / total:6.2f}%" if total else ""
        out.append(f"  {k:<24} {v:12.6f} s{share}")
    return "\n".join(out)


def summary(r: dict) -> str:
    busy, idle = r["busy_s"], r["window_s"] - r["busy_s"]
    return "\n".join([
        f"window {r['window_s']:.6f} s, device busy {busy:.6f} s, idle "
        f"{idle:.6f} s, {r['devices']} device(s); seconds per window, "
        "averaged over the devices",
        _table("device time by stage", r["stages"], busy),
        _table("device time by sub-stage", r["substages"], busy),
        _table("unscoped ops, the 10 largest", dict(r["top_unscoped"]),
               busy),
        _table("host spans in the window (all threads)",
               {k: v["s"] for k, v in r["host"].items()}),
        "  counts: " + ", ".join(f"{k} {v['n']}"
                                 for k, v in sorted(r["host"].items())),
        _table("device idle by the window thread's span", r["idle"], idle),
        _table("  of which the producer was in loader.place",
               r["idle_place"], idle),
        f"  of all idle, {r['idle_in_program_s']:.6f} s lies inside a "
        f"program ({MODULES_LINE}) that ran no op",
        "the step's program starts, after its dpmr.dispatch starts: "
        + ("min {:+.3f} ms, median {:+.3f} ms (below 0, the host and "
           "device clocks disagree by at least as much, and so does the "
           "idle attribution)".format(*r["launch_ms"])
           if r["launch_ms"] else "no step program or dispatch"),
        _table("programs run in the window",
               {f"{k} x{v['n']:g}": v["s"]
                for k, v in r["programs"].items()})])


if __name__ == "__main__":
    where = sys.argv[1] if len(sys.argv) > 1 else TRACE
    p = where if where.endswith(".pb") else trace_reduce.find_xplane(where)
    files = sys.argv[2:] or sorted(glob.glob(
        os.path.join(os.path.dirname(p), "*.hlo.txt")))
    texts = []
    for name in files:
        with open(name) as f:
            texts.append(f.read())
    print(summary(reduce(p, texts)))
