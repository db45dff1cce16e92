"""Reduce a profiler trace (`.xplane.pb`) to the numbers the metrics read.

The trace holds one plane per TPU chip (`/device:TPU:<n>`); its line
`XLA Ops` has one event per HLO instruction that ran, named by the
instruction's text (`%fusion.5 = f32[262144]{0} fusion(...),
kind=kCustom, calls=%fused_computation.5`). A `while` event spans the ops
of its body, which have events of their own, so it is left out of the
sums. What a fusion does is not in the trace: given the module's HLO text
(`compiled.as_text()`), a fusion is counted as the first of scatter,
sort, gather, ... that it contains. The host plane (`/host:CPU`) holds the benchmark's
own spans, `bench.*`, written with `jax.profiler.TraceAnnotation`; the
span `bench.window` marks the measured window, and both planes share one
clock.

    python3 bench/trace_reduce.py .bench_trace   # a summary, to look at

`reduce(path)` gives, over the window and averaged over the chips:
  busy_s, window_s   the union of op intervals, and the window's length
  kinds              device seconds by op kind (`sort`, `scatter`, ...)
  a2a_exposed_s      seconds of all-to-all during which no other op ran
  top_ops            the 10 ops that took most time, [name [kind], s]
  idle_gaps          idle device time by the benchmark span the host was
                     in at the time, the 10 largest, [name, seconds]
"""
from __future__ import annotations

import collections
import glob
import os
import re
import sys

import numpy as np

OPS_LINE = "XLA Ops"
WINDOW = "bench.window"
COLLECTIVE = ("all-to-all", "all-reduce", "all-gather", "reduce-scatter",
              "collective-permute")


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


CONTAINERS = ("while", "conditional", "call")
KINDS = ("scatter", "sort", "gather", "dynamic-update-slice",
         "dynamic-slice") + COLLECTIVE


def parse(text: str) -> tuple[str, str, str | None]:
    """(name, opcode, called computation) of one HLO instruction's text,
    `%fusion.5 = f32[8]{0} fusion(...), kind=kCustom, calls=%fused.5`."""
    name, _, rest = text.partition(" = ")
    if not rest:
        return text, text.split(".")[0], None
    if rest.startswith("("):                   # a tuple shape
        depth = 0
        for i, c in enumerate(rest):
            depth += (c == "(") - (c == ")")
            if depth == 0:
                rest = rest[i + 1:]
                break
    else:
        rest = rest.partition(" ")[2]
    opcode = rest.lstrip().partition("(")[0].strip()
    m = re.search(r"(?:calls|body)=%?([\w.\-]+)", rest)
    return name.strip().lstrip("%"), opcode, m.group(1) if m else None


def computations(hlo_text: str) -> dict[str, set[str]]:
    """Opcodes inside each computation of a module's HLO text, those of
    the computations it calls included."""
    ops, calls, cur = {}, {}, None
    for line in hlo_text.splitlines():
        if line.endswith("{") and not line.startswith(" "):
            cur = line.split()[1 if line.startswith("ENTRY") else 0]
            cur = cur.lstrip("%")
            ops[cur], calls[cur] = set(), set()
        elif cur and " = " in line:
            _, opcode, called = parse(line.strip().removeprefix("ROOT "))
            ops[cur].add(opcode)
            if called:
                calls[cur].add(called)

    def closure(c, seen):
        out = set(ops.get(c, ()))
        for d in calls.get(c, ()):
            if d not in seen:
                seen.add(d)
                out |= closure(d, seen)
        return out

    return {c: closure(c, {c}) for c in ops}


def op_kind(text: str, contents: dict[str, set[str]] | None = None
            ) -> tuple[str, str]:
    """(short name, kind) of one op: a fusion's kind is the first of
    KINDS found inside it, where the module's HLO is known."""
    name, opcode, called = parse(text)
    kind = opcode
    if opcode == "fusion" and contents and called in contents:
        kind = next((k for k in KINDS if k in contents[called]), "fusion")
    for c in COLLECTIVE:
        if kind.startswith(c):
            kind = c
    return f"{name} [{kind}]", kind


def _union(iv: np.ndarray) -> np.ndarray:
    """Merged, sorted intervals of an (n, 2) array."""
    if len(iv) == 0:
        return iv.reshape(0, 2)
    iv = iv[np.argsort(iv[:, 0])]
    out = [list(iv[0])]
    for s, e in iv[1:]:
        if s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return np.asarray(out, np.float64)


def _covered(iv: np.ndarray, cover: np.ndarray) -> float:
    """Length of the intervals `iv` that the merged `cover` overlaps."""
    tot = 0.0
    for s, e in iv:
        lo = np.searchsorted(cover[:, 1], s, side="right")
        for cs, ce in cover[lo:]:
            if cs >= e:
                break
            tot += min(e, ce) - max(s, cs)
    return tot


def load(path: str):
    from jax.profiler import ProfileData

    return ProfileData.from_file(path)


def collect(pd, contents: dict | None = None) -> dict:
    """Device ops per chip as (name, kind, start_ns, end_ns), without the
    loops and calls that only contain other ops, and host spans `bench.*`
    as (name, start_ns, end_ns)."""
    devices, spans = {}, []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:") and \
                plane.name.split(":")[-1].isdigit():
            ops = []
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for ev in line.events:
                    name, kind = op_kind(ev.name, contents)
                    if kind not in CONTAINERS:
                        ops.append((name, kind, ev.start_ns,
                                    ev.start_ns + ev.duration_ns))
            devices[plane.name] = ops
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("bench."):
                        spans.append((ev.name, ev.start_ns,
                                      ev.start_ns + ev.duration_ns))
    return {"devices": devices, "spans": spans}


def reduce(path: str, hlo_texts: list[str] = ()) -> dict:
    contents = {}
    for text in hlo_texts:
        contents.update(computations(text))
    got = collect(load(path), contents)
    devices, spans = got["devices"], got["spans"]
    if not devices:
        raise ValueError(f"no TPU device plane with '{OPS_LINE}' in {path}")
    win = [s for s in spans if s[0] == WINDOW]
    if win:
        ws, we = win[0][1], win[0][2]
    else:
        ends = [(o[2], o[3]) for ops in devices.values() for o in ops]
        ws, we = min(e[0] for e in ends), max(e[1] for e in ends)
    host = sorted((s for s in spans if s[0] != WINDOW), key=lambda s: s[1])
    starts = np.asarray([s[1] for s in host], np.float64)
    n = len(devices)
    busy = exposed = 0.0
    by_name, by_kind = collections.Counter(), collections.Counter()
    gaps = collections.Counter()
    for ops in devices.values():
        live = [o for o in ops if o[3] > ws and o[2] < we]
        iv = np.asarray([(max(o[2], ws), min(o[3], we)) for o in live],
                        np.float64).reshape(-1, 2)
        for o, (s, e) in zip(live, iv, strict=True):
            by_name[o[0]] += (e - s) / n
            by_kind[o[1]] += (e - s) / n
        merged = _union(iv)
        busy += float(np.sum(merged[:, 1] - merged[:, 0])) / n
        coll = iv[[o[1] in COLLECTIVE for o in live]] if live else iv
        other = _union(iv[[o[1] not in COLLECTIVE for o in live]]
                       if live else iv)
        if len(coll):
            exposed += (float(np.sum(coll[:, 1] - coll[:, 0]))
                        - _covered(_union(coll), other)) / n
        edges = np.concatenate([[ws], merged.reshape(-1), [we]])
        for s, e in edges.reshape(-1, 2):
            if e > s:
                gaps[_host_during(host, starts, s, e)] += (e - s) / n
    ns = 1e-9
    return {"busy_s": busy * ns, "window_s": (we - ws) * ns, "devices": n,
            "kinds": {k: v * ns for k, v in by_kind.items()},
            "a2a_exposed_s": exposed * ns,
            "top_ops": [[k, v * ns] for k, v in by_name.most_common(10)],
            "idle_gaps": [[k, v * ns] for k, v in gaps.most_common(10)]}


def _host_during(host: list, starts: np.ndarray, s: float, e: float
                 ) -> str:
    """The benchmark span that overlaps [s, e) most; "no span" if none.
    `host` is sorted by start; spans of one thread do not overlap, so the
    few that start last before `e` are the candidates."""
    best, name = 0.0, "no span"
    hi = int(np.searchsorted(starts, e))
    for hn, hs, he in host[max(0, hi - 8):hi]:
        ov = min(e, he) - max(s, hs)
        if ov > best:
            best, name = ov, hn
    return name


def summary(path: str) -> str:
    """Planes, lines, event counts and the first events of each line."""
    out = []
    for plane in load(path).planes:
        out.append(f"PLANE {plane.name}")
        for line in plane.lines:
            evs = list(line.events)
            out.append(f"  LINE {line.name!r}: {len(evs)} events")
            for ev in evs[:4]:
                out.append(f"    {ev.name!r} start {ev.start_ns} dur "
                           f"{ev.duration_ns} {dict(ev.stats)}")
    return "\n".join(out)


if __name__ == "__main__":
    p = sys.argv[1] if len(sys.argv) > 1 else ".bench_trace"
    p = p if p.endswith(".pb") else find_xplane(p)
    print(summary(p))
    print(reduce(p))
