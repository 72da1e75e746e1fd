"""From a profiler trace to device busy time, kernel time and idle gaps.

`flatten` turns the profiler's XSpace file into plain events
`{"plane", "line", "name", "start_ns", "dur_ns"}`, which is also the form
of the small recorded trace the tests read. `reduce_trace` works on that
form only:

  device ops     events of a TPU plane's op line ("XLA Ops")
  busy_s         the union of the device op intervals inside the window,
                 averaged over the chips traced
  op time        summed device time per op name
  call time      summed device time per custom-call signature (target,
                 operand and result types), which tells Pallas kernels
                 apart where op names do not
  idle gaps      the stretches of the window in which no op ran, each
                 named by the benchmark's innermost host span ("bench.*")
                 open at its midpoint, or "no span"
"""
from __future__ import annotations

import glob
import os
import re
from collections import defaultdict
from typing import Dict, List, Optional, Sequence, Tuple

OP_LINE = "XLA Ops"
SPAN_PREFIX = "bench."


def find_xplane(log_dir: str) -> Optional[str]:
    files = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    return files[-1] if files else None


def op_name(hlo: str) -> str:
    """A device op's HLO instruction text cut to its name and kind:
    `%fusion.110 = bf16[64,14336]{...} fusion(...)` -> `%fusion.110 fusion`
    (a Pallas kernel's kind is `custom-call`)."""
    head, _, rest = hlo.partition(" = ")
    m = re.search(r"[\]\}\)] ([a-z][a-z0-9_-]*)\(", rest)
    return f"{head} {m.group(1)}" if m else head


_TYPE = r"[a-z][a-z0-9]*\[[0-9,]*\]"


def call_signature(hlo: str) -> Optional[str]:
    """A custom call's target, operand types and result type, as
    `tpu_custom_call(s32[64],s32[1],bf16[64,8,4,128],...)->bf16[...]`,
    from the HLO text a trace gives its device op (layouts dropped; the
    trace cuts long texts, so trailing operands may be missing). A Pallas
    kernel's own name is not in the trace: its operand and result shapes
    are what tell one kernel from another. None for other ops."""
    head, _, rest = hlo.partition(" = ")
    m = re.match(r"(" + _TYPE + r")\S* custom-call\((.*)", rest)
    if not m:
        return None
    target = re.search(r'custom_call_target="([^"]+)"', rest)
    ops = re.findall(r"(?:^|, )(" + _TYPE + r")\S* %", m.group(2))
    return (f"{target.group(1) if target else '?'}({','.join(ops)})"
            f"->{m.group(1)}")


def flatten(path: str) -> List[Dict]:
    """Device-op and benchmark-span events of an .xplane.pb file; a custom
    call's event also carries its `call_signature` as `call`."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        device = plane.name.startswith("/device:")
        for line in plane.lines:
            if device and line.name != OP_LINE:
                continue
            for ev in line.events:
                if not device and not ev.name.startswith(SPAN_PREFIX):
                    continue
                e = {"plane": plane.name, "line": line.name,
                     "name": op_name(ev.name) if device else ev.name,
                     "start_ns": float(ev.start_ns),
                     "dur_ns": float(ev.duration_ns)}
                call = call_signature(ev.name) if device else None
                if call:
                    e["call"] = call
                out.append(e)
    return out


def union_ns(intervals: Sequence[Tuple[float, float]]) -> List[List[float]]:
    """Merge [start, end) intervals."""
    merged: List[List[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def _clip(intervals, t0, t1):
    return [(max(s, t0), min(e, t1)) for s, e in intervals
            if e > t0 and s < t1]


def window_of(events: Sequence[Dict], name: str = "bench.window"):
    """[start, end) in ns of the benchmark's window span."""
    for ev in events:
        if ev["name"] == name and not ev["plane"].startswith("/device:"):
            return ev["start_ns"], ev["start_ns"] + ev["dur_ns"]
    raise ValueError(f"no {name!r} span in the trace")


def reduce_trace(events: Sequence[Dict], window: Tuple[float, float],
                 top: int = 10) -> Dict:
    t0, t1 = window
    dev = defaultdict(list)
    op_time: Dict[str, float] = defaultdict(float)
    call_time: Dict[str, float] = defaultdict(float)
    spans = []
    for ev in events:
        s, e = ev["start_ns"], ev["start_ns"] + ev["dur_ns"]
        if ev["plane"].startswith("/device:"):
            clipped = _clip([(s, e)], t0, t1)
            if clipped:
                dev[ev["plane"]].append(clipped[0])
                t = (clipped[0][1] - clipped[0][0]) * 1e-9
                op_time[ev["name"]] += t
                if "call" in ev:
                    call_time[ev["call"]] += t
        elif ev["name"] != "bench.window":
            spans.append((s, e, ev["name"]))
    window_s = (t1 - t0) * 1e-9
    if not dev:
        return {"window_s": window_s, "busy_s": 0.0, "chips": 0,
                "op_time": {}, "call_time": {}, "device_ops": [],
                "idle_gaps": []}
    busy, gaps = 0.0, defaultdict(float)
    for plane, ivs in dev.items():
        merged = union_ns(ivs)
        busy += sum(e - s for s, e in merged) * 1e-9
        edges = [t0] + [x for iv in merged for x in iv] + [t1]
        idle = [(gs, ge) for gs, ge in zip(edges[0::2], edges[1::2])
                if ge > gs]
        for (gs, ge), name in zip(idle, _innermost(
                spans, [0.5 * (gs + ge) for gs, ge in idle])):
            gaps[name] += (ge - gs) * 1e-9 / len(dev)
    busy /= len(dev)
    ops = sorted(op_time.items(), key=lambda kv: -kv[1])
    return {"window_s": window_s, "busy_s": busy, "chips": len(dev),
            "op_time": dict(op_time), "call_time": dict(call_time),
            "device_ops": [[n, s] for n, s in ops[:top]],
            "idle_gaps": [[n, s] for n, s in sorted(
                gaps.items(), key=lambda kv: -kv[1])[:top]]}


def _innermost(spans, points) -> List[str]:
    """For each time point (ascending), the name of the latest-started
    span open at it, by one sweep over span starts and ends."""
    marks = sorted([(s, 0, i) for i, (s, _, _) in enumerate(spans)]
                   + [(e, 1, i) for i, (_, e, _) in enumerate(spans)])
    out, open_, j = [], [], 0
    for p in points:
        while j < len(marks) and marks[j][0] <= p:
            _, is_end, i = marks[j]
            if not is_end:
                open_.append(i)
            else:
                open_.remove(i)
            j += 1
        out.append(spans[max(open_, key=lambda i: spans[i][0])][2]
                   if open_ else "no span")
    return out


def kernel_time(reduced: Dict, pattern: str) -> Optional[float]:
    """Summed device seconds of the custom calls whose signature matches
    the regular expression `pattern`, or None where none does."""
    hits = [t for call, t in reduced["call_time"].items()
            if re.match(pattern, call)]
    return sum(hits) if hits else None
