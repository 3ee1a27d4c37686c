"""Reduce one profiler trace (``.xplane.pb``) to the device's numbers over
the benchmark's traced window.

The window is the host annotation ``bench.window`` that the harness opens
and closes around the traced steps. Device planes are ``/device:TPU:<n>``;
an operation is an event of their ``XLA Ops`` line, a program an event of
their ``XLA Modules`` line. Busy time is the union of the operations'
intervals inside the window, averaged over the chips. Each stretch of an
idle gap on the first chip is charged to the innermost host annotation (a
program span such as ``plan`` or ``collect``) open over it.
"""
from __future__ import annotations

import bisect
import collections
import re
from typing import Dict, List, Tuple

WINDOW = "bench.window"
_DEVICE = re.compile(r"^/device:TPU:\d+$")


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def _clip(a, b, lo, hi):
    return max(a, lo), min(b, hi)


def _op_name(name: str, start, mods, mod_starts) -> str:
    """``<program>:<op> = <shape>``: the HLO text up to its layout, behind
    the name of the program whose interval holds the operation's start."""
    i = bisect.bisect_right(mod_starts, float(start)) - 1
    prog = mods[i][2] if i >= 0 and float(start) < mods[i][1] else "?"
    return f"{prog}:{name.split('{')[0].strip()}"


def reduce_trace(path: str, span_names=None) -> dict:
    """Everything the readers need from one trace, in seconds. Idle gaps
    are charged only to host annotations named in ``span_names`` (the
    program's spans), where it is given."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    host_spans: List[Tuple[float, float, str]] = []
    window = None
    devices = []
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    a, d = float(e.start_ns), float(e.duration_ns)
                    if e.name == WINDOW:
                        window = (a, a + d)
                    elif d > 0 and (span_names is None or e.name in span_names):
                        host_spans.append((a, a + d, e.name))
        elif _DEVICE.match(plane.name):
            lines = {line.name: line for line in plane.lines}
            devices.append((plane.name, lines))
    if window is None:
        raise ValueError(f"no {WINDOW!r} annotation in {path}")
    if not devices:
        raise ValueError(f"no TPU device plane in {path}")
    lo, hi = window
    busy, ops, modules, first_busy = [], collections.Counter(), {}, None
    for _name, lines in sorted(devices):
        mods = sorted(
            (float(e.start_ns), float(e.start_ns + e.duration_ns),
             e.name.split("(")[0])
            for e in getattr(lines.get("XLA Modules"), "events", ())
        )
        mod_starts = [m[0] for m in mods]
        ivs = []
        for e in getattr(lines.get("XLA Ops"), "events", ()):
            a, b = _clip(float(e.start_ns), float(e.start_ns + e.duration_ns), lo, hi)
            if b > a:
                ivs.append((a, b))
                if first_busy is None:
                    ops[_op_name(e.name, e.start_ns, mods, mod_starts)] += (b - a) / 1e9
        merged = _union(ivs)
        busy.append(sum(b - a for a, b in merged) / 1e9)
        if first_busy is None:
            first_busy = merged
            for a0, b0, name in mods:
                a, b = _clip(a0, b0, lo, hi)
                if b > a:
                    n, s = modules.get(name, (0, 0.0))
                    modules[name] = (n + 1, s + (b - a) / 1e9)
    gaps = collections.Counter()
    edges = [lo] + [x for iv in first_busy for x in iv] + [hi]
    host_spans.sort()
    starts = [s[0] for s in host_spans]
    longest = max((s[1] - s[0] for s in host_spans), default=0.0)
    for a, b in zip(edges[::2], edges[1::2]):
        if b <= a:
            continue
        near = host_spans[bisect.bisect_left(starts, a - longest):
                          bisect.bisect_left(starts, b)]
        near = [s for s in near if s[1] > a]
        cuts = sorted({a, b} | {x for s in near for x in s[:2] if a < x < b})
        for c0, c1 in zip(cuts, cuts[1:]):
            open_ = [s for s in near if s[0] <= c0 and c1 <= s[1]]
            name = (min(open_, key=lambda s: s[1] - s[0])[2] if open_
                    else "(no span)")
            gaps[name] += (c1 - c0) / 1e9
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": sum(busy) / len(busy),
        "chips": len(devices),
        "ops": dict(ops),
        "modules": modules,  # program -> (count, seconds), first chip
        "idle_by_span": dict(gaps),
    }


def module_seconds(red: dict, pattern: str) -> Tuple[int, float]:
    """(count, seconds) of the programs whose name matches ``pattern``."""
    rx = re.compile(pattern)
    n = s = 0
    for name, (c, sec) in red["modules"].items():
        if rx.search(name):
            n += c
            s += sec
    return n, s


def breakdown(red: dict, top: int = 10) -> Dict[str, list]:
    """The ``breakdown`` of a traced result line."""
    def most(d):
        return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:top]]

    return {"device_ops": most(red["ops"]), "idle_gaps": most(red["idle_by_span"])}
