"""From a profiler trace (``.xplane.pb``) to the device's busy and idle
time, the time of each program, the top operations and the idle gaps by
what the host was doing.

The traced slice is bounded by the harness's own host spans (``step``,
``submit``, ``wait_arrival``): its window runs from the first span's
start to the last span's end, and every device interval is clipped to
it.  On each device plane (``/device:TPU:<n>``) the ``XLA Ops`` line
gives the operations and the ``XLA Modules`` line the programs.  Loops
and calls are listed there beside the operations of their bodies, so the
top operations leave them out.  Busy
time is the union of the operation intervals, averaged over the devices.
Idle time is labelled, piece by piece, with the innermost host span
over it: the program annotations (``prefill``, ``decode<k>``, ``reset``,
``cow``) inside ``step`` inside the window.
"""
from __future__ import annotations

import glob
import os
import re
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, List, Sequence, Tuple

Interval = Tuple[float, float]

#: programs, by parts of their XLA module names.  The engine jits the
#: decode macro-step as ``functools.partial(model.decode_steps, k=k)``,
#: which has no name of its own: XLA calls it ``jit__unknown``.
PROGRAMS = {"decode": ("decode_steps", "jit__unknown"),
            "prefill": ("prefill_chunk",)}


@dataclass
class Summary:
    window_s: float
    busy_s: float
    devices: int
    program_s: Dict[str, float] = field(default_factory=dict)
    program_calls: Dict[str, int] = field(default_factory=dict)
    top_ops: List[Tuple[str, float]] = field(default_factory=list)
    idle_by_span: List[Tuple[str, float]] = field(default_factory=list)

    @property
    def idle_frac(self) -> float:
        return 1.0 - self.busy_s / self.window_s


def union(intervals: Sequence[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


def clip(intervals, lo, hi) -> List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if b > lo and a < hi]


def gaps(busy: List[Interval], lo: float, hi: float) -> List[Interval]:
    out, t = [], lo
    for a, b in busy:
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if hi > t:
        out.append((t, hi))
    return out


CONTAINERS = ("%while", "%conditional", "%call")


def op_name(text: str) -> str:
    """``%name shape`` of an XLA op's full text (layouts dropped)."""
    m = re.match(r"(%[\w.-]+) = ([^{ ]+)", text)
    return f"{m.group(1)} {m.group(2)}" if m else text[:80]


def program_of(module: str) -> str:
    for prog, parts in PROGRAMS.items():
        if any(p in module for p in parts):
            return prog
    return module.split("(")[0]


def timeline(spans) -> List[Tuple[float, float, str]]:
    """Host time cut where spans begin or end, each piece labelled with
    the innermost (shortest) span over it ("none" where none is)."""
    marks = sorted([(a, 1, i) for i, (a, _, _) in enumerate(spans)]
                   + [(b, -1, i) for i, (_, b, _) in enumerate(spans)])
    out, active = [], set()
    for (t, kind, i), nxt in zip(marks, marks[1:] + [(None, 0, 0)]):
        if kind > 0:
            active.add(i)
        else:
            active.discard(i)
        if nxt[0] is not None and nxt[0] > t:
            inner = min(active, default=None,
                        key=lambda j: spans[j][1] - spans[j][0])
            out.append((t, nxt[0],
                        "none" if inner is None else spans[inner][2]))
    return out


def attribute(gap_list, pieces) -> Dict[str, float]:
    """Idle seconds per label: each gap cut by the labelled pieces of
    :func:`timeline` (both sorted by time)."""
    out: Dict[str, float] = defaultdict(float)
    j = 0
    for a, b in gap_list:
        while j < len(pieces) and pieces[j][1] <= a:
            j += 1
        t, k = a, j
        while t < b:
            if k < len(pieces) and pieces[k][0] <= t:
                end = min(b, pieces[k][1])
                out[pieces[k][2]] += end - t
                t = end
                k += 1
            else:
                end = b if k >= len(pieces) else min(b, pieces[k][0])
                out["none"] += end - t
                t = end
    return out


def find_xplane(directory: str) -> str:
    found = sorted(glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return found[-1]


def events(path: str):
    """(plane name, line name, event name, start s, end s) of every
    event in the trace (a ``.xplane.pb``, or one gzipped)."""
    import gzip

    import jax
    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            pd = jax.profiler.ProfileData.from_serialized_xspace(f.read())
    else:
        pd = jax.profiler.ProfileData.from_file(path)
    for plane in pd.planes:
        for line in plane.lines:
            for e in line.events:
                yield (plane.name, line.name, e.name, e.start_ns * 1e-9,
                       (e.start_ns + e.duration_ns) * 1e-9)


def reduce(rows, span_names: Sequence[str]) -> Summary:
    """The summary of ``rows`` as :func:`events` yields them."""
    spans, ops, modules = [], defaultdict(list), defaultdict(list)
    for plane, line, name, a, b in rows:
        if plane.startswith("/host:"):
            if name in span_names or name.startswith("decode"):
                spans.append((a, b, name))
        elif plane.startswith("/device:TPU:") and "SparseCore" not in plane:
            if line == "XLA Ops":
                ops[plane].append((a, b, name))
            elif line == "XLA Modules":
                modules[plane].append((a, b, name))
    outer = [s for s in spans if s[2] in ("step", "submit", "wait_arrival")]
    if not outer:
        raise ValueError("no host spans of the harness in the trace")
    lo, hi = min(s[0] for s in outer), max(s[1] for s in outer)
    devices = sorted(set(ops) | set(modules))
    if not devices:
        raise ValueError("no device operations in the trace")
    busy_total, prog_s, prog_n = 0.0, defaultdict(float), defaultdict(int)
    op_s, idle = defaultdict(float), defaultdict(float)
    pieces = timeline(spans)
    for dev in devices:
        mods = sorted(modules[dev])
        for a, b, name in mods:
            c = clip([(a, b)], lo, hi)
            if c:
                prog = program_of(name)
                prog_s[prog] += c[0][1] - c[0][0]
                prog_n[prog] += 1
        src = ops[dev] or mods
        busy = union(clip([(a, b) for a, b, _ in src], lo, hi))
        busy_total += sum(b - a for a, b in busy)
        starts = [a for a, _, _ in mods]
        for a, b, name in src:
            c = clip([(a, b)], lo, hi)
            if not c:
                continue
            if name.startswith(CONTAINERS):
                continue                    # its body's ops are listed
            i = _bisect(starts, a)
            owner = (program_of(mods[i][2])
                     if i >= 0 and mods[i][1] >= a else "?")
            op_s[f"{owner}:{op_name(name)}"] += c[0][1] - c[0][0]
        for name, t in attribute(gaps(busy, lo, hi), pieces).items():
            idle[name] += t
    n = len(devices)
    top = sorted(op_s.items(), key=lambda kv: -kv[1])
    return Summary(window_s=hi - lo, busy_s=busy_total / n, devices=n,
                   program_s={k: v / n for k, v in prog_s.items()},
                   program_calls={k: v // n for k, v in prog_n.items()},
                   top_ops=[(k, v / n) for k, v in top],
                   idle_by_span=sorted(((k, v / n) for k, v in idle.items()),
                                       key=lambda kv: -kv[1]))


def _bisect(starts, t) -> int:
    """Index of the last start <= t (-1 if none)."""
    import bisect
    return bisect.bisect_right(starts, t) - 1


def summarize(directory: str, span_names: Sequence[str]) -> Summary:
    return reduce(list(events(find_xplane(directory))), span_names)
