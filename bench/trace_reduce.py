"""Reduce a profiler trace (``.xplane.pb``) to device busy and idle time,
device seconds per jitted program and per operation, and the idle gaps
labelled by what the host was doing.

Device events come from the ``XLA Ops`` and ``XLA Modules`` lines of each
``/device:...`` plane; host spans (``jax.profiler.TraceAnnotation``) from
the ``/host:CPU`` plane, on the same clock.  On the ops line a loop or a
call spans the ops it runs; busy time is the union of all of them, while
time per op counts the innermost ones alone, so nothing counts twice.  The benchmark brackets its
measured window with a host span (``WINDOW``); only device time inside it
counts.
"""

from __future__ import annotations

import bisect
import dataclasses
import heapq
import glob
import os
import re
from collections import defaultdict
from typing import Dict, List, Tuple

WINDOW = "bench_window"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
_SUFFIX = re.compile(r"(\(\d+\))$")


@dataclasses.dataclass
class Op:
    name: str
    module: str
    seconds: float
    count: int
    detail: str        # the op's string stats (HLO text, name stack)


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float                    # averaged over the device planes
    n_devices: int
    programs: Dict[str, Tuple[float, int]]   # module → (seconds, launches)
    ops: List[Op]
    idle_gaps: List[Tuple[str, float]]       # label → seconds, largest first
    gaps: List[Tuple[str, float]]            # the longest single gaps

    def program_seconds(self, *needles: str) -> Tuple[float, int]:
        """Device seconds and launches of programs whose name holds any
        needle."""
        s = n = 0
        for name, (sec, cnt) in self.programs.items():
            if any(k in name for k in needles):
                s += sec
                n += cnt
        return s, n

    def op_seconds(self, needle: str, module: str = "") -> Tuple[float, int]:
        """Device seconds and calls of ops whose name or detail holds the
        needle, inside programs whose name holds ``module``."""
        s = n = 0
        for op in self.ops:
            if module not in op.module:
                continue
            if needle in op.name or needle in op.detail:
                s += op.seconds
                n += op.count
        return s, n


def find_xplane(log_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def module_name(name: str) -> str:
    return _SUFFIX.sub("", name)


def op_name(name: str) -> str:
    """The op's own name: a TPU trace names an op by its HLO line
    (``%fusion.3 = f32[...] fusion(...)``)."""
    return name.split(" = ", 1)[0].lstrip("%")


def enclosing(ops) -> set:
    """Indices of ops, (name, start, end, ...) sorted by start, that span
    another op of the same line."""
    out, stack = set(), []
    for i in sorted(range(len(ops)), key=lambda i: (ops[i][1], -ops[i][2])):
        _, a, b = ops[i][:3]
        while stack and ops[stack[-1]][2] <= a:
            stack.pop()
        if stack and b <= ops[stack[-1]][2]:
            out.add(stack[-1])
        stack.append(i)
    return out


def union(intervals) -> List[Tuple[float, float]]:
    """Merge (start, end) intervals into disjoint sorted ones."""
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def clip(intervals, lo: float, hi: float):
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if b > lo and a < hi]


def gaps_of(busy, lo: float, hi: float):
    """Idle intervals of ``busy`` (merged, sorted) inside [lo, hi]."""
    out, t = [], lo
    for a, b in busy:
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if hi > t:
        out.append((t, hi))
    return out


def _detail(ev) -> str:
    return " | ".join(v for _, v in ev.stats if isinstance(v, str))


def _host_spans(pd, window: str):
    """Host spans of the thread that recorded ``window`` (every host
    thread when none did), sorted by start, and the window span."""
    lines = [ln for p in pd.planes if p.name.startswith("/host")
             for ln in p.lines]
    evs = [[(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
            for ev in ln.events] for ln in lines]
    for spans in evs:
        win = [s for s in spans if s[0] == window]
        if win:
            return sorted(spans, key=lambda s: s[1]), win[0]
    return sorted((s for sp in evs for s in sp), key=lambda s: s[1]), None


def _labels(times, spans):
    """For increasing ``times``, the innermost host span open at each (the
    open span that started last), by one sweep over the sorted spans."""
    out, open_, k = [], [], 0
    for t in times:
        while k < len(spans) and spans[k][1] <= t:
            if spans[k][0] != WINDOW:
                heapq.heappush(open_, (-spans[k][1], spans[k][2], spans[k][0]))
            k += 1
        while open_ and open_[0][1] < t:
            heapq.heappop(open_)
        out.append(open_[0][2] if open_ else "no host span")
    return out


def reduce(pd, window: str = WINDOW, top: int = 10) -> Summary:
    """Reduce a ``jax.profiler.ProfileData`` over the host span ``window``
    (or over all device activity when no such span was recorded)."""
    spans, win = _host_spans(pd, window)
    dev_planes = [p for p in pd.planes if p.name.startswith("/device:")
                  and any(ln.name == OPS_LINE for ln in p.lines)]
    if not dev_planes:
        raise ValueError("trace has no device plane with an XLA Ops line")
    per_dev_ops, per_dev_mods = [], []
    for plane in dev_planes:
        ops, mods = [], []
        for line in plane.lines:
            if line.name == OPS_LINE:
                ops = [(op_name(ev.name), ev.start_ns,
                        ev.start_ns + ev.duration_ns, ev)
                       for ev in line.events]
            elif line.name == MODULES_LINE:
                mods = [(module_name(ev.name), ev.start_ns,
                         ev.start_ns + ev.duration_ns) for ev in line.events]
        per_dev_ops.append(ops)
        per_dev_mods.append(mods)
    if win:
        lo, hi = win[1], win[2]
    else:
        allev = [e for ops in per_dev_ops for e in ops]
        lo = min(e[1] for e in allev)
        hi = max(e[2] for e in allev)

    busy_total = 0.0
    programs: Dict[str, List[float]] = defaultdict(lambda: [0.0, 0])
    op_acc: Dict[Tuple[str, str], list] = {}
    gap_acc: Dict[str, float] = defaultdict(float)
    longest: List[Tuple[str, float]] = []
    for ops, mods in zip(per_dev_ops, per_dev_mods):
        inside = [(a, b) for _, a, b, _ in ops if b > lo and a < hi]
        busy = union(clip(inside, lo, hi))
        busy_total += sum(b - a for a, b in busy)
        mods_in = sorted((a, b, n) for n, a, b in mods if b > lo and a < hi)
        for a, b, n in mods_in:
            programs[n][0] += (min(b, hi) - max(a, lo)) * 1e-9
            programs[n][1] += 1
        starts = [a for a, _, _ in mods_in]
        names = [n for _, _, n in mods_in]
        outer = enclosing(ops)
        for k, (name, a, b, ev) in enumerate(ops):
            if b <= lo or a >= hi or k in outer:
                continue
            # the program an op ran in: the module interval holding its start
            i = bisect.bisect_right(starts, a) - 1
            mod = names[i] if i >= 0 and mods_in[i][1] >= a else ""
            key = (name, mod)
            if key not in op_acc:
                op_acc[key] = [0.0, 0, _detail(ev)]
            op_acc[key][0] += (min(b, hi) - max(a, lo)) * 1e-9
            op_acc[key][1] += 1
        gaps = gaps_of(busy, lo, hi)
        hosts = _labels([(a + b) / 2 for a, b in gaps], spans)
        for (a, b), host in zip(gaps, hosts):
            i = bisect.bisect_left(starts, b)
            nxt = names[i] if i < len(names) else "end of window"
            label = f"{host} -> {nxt}"
            gap_acc[label] += (b - a) * 1e-9
            longest.append((label, (b - a) * 1e-9))
    n = len(dev_planes)
    ops_list = sorted((Op(k[0], k[1], v[0] / n, v[1], v[2])
                       for k, v in op_acc.items()),
                      key=lambda o: -o.seconds)
    return Summary(
        window_s=(hi - lo) * 1e-9, busy_s=busy_total * 1e-9 / n, n_devices=n,
        programs={k: (v[0] / n, v[1]) for k, v in programs.items()},
        ops=ops_list,
        idle_gaps=sorted(((k, v / n) for k, v in gap_acc.items()),
                         key=lambda kv: -kv[1])[:top],
        gaps=sorted(longest, key=lambda kv: -kv[1])[:top])


def breakdown(s: Summary, top: int = 10) -> dict:
    """The result line's ``breakdown``: device ops that took most time and
    the idle gaps by what the host was doing."""
    agg: Dict[str, float] = defaultdict(float)
    for op in s.ops:
        agg[f"{op.module}:{op.name}" if op.module else op.name] += op.seconds
    ops = sorted(agg.items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[k, v] for k, v in ops],
            "idle_gaps": [[k, v] for k, v in s.idle_gaps[:top]]}
