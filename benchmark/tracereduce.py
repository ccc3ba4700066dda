"""From a JAX profiler trace (`.xplane.pb`) to the device numbers the
per-layer metrics read: busy time as the union of the intervals in which
an operation ran on the device, per-name device time, executions of each
compiled program, and the idle gaps with what the host was doing in them.

Device planes are named ``/device:TPU:<n>``; their ``XLA Ops`` line holds
one event per operation and their ``XLA Modules`` line one per program
execution. The harness opens a ``bench.window`` annotation around the
traced window, which pins the window's bounds on the trace's clock.
"""

from __future__ import annotations

import glob
import os
import re
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Callable, Dict, Iterable, List, Optional, Sequence, Tuple

Event = Tuple[str, float, float]           # (name, start_ns, end_ns)

WINDOW_ANNOTATION = "bench.window"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def trace_options():
    """Host spans and the device, without the Python tracer: it would
    time every Python call of the host path and slow it by several
    times."""
    import jax

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    return options


def load(profile_dir: str):
    import jax

    paths = sorted(glob.glob(os.path.join(profile_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {profile_dir}")
    return jax.profiler.ProfileData.from_file(paths[-1])


def events(profile, plane_pred: Callable[[str], bool],
           line_pred: Callable[[str], bool]) -> List[Event]:
    out: List[Event] = []
    for plane in profile.planes:
        if not plane_pred(plane.name):
            continue
        for line in plane.lines:
            if not line_pred(line.name):
                continue
            for ev in line.events:
                out.append((ev.name, float(ev.start_ns),
                            float(ev.start_ns + ev.duration_ns)))
    return out


_HLO = re.compile(r"^(%?[\w.\-]+) = (\w+\[[^\]]*\])?\S* ([\w\-]+)\(")


def op_label(name: str) -> str:
    """A device op event is named by its whole HLO instruction; keep its
    name, result shape and opcode (`%fusion.146 s32[1048576] fusion`)."""
    m = _HLO.match(name)
    if m is None:
        return name[:120]
    return " ".join(part for part in m.groups() if part)


def is_device_plane(name: str) -> bool:
    return name.startswith("/device:TPU:")


def merge(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float,
                                                                 float]]:
    """Disjoint sorted union of [start, end) intervals."""
    out: List[List[float]] = []
    for start, end in sorted(intervals):
        if end <= start:
            continue
        if out and start <= out[-1][1]:
            out[-1][1] = max(out[-1][1], end)
        else:
            out.append([start, end])
    return [(a, b) for a, b in out]


def covered(intervals: Sequence[Tuple[float, float]]) -> float:
    return sum(b - a for a, b in merge(intervals))


def gaps(busy: Sequence[Tuple[float, float]], lo: float,
         hi: float) -> List[Tuple[float, float]]:
    """The idle intervals of [lo, hi) around a disjoint sorted `busy`."""
    out, t = [], lo
    for a, b in busy:
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if hi > t:
        out.append((t, hi))
    return out


def per_name(evs: Sequence[Event]) -> Dict[str, float]:
    """Summed duration per event name (ns)."""
    out: Dict[str, float] = defaultdict(float)
    for name, start, end in evs:
        out[name] += end - start
    return dict(out)


def attribute(idle: Sequence[Tuple[float, float]],
              host: Sequence[Event]) -> List[Tuple[str, float]]:
    """Name each idle gap by the host span name whose events cover at
    least half of it, else "unattributed"; returns (name, seconds)."""
    host = sorted(host, key=lambda e: e[1])
    out = []
    for lo, hi in idle:
        cover: Dict[str, List[Tuple[float, float]]] = defaultdict(list)
        for name, start, end in host:
            if start >= hi:
                break
            if end > lo:
                cover[name].append((max(start, lo), min(end, hi)))
        best, best_ns = "unattributed", 0.5 * (hi - lo)
        for name, spans in cover.items():
            ns = covered(spans)
            if ns >= best_ns:
                best, best_ns = name, ns
        out.append((best, (hi - lo) / 1e9))
    return out


@dataclass
class DeviceWindow:
    """The reduction of one traced window, averaged over the chips."""

    window_s: float
    busy_s: float
    op_ns: Dict[str, float] = field(default_factory=dict)
    module_ns: Dict[str, float] = field(default_factory=dict)
    module_runs: Dict[str, int] = field(default_factory=dict)
    idle_gaps: List[Tuple[str, float]] = field(default_factory=list)

    @property
    def idle_pct(self) -> float:
        return 100.0 * (1.0 - self.busy_s / self.window_s)

    def ops_matching(self, needle: str) -> float:
        """Device seconds of the ops whose name holds `needle`."""
        return sum(ns for name, ns in self.op_ns.items()
                   if needle in name) / 1e9

    def runs_matching(self, needle: str) -> Tuple[int, float]:
        """(executions, device seconds) of the programs whose name holds
        `needle`."""
        runs = sum(n for name, n in self.module_runs.items()
                   if needle in name)
        secs = sum(ns for name, ns in self.module_ns.items()
                   if needle in name) / 1e9
        return runs, secs


def reduce(profile, window_s: float,
           plane_pred: Callable[[str], bool] = is_device_plane,
           ops_line: Callable[[str], bool] = lambda n: n == OPS_LINE,
           modules_line: Callable[[str], bool] = lambda n: n == MODULES_LINE,
           host_plane: Callable[[str], bool] = lambda n: n == "/host:CPU",
           ) -> Optional[DeviceWindow]:
    """Busy share, op and program times and idle gaps inside the
    harness's window annotation; None when the trace holds no device
    operation. `plane_pred` and the line predicates let a test point the
    reduction at the CPU's own planes."""
    host = events(profile, host_plane, lambda n: True)
    marks = [e for e in host if e[0] == WINDOW_ANNOTATION]
    if marks:
        lo, hi = marks[0][1], marks[0][2]
    else:
        lo, hi = None, None
    planes = [p.name for p in profile.planes if plane_pred(p.name)]
    busy_ns, op_ns, mod_ns, runs = 0.0, defaultdict(float), \
        defaultdict(float), defaultdict(int)
    all_idle: List[Tuple[str, float]] = []
    any_op = False
    for plane in planes:
        only = (lambda n, p=plane: n == p)
        ops = events(profile, only, ops_line)
        if not ops:
            continue
        any_op = True
        w_lo = lo if lo is not None else min(e[1] for e in ops)
        w_hi = hi if hi is not None else max(e[2] for e in ops)
        ops = [(op_label(n), max(a, w_lo), min(b, w_hi)) for n, a, b in ops
               if b > w_lo and a < w_hi]
        intervals = merge((a, b) for _, a, b in ops)
        busy_ns += covered(intervals)
        for name, ns in per_name(ops).items():
            op_ns[name] += ns
        for name, a, b in events(profile, only, modules_line):
            if b > w_lo and a < w_hi:
                mod_ns[name] += min(b, w_hi) - max(a, w_lo)
                runs[name] += 1
        busy_host = [e for e in host if e[0] != WINDOW_ANNOTATION]
        longest = sorted(gaps(intervals, w_lo, w_hi),
                         key=lambda g: g[0] - g[1])[:10]
        all_idle += attribute(longest, busy_host)
    if not any_op:
        return None
    n = len(planes)
    all_idle.sort(key=lambda g: -g[1])
    return DeviceWindow(
        window_s=window_s, busy_s=busy_ns / 1e9 / n,
        op_ns={k: v / n for k, v in op_ns.items()},
        module_ns={k: v / n for k, v in mod_ns.items()},
        module_runs={k: v // n for k, v in runs.items()},
        idle_gaps=all_idle)


def breakdown(dw: DeviceWindow, top: int = 10) -> Dict[str, List]:
    """The `--trace 1` result's `breakdown`: the device ops that took most
    time and the longest idle gaps by what the host was doing."""
    ops = sorted(dw.op_ns.items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[name, ns / 1e9] for name, ns in ops],
            "idle_gaps": [[name, s] for name, s in dw.idle_gaps[:top]]}
