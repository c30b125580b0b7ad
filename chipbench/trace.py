"""Read a JAX profiler trace into what the per-layer metrics need.

``load_trace`` reads the newest ``*.xplane.pb`` under a trace directory
with ``jax.profiler.ProfileData`` and keeps:

* the device's operations (the ``XLA Ops`` line of each ``/device:TPU:n``
  plane): name, start, duration, and the program (``XLA Modules`` line)
  whose interval holds it;
* the device's program executions (``XLA Modules``);
* the host's spans (every event with a duration on the host plane's
  threads), which name what the host was doing in a device gap.

``TraceContext`` is what each ``metrics/<name>.py`` reader gets: the
trace, the traced run's counters, the configuration and traffic, the
window, the chip's peaks and the operation counts (``flops``).
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Any, Dict, List, Tuple

import numpy as np

# how the Pallas weighted-aggregation kernel shows in the trace: a Mosaic
# custom call (the only one on these paths; its op text carries no
# kernel name)
WAGG_KERNEL = 'custom_call_target="tpu_custom_call"'


@dataclasses.dataclass
class Events:
    """Events of one kind, as parallel arrays (ns on the trace clock)."""
    names: List[str]
    start: np.ndarray
    dur: np.ndarray

    @classmethod
    def of(cls, rows: List[Tuple[str, float, float]]) -> "Events":
        rows = sorted(rows, key=lambda r: r[1])
        return cls([r[0] for r in rows],
                   np.asarray([r[1] for r in rows], np.float64),
                   np.asarray([r[2] for r in rows], np.float64))

    def __len__(self) -> int:
        return len(self.names)

    @property
    def end(self) -> np.ndarray:
        return self.start + self.dur


@dataclasses.dataclass
class Trace:
    ops: Events                 # device operations
    op_module: List[str]        # program holding each op ("" if none)
    modules: Events             # device program executions
    host: Events                # host spans
    devices: int                # device planes seen


def _device_planes(pd):
    return [p for p in pd.planes if p.name.startswith("/device:TPU:")
            or p.name.startswith("/device:CPU:")]


def _line_rows(line) -> List[Tuple[str, float, float]]:
    return [(ev.name, float(ev.start_ns), float(ev.duration_ns))
            for ev in line.events]


def _assign_modules(ops: Events, modules: Events) -> List[str]:
    """Name of the module execution whose interval holds each op's
    start (modules on one device do not overlap)."""
    if not len(modules):
        return [""] * len(ops)
    idx = np.searchsorted(modules.start, ops.start, side="right") - 1
    out = []
    for i, k in enumerate(idx):
        inside = k >= 0 and ops.start[i] <= modules.end[k]
        out.append(modules.names[k] if inside else "")
    return out


def load_trace(trace_dir: str) -> Trace:
    from jax.profiler import ProfileData

    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no *.xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(files[-1])
    ops, mods, host = [], [], []
    planes = _device_planes(pd)
    for plane in planes:
        for line in plane.lines:
            if line.name == "XLA Ops":
                ops += _line_rows(line)
            elif line.name == "XLA Modules":
                mods += _line_rows(line)
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                host += [r for r in _line_rows(line) if r[2] > 0]
    ops_e, mods_e = Events.of(ops), Events.of(mods)
    return Trace(ops_e, _assign_modules(ops_e, mods_e), mods_e,
                 Events.of(host), max(1, len(planes)))


def short_op(name: str, width: int = 100) -> str:
    """An HLO op's trace name without layouts or operands: ``%fusion.3 =
    f32[5,512] fusion``."""
    name = re.sub(r"\{[^{}]*\}", "", name)
    m = re.match(r"(%\S+ = (?:\([^()]*\)|\S+) [\w-]+)", name)
    return (m.group(1) if m else name)[:width]


def union_ns(start: np.ndarray, end: np.ndarray) -> float:
    """Length of the union of intervals [start, end)."""
    if not len(start):
        return 0.0
    order = np.argsort(start)
    s, e = start[order], end[order]
    total, cur_s, cur_e = 0.0, s[0], e[0]
    for a, b in zip(s[1:], e[1:]):
        if a > cur_e:
            total += cur_e - cur_s
            cur_s, cur_e = a, b
        elif b > cur_e:
            cur_e = b
    return total + (cur_e - cur_s)


def gaps(start: np.ndarray, end: np.ndarray, t0: float, t1: float
         ) -> List[Tuple[float, float]]:
    """Idle intervals of [t0, t1) outside the union of the intervals."""
    order = np.argsort(start)
    out, cur = [], t0
    for a, b in zip(start[order], end[order]):
        if a > cur:
            out.append((cur, min(a, t1)))
        cur = max(cur, b)
    if cur < t1:
        out.append((cur, t1))
    return [g for g in out if g[1] > g[0]]


class TraceContext:
    """What a metric reader gets.  ``window`` is the traced run's span on
    the trace clock: from the first to the last device operation, or
    the ``bench.run`` host span when there is one."""

    def __init__(self, trace: Trace, *, cell, stats: Dict[str, Any],
                 peaks: Dict[str, float], flops):
        self.trace, self.cell, self.stats = trace, cell, stats
        self.config, self.traffic = cell.config, cell.traffic
        self.peaks, self.flops = peaks, flops
        runs = [i for i, n in enumerate(trace.host.names)
                if n == "bench.run"]
        if runs:
            i = runs[0]
            self.t0 = trace.host.start[i]
            self.t1 = trace.host.end[i]
        elif len(trace.ops):
            self.t0, self.t1 = trace.ops.start.min(), trace.ops.end.max()
        else:
            self.t0 = self.t1 = 0.0
        self.window_s = (self.t1 - self.t0) * 1e-9
        busy = union_ns(np.clip(trace.ops.start, self.t0, self.t1),
                        np.clip(trace.ops.end, self.t0, self.t1))
        self.busy_s = busy * 1e-9 / trace.devices

    # -- selections -----------------------------------------------------------
    def ops_matching(self, name_has: str) -> List[Tuple[str, str, float]]:
        """(name, program, seconds) of each device op whose name holds
        ``name_has``."""
        tr = self.trace
        return [(n, tr.op_module[i], tr.ops.dur[i] * 1e-9)
                for i, n in enumerate(tr.ops.names) if name_has in n]

    def module_time_s(self, name_has: str) -> Tuple[float, int]:
        """(seconds, executions) of programs whose name holds
        ``name_has``."""
        m = self.trace.modules
        sel = [i for i, n in enumerate(m.names) if name_has in n]
        return float(np.sum(m.dur[sel])) * 1e-9, len(sel)

    # -- breakdown ------------------------------------------------------------
    def breakdown(self, top: int = 10) -> Dict[str, List]:
        """The ``top`` device ops by time (loops, which hold other ops,
        left out; HLO text cut to its instruction and shapes) and the
        ``top`` longest idle gaps, each named by its host span."""
        tr = self.trace
        by_op: Dict[str, float] = {}
        for n, d in zip(tr.ops.names, tr.ops.dur):
            if " while(" in n:
                continue
            n = short_op(n)
            by_op[n] = by_op.get(n, 0.0) + d
        ops = sorted(by_op.items(), key=lambda kv: -kv[1])[:top]
        idle = gaps(tr.ops.start, tr.ops.end, self.t0, self.t1)
        idle.sort(key=lambda g: g[0] - g[1])
        named = [[self.host_span_at(a, b), (b - a) * 1e-9]
                 for a, b in idle[:top]]
        return {"device_ops": [[n, d * 1e-9] for n, d in ops],
                "idle_gaps": named}

    def host_span_at(self, a: float, b: float) -> str:
        """The innermost host span that covers most of [a, b)."""
        h = self.trace.host
        best, best_key = "(no host span)", None
        for n, s, e in zip(h.names, h.start, h.end):
            cover = min(e, b) - max(s, a)
            if cover <= 0.5 * (b - a):
                continue
            key = e - s
            if best_key is None or key < best_key:
                best, best_key = n, key
        return best
