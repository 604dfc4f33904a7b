"""From a JAX profiler trace to device busy time, program time and gaps.

The harness records the measured window with ``jax.profiler`` and marks
it on the host with a ``TraceAnnotation`` named ``WINDOW``. This module
reads the ``.xplane.pb`` with ``jax.profiler.ProfileData`` (nothing but
JAX) and reduces it:

- device planes are those named ``/device:TPU:<i>``; on each, the
  ``XLA Ops`` line holds one event per device operation and the
  ``XLA Modules`` line one event per run of a compiled program;
- busy time is the union of the operation intervals inside the window,
  averaged over the chips used; idle share = 1 - busy / window;
- operations nest (a ``while`` holds its body's operations), so an
  operation's time in the breakdown is its self time: its duration less
  that of the operations inside it; it is named by its HLO instruction
  (the text before `` = ``);
- a program's device time is the sum of its module events inside the
  window; a program is found by a name fragment (``jit_<function>`` as
  XLA names a jitted function);
- each idle gap of the busy union of at least ``MIN_GAP_NS`` is put
  down to what the host was doing: the shortest host event that covers
  at least half of the gap. Shorter gaps, between operations of one
  program, are summed under ``SHORT_GAPS``.
"""

from __future__ import annotations

import glob
import os
from dataclasses import dataclass, field

WINDOW = "bench.window"
DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
TOP = 10
MIN_GAP_NS = 10_000
SHORT_GAPS = "(gaps under 10 us)"
NO_HOST = "(no host event)"


@dataclass
class Interval:
    name: str
    start: float  # ns, the profile's own clock
    end: float


@dataclass
class Reduction:
    window_s: float
    busy_s: float
    chips: int
    programs: dict = field(default_factory=dict)  # module name -> (s, runs)
    ops: dict = field(default_factory=dict)       # op name -> s
    gaps: dict = field(default_factory=dict)      # host event -> idle s

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def program_seconds(self, fragment: str) -> tuple[float, int]:
        """Device seconds and runs of every module whose name holds
        ``fragment``."""
        s, runs = 0.0, 0
        for name, (sec, k) in self.programs.items():
            if fragment in name:
                s += sec
                runs += k
        return s, runs

    def breakdown(self) -> dict:
        top = sorted(self.ops.items(), key=lambda kv: -kv[1])[:TOP]
        gaps = sorted(self.gaps.items(), key=lambda kv: -kv[1])[:TOP]
        return {"device_ops": [[k, v] for k, v in top],
                "idle_gaps": [[k, v] for k, v in gaps]}


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def _events(line) -> list[Interval]:
    return [Interval(e.name, e.start_ns, e.start_ns + e.duration_ns)
            for e in line.events]


def read_planes(path: str):
    """(device lines by plane name, host intervals) of one trace file,
    ``.xplane.pb`` or gzipped ``.xplane.pb.gz``."""
    import gzip

    from jax.profiler import ProfileData

    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            pd = ProfileData.from_serialized_xspace(f.read())
    else:
        pd = ProfileData.from_file(path)
    device, host = {}, []
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            device[plane.name] = {line.name: _events(line)
                                  for line in plane.lines}
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                host.extend(_events(line))
    return device, host


def union(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    """Merged [start, end) pieces of ``intervals`` clipped to [lo, hi)."""
    pieces = sorted((max(i.start, lo), min(i.end, hi)) for i in intervals
                    if i.end > lo and i.start < hi)
    merged: list[list[float]] = []
    for s, e in pieces:
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def op_name(text: str) -> str:
    """``%fusion.12 = f32[...] fusion(...)`` -> ``fusion.12``."""
    return text.split(" = ", 1)[0].lstrip("%")


def self_times(intervals, lo: float, hi: float):
    """(op name, self ns) of each interval clipped to [lo, hi): its
    length less the length of the intervals nested inside it."""
    clipped = sorted(((max(i.start, lo), -min(i.end, hi), i.name)
                      for i in intervals if i.end > lo and i.start < hi))
    out = []
    stack: list = []  # [end, index into out]
    for start, neg_end, name in clipped:
        end = -neg_end
        while stack and stack[-1][0] <= start:
            stack.pop()
        if stack:
            out[stack[-1][1]][1] -= end - start
        out.append([op_name(name), end - start])
        stack.append([end, len(out) - 1])
    return out


class _Host:
    """Host events as arrays, for attributing gaps."""

    def __init__(self, host: list[Interval]):
        import numpy as np

        keep = [h for h in host if h.name != WINDOW]
        self.names = [h.name for h in keep]
        self.start = np.array([h.start for h in keep], dtype=np.float64)
        self.end = np.array([h.end for h in keep], dtype=np.float64)

    def attribute(self, g0: float, g1: float) -> str:
        import numpy as np

        if g1 - g0 < MIN_GAP_NS:
            return SHORT_GAPS
        if not self.names:
            return NO_HOST
        cover = np.minimum(self.end, g1) - np.maximum(self.start, g0)
        ok = np.flatnonzero(cover >= 0.5 * (g1 - g0))
        if not len(ok):
            return NO_HOST
        best = ok[np.argmin(self.end[ok] - self.start[ok])]
        return self.names[best]


def reduce_planes(device: dict, host: list[Interval]) -> Reduction:
    windows = [h for h in host if h.name == WINDOW]
    if len(windows) != 1:
        raise ValueError(f"expected one {WINDOW!r} host event, found "
                         f"{len(windows)}")
    lo, hi = windows[0].start, windows[0].end
    window_s = (hi - lo) * 1e-9
    busy = 0.0
    programs: dict = {}
    ops: dict = {}
    gaps: dict = {}
    hosts = _Host(host)
    for lines in device.values():
        pieces = union(lines.get(OPS_LINE, []), lo, hi)
        busy += sum(e - s for s, e in pieces) * 1e-9
        for ev in lines.get(MODULES_LINE, []):
            d = min(ev.end, hi) - max(ev.start, lo)
            if d > 0:
                sec, k = programs.get(ev.name, (0.0, 0))
                programs[ev.name] = (sec + d * 1e-9, k + 1)
        for name, d in self_times(lines.get(OPS_LINE, []), lo, hi):
            ops[name] = ops.get(name, 0.0) + d * 1e-9
        edges = [lo] + [x for p in pieces for x in p] + [hi]
        for g0, g1 in zip(edges[::2], edges[1::2]):
            if g1 > g0:
                name = hosts.attribute(g0, g1)
                gaps[name] = gaps.get(name, 0.0) + (g1 - g0) * 1e-9
    chips = max(len(device), 1)
    return Reduction(window_s=window_s, busy_s=busy / chips,
                     chips=len(device), programs=programs,
                     ops={k: v / chips for k, v in ops.items()},
                     gaps={k: v / chips for k, v in gaps.items()})


def reduce_dir(log_dir: str) -> Reduction:
    return reduce_planes(*read_planes(find_xplane(log_dir)))
