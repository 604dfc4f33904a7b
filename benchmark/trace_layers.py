"""Per-layer readings of a profiler trace that ``trace_reduce`` does not
make, both inside the measured window (the host event ``bench.window``):

- ``host_spans``: host event name -> (seconds inside the window, events
  that overlap it). The program's spans (``protocol_tpu/utils/trace.py``)
  are host events of the trace, named exactly as the span;
- ``scopes``: the scope path of a device operation (the ``tf_op`` stat
  of its event metadata, read by ``benchmark/xspace.py``) -> its self
  seconds inside the window, summed over the operations of the
  ``XLA Ops`` line and averaged over the chips, as ``trace_reduce``
  sums operations. Operations without a scope path go under
  ``NO_SCOPE``.

A scope path reads ``jit(<function>)/while/body/<named scope>/.../<op>``:
its first part names the outermost jitted function, and the named
scopes of the program (``jax.named_scope``) are parts of it. Unlike an
operation's HLO name, it does not change when XLA renumbers its
instructions.
"""

from __future__ import annotations

from dataclasses import dataclass, field

from benchmark import trace_reduce, xspace

NO_SCOPE = "(no scope)"
SCOPE_STAT = "tf_op"


@dataclass
class Layers:
    window_s: float
    host_spans: dict = field(default_factory=dict)  # name -> (s, count)
    scopes: dict = field(default_factory=dict)      # scope path -> s

    def span_ms(self, name: str) -> float | None:
        """Milliseconds per event of host span ``name`` in the window;
        None where the trace holds no such event."""
        seconds, count = self.host_spans.get(name, (0.0, 0))
        return 1e3 * seconds / count if count else None

    def scope_seconds(self, function: str, scope: str) -> float | None:
        """Device self seconds of the operations of jitted ``function``
        whose scope path has ``scope`` as one of its parts; None where
        no operation has such a path. A fused operation's path may join
        the paths of its parts with ``;`` after their common prefix
        (``jit(f)/a/x;b/y``): it counts under the scopes of the first."""
        top = f"jit({function})"
        found = [s for path, s in self.scopes.items()
                 if _parts(path)[0] == top and scope in _parts(path)]
        return sum(found) if found else None


def _parts(path: str) -> list:
    return path.split(";", 1)[0].split("/")


def reduce_space(space) -> Layers:
    host = [p for p in space.planes if p.name == trace_reduce.HOST_PLANE]
    intervals = []
    for plane in host:
        names = xspace.event_names(plane)
        intervals += [(names.get(mid, ""), s, e)
                      for mid, s, e in xspace.events(plane)]
    windows = [(s, e) for name, s, e in intervals
               if name == trace_reduce.WINDOW]
    if len(windows) != 1:
        raise ValueError(f"expected one {trace_reduce.WINDOW!r} host event, "
                         f"found {len(windows)}")
    lo, hi = windows[0]
    spans: dict = {}
    for name, s, e in intervals:
        d = min(e, hi) - max(s, lo)
        if name != trace_reduce.WINDOW and d > 0:
            sec, k = spans.get(name, (0.0, 0))
            spans[name] = (sec + d * 1e-9, k + 1)
    devices = [p for p in space.planes
               if p.name.startswith(trace_reduce.DEVICE_PREFIX)]
    scopes: dict = {}
    for plane in devices:
        path = xspace.stat_strings(plane, SCOPE_STAT)
        ops = [trace_reduce.Interval(str(mid), s, e) for mid, s, e in
               xspace.events(plane, trace_reduce.OPS_LINE)]
        for mid, d in trace_reduce.self_times(ops, lo, hi):
            key = path.get(int(mid)) or NO_SCOPE
            scopes[key] = scopes.get(key, 0.0) + d * 1e-9
    chips = max(len(devices), 1)
    return Layers(window_s=(hi - lo) * 1e-9, host_spans=spans,
                  scopes={k: v / chips for k, v in scopes.items()})


def reduce_file(path: str) -> Layers:
    return reduce_space(xspace.read(path))


def of(rec) -> Layers | None:
    """The readings of the run's own trace, made once per run and kept
    on its record; None for a run that was not traced."""
    if rec.trace is None:
        return None
    if getattr(rec, "layers", None) is None:
        from benchmark.run import TRACE_DIR

        rec.layers = reduce_file(trace_reduce.find_xplane(TRACE_DIR))
    return rec.layers
