"""Run one cell of the benchmark once.

    python3 benchmark/run.py --workload g500-s21.converge --seed 7 \\
        --seconds 40 --trace 0

The cell is looked up in ``BENCHMARK.json``; its configuration, traffic
mix and per-layer metric readers are found by name under ``benchmark/``
(see ``benchmark/__init__.py``). A run:

1. finds the chips the cell asks for, or exits 2 with no result line;
2. points JAX's persistent compile cache at ``bench_cache/benchmark`` in
   the checkout, whatever the environment says;
3. lets the traffic's runner set up (data, program state, warm-up);
   ``setup_s`` runs from the start of this process to the window;
4. measures for ``--seconds``; with ``--trace 1`` the window runs under
   the JAX profiler and the per-layer metrics are reported instead of
   the end-to-end ones;
5. reads the device's peak memory, frees the program's state and has
   the runner compare what the window produced with the reference;
6. prints earlier lines of detail, then, as its last line of standard
   output, one JSON object: ``correct``, ``attempted``, ``failed``,
   ``metrics``, ``device`` (and ``breakdown`` when traced), and last
   ``checks``: each number compared, with its limit. The same numbers
   are the last lines of standard error.
"""

from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(HERE)
CACHE_DIR = os.path.join(CHECKOUT, "bench_cache", "benchmark", "xla")
TRACE_DIR = os.path.join(CHECKOUT, "bench_cache", "benchmark", "trace")


def emit(obj: dict) -> None:
    print(json.dumps(obj), flush=True)


def load_json(*parts) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def cell_spec(workload: str) -> dict:
    """The cell's entries of BENCHMARK.json and the files they name."""
    bench = load_json(CHECKOUT, "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"run.py: no workload {workload!r} in "
                         f"BENCHMARK.json")
    cell = cells[workload]

    def applies(metric):
        return workload in metric.get("workloads", [workload])

    return {
        "cell": cell,
        "config": load_json(HERE, "configs", cell["config"] + ".json"),
        "traffic": load_json(HERE, "traffic", cell["traffic"] + ".json"),
        "end_to_end": [m for m in bench["end_to_end"] if applies(m)],
        "per_layer": [m for m in bench["per_layer"] if applies(m)],
    }


def require_chips(chips: int) -> dict:
    """The cell's chips, as JAX reports them; no accelerator ends the run."""
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"run.py: JAX found {dev.platform}, not a TPU; no result",
              file=sys.stderr)
        raise SystemExit(2)
    if len(devices) < chips:
        print(f"run.py: the cell needs {chips} chips, JAX found "
              f"{len(devices)}; no result", file=sys.stderr)
        raise SystemExit(2)
    emit({"devices_visible": len(devices)})
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": chips}


def use_compile_cache() -> None:
    """JAX's persistent cache at one fixed path in the checkout, every
    program written to it however quick its compile, and nothing
    evicted (an eviction limit set in the environment is dropped)."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    os.environ.pop("JAX_ENABLE_COMPILATION_CACHE", None)
    os.environ.pop("JAX_COMPILATION_CACHE_MAX_SIZE", None)
    import jax

    os.makedirs(CACHE_DIR, exist_ok=True)
    jax.config.update("jax_enable_compilation_cache", True)
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    jax.config.update("jax_compilation_cache_max_size", -1)


class Compiles:
    """JAX's compile events: backend compiles and persistent-cache hits.
    A compile event that is not a cache hit is a fresh compile."""

    COMPILE = "/jax/core/compile/backend_compile_duration"
    HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        import jax

        self.compiles = 0
        self.hits = 0
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, event, **_):
        if event == self.HIT:
            self.hits += 1

    def _duration(self, event, duration, **_):
        if event == self.COMPILE:
            self.compiles += 1

    def snapshot(self) -> tuple[int, int]:
        return self.compiles, self.hits


class Window:
    """The measured window: the runner enters it once, where timing
    starts, and leaves it where the last timed operation ended. Under
    ``trace`` the profiler records it, marked by a host annotation."""

    def __init__(self, trace: bool, compiles: Compiles):
        self.trace = trace
        self.compiles = compiles
        self.start = self.end = None
        self.setup_s = None
        self._stack = None

    def __enter__(self):
        import jax

        from benchmark import trace_reduce

        self._stack = contextlib.ExitStack()
        if self.trace:
            shutil.rmtree(TRACE_DIR, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(TRACE_DIR, profiler_options=opts)
            self._stack.callback(jax.profiler.stop_trace)
            self._stack.enter_context(
                jax.profiler.TraceAnnotation(trace_reduce.WINDOW))
        self.before = self.compiles.snapshot()
        self.start = time.perf_counter()
        self.setup_s = self.start - T0
        return self

    def __exit__(self, *exc):
        self.end = time.perf_counter()
        self.after = self.compiles.snapshot()
        self._stack.close()
        return False

    @property
    def seconds(self) -> float:
        return self.end - self.start

    def fresh_compiles(self) -> dict:
        c = self.after[0] - self.before[0]
        h = self.after[1] - self.before[1]
        return {"compile_events": c, "cache_hits": h,
                "fresh_compiles": max(c - h, 0)}


def peak_bytes(chips: int) -> int | None:
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.devices()[:chips]]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def run_cell(spec: dict, seed: int, seconds: float, trace: bool,
             device: dict) -> dict:
    """Set up, measure, check. Returns the result object."""
    from benchmark import metrics as readers
    from benchmark import trace_reduce

    compiles = Compiles()
    runner = importlib.import_module(
        "benchmark.runners." + spec["traffic"]["runner"])
    run = runner.Run(spec["config"], spec["traffic"], seed)
    run.setup()
    setup_compiles, setup_hits = compiles.snapshot()
    window = Window(trace, compiles)
    run.measure(window, seconds)
    emit({"setup": {"compile_events": setup_compiles,
                    "cache_hits": setup_hits},
          "window": {"seconds": window.seconds, **window.fresh_compiles()}})
    device = dict(device, memory_peak_bytes=peak_bytes(device["count"]))
    reduction = None
    if trace:
        reduction = trace_reduce.reduce_dir(TRACE_DIR)
        device.update(busy_s=reduction.busy_s, window_s=reduction.window_s)
        emit({"trace": {"programs": reduction.programs,
                        "busy_s": reduction.busy_s,
                        "window_s": reduction.window_s}})
    summary = run.summary(window)
    run.release()
    gc.collect()
    checks = run.check()
    correct = bool(checks) and all(c["value"] <= c["limit"]
                                   for c in checks.values())
    correct = (correct and summary["attempted"] > 0
               and summary["failed"] == 0)
    metrics = {}
    if trace:
        record = Record(spec["config"], device, reduction, summary["layer"])
        for m in spec["per_layer"]:
            value = readers.load(m["name"])(record)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        values = dict(summary["end_to_end"], setup_s=window.setup_s)
        for m in spec["end_to_end"]:
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": m["unit"]}
    out = {"correct": correct, "attempted": summary["attempted"],
           "failed": summary["failed"], "metrics": metrics,
           "device": device}
    if reduction is not None:
        out["breakdown"] = reduction.breakdown()
    out["checks"] = checks
    return out


class Record:
    """What a per-layer metric reader may read: the configuration, the
    device, the trace reduction (None when the run was not traced) and
    the runner's per-layer counts (``layer``)."""

    def __init__(self, config, device, reduction, layer):
        self.config = config
        self.device = device
        self.trace = reduction
        self.layer = layer


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path[0] = CHECKOUT  # import benchmark.* as a package
    spec = cell_spec(args.workload)
    use_compile_cache()
    device = require_chips(int(spec["cell"]["chips"]))
    out = run_cell(spec, args.seed, args.seconds, bool(args.trace), device)
    for name, c in out["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    print(f"correct {out['correct']}", file=sys.stderr, flush=True)
    emit(out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
