"""Batch converges: a closed loop of cold EigenTrust converges.

Set-up generates the configuration's graph from the seed, builds a
fresh routing plan (the load phase: no plan cache), and runs one
converge to load or compile the program. The window then runs
back-to-back converges from uniform scores through
``JaxRoutedBackend.converge_edges`` on that plan, each ending in the
host fetch of the node-order score vector. ``converge_s`` is the window
over the number of converges it completed.

Every converge of the window is compared with the float64 reference
over the same graph: the largest relative L1 distance is the number
checked. A converge that stops without reaching the tolerance counts as
failed.
"""

from __future__ import annotations

import json
import resource
import time

import numpy as np

from benchmark import kronecker, reference

ANNOTATION = "bench.converge"


class Run:
    def __init__(self, config: dict, traffic: dict, seed: int):
        self.config = config
        self.seed = seed
        self.et = config["eigentrust"]
        self.times: dict = {}
        self.results: list = []

    def setup(self) -> None:
        from protocol_tpu.backend import JaxRoutedBackend
        from protocol_tpu.ops.routed import build_routed_operator
        from protocol_tpu.utils import trace

        trace.enable()
        t = time.perf_counter()
        self.n, self.src, self.dst, self.val = kronecker.graph_for(
            self.config, self.seed)
        self.valid = np.ones(self.n, dtype=bool)
        self.times["graph_s"] = time.perf_counter() - t
        t = time.perf_counter()
        self.op = build_routed_operator(self.n, self.src, self.dst,
                                        self.val, self.valid)
        self.times["plan_s"] = time.perf_counter() - t
        self.backend = JaxRoutedBackend()
        t = time.perf_counter()
        self._converge()
        self.times["warm_s"] = time.perf_counter() - t
        rss = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024
        print(json.dumps({"setup": dict(self.times,
                                        host_peak_rss_bytes=rss)}),
              flush=True)

    def _converge(self):
        return self.backend.converge_edges(
            self.n, self.src, self.dst, self.val, self.valid,
            self.et["initial_score"], self.et["max_iterations"],
            tol=self.et["tol"], alpha=self.et["alpha"], operator=self.op)

    def measure(self, window, seconds: float) -> None:
        import jax

        with window:
            deadline = window.start + seconds
            while True:
                with jax.profiler.TraceAnnotation(ANNOTATION):
                    scores, iterations, delta = self._converge()
                self.results.append((scores, int(iterations), float(delta)))
                if time.perf_counter() >= deadline:
                    break

    def _failed(self, iterations: int, delta: float) -> bool:
        return (delta > self.et["tol"]
                or iterations >= self.et["max_iterations"])

    def summary(self, window) -> dict:
        from protocol_tpu.utils import trace

        edges, peers = kronecker.filtered_shape(self.n, self.src, self.dst,
                                                self.val)
        iterations = [it for _, it, _ in self.results]
        print(json.dumps({"converges": {
            "count": len(self.results), "iterations": sorted(set(iterations)),
            "max_delta": max(d for _, _, d in self.results)}}), flush=True)
        spans = trace.summary()
        return {
            "attempted": len(self.results),
            "failed": sum(self._failed(it, d) for _, it, d in self.results),
            "end_to_end": {"converge_s": window.seconds / len(self.results)},
            "layer": {
                "edges": edges, "peers": peers,
                "iterations": iterations,
                "plan_build_s": spans.get("routed.plan_build",
                                          {}).get("total_s"),
            },
        }

    def release(self) -> None:
        del self.op, self.backend

    def check(self) -> dict:
        t = time.perf_counter()
        ref, ref_iterations = reference.eigentrust_f64(
            self.n, self.src, self.dst, self.val, self.et["alpha"],
            self.et["initial_score"])
        worst = max(reference.rel_l1(scores, ref)
                    for scores, _, _ in self.results)
        print(json.dumps({"reference": {
            "iterations": ref_iterations, "seconds": time.perf_counter() - t,
            "compared": len(self.results)}}), flush=True)
        return {"rel_l1": {"value": worst,
                           "limit": self.config["checks"]["rel_l1"]}}


def control(config: dict, seed: int) -> dict:
    """The control's reading of each compared number at the cell's own
    size: the bfloat16 reference put in the program's place."""
    et = config["eigentrust"]
    n, src, dst, val = kronecker.graph_for(config, seed)
    ref, _ = reference.eigentrust_f64(n, src, dst, val, et["alpha"],
                                      et["initial_score"])
    low, iterations = reference.eigentrust_bf16(
        n, src, dst, val, et["alpha"], et["initial_score"], et["tol"],
        et["max_iterations"])
    return {"rel_l1": reference.rel_l1(low, ref),
            "control_iterations": iterations}
