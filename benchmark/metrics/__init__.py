"""One reader per per-layer metric: ``<metric name>.py`` defines
``read(record)``, which returns the metric's value, or None where the
run holds nothing to read (the harness then leaves the metric out)."""

import importlib.util
import os

HERE = os.path.dirname(os.path.abspath(__file__))


def load(name: str):
    """The ``read`` function of metric ``name``."""
    path = os.path.join(HERE, name + ".py")
    spec = importlib.util.spec_from_file_location(
        "benchmark.metrics._" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
