"""Sweeps per converge, as the program returns them, mean over the
window's converges."""


def read(rec):
    its = rec.layer.get("iterations") or []
    return sum(its) / len(its) if its else None
