"""Share of the traced window in which no operation ran on the device
(1 - busy union / window)."""


def read(rec):
    if rec.trace is None or not rec.trace.chips:
        return None
    return 100.0 * rec.trace.idle_share
