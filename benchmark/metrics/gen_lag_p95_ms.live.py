"""95th percentile, in milliseconds, of how late the benchmark landed
the window's attestations: the host's time from each one's due time to
the return of ``LocalChain.attest``."""

import numpy as np


def read(rec):
    lags = rec.layer.get("gen_lag_ms") or []
    return float(np.percentile(lags, 95)) if lags else None
