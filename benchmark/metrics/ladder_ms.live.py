"""Device milliseconds per batched ingest dispatch: the device time of
the recovery programs (``_recover_prep``, the GLV ladder
``_recover_glv``, ``ops/secp_batch.py``) and the Poseidon permutation
(``permute_mont``, ``ops/poseidon_batch.py``) in the trace's window,
over the runs of the GLV ladder there."""

PROGRAMS = ("_recover_prep", "_recover_glv", "permute_mont")
LADDER = "_recover_glv"


def read(rec):
    if rec.trace is None:
        return None
    _, dispatches = rec.trace.program_seconds(LADDER)
    if not dispatches:
        return None
    seconds = sum(rec.trace.program_seconds(p)[0] for p in PROGRAMS)
    return 1e3 * seconds / dispatches
