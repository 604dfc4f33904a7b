"""Milliseconds of the daemon's ingest per thousand attestations: the
program's ``service.ingest`` span (signer recovery in ``recover_signers``:
the batched device pipeline of ``client/ingest.py``, or the host path
below 32 lanes) over the window, as the program's tracer aggregates it,
per thousand attestations the window applied."""


def read(rec):
    span = rec.layer.get("spans", {}).get("service.ingest", {})
    ingested = rec.layer.get("ingested") or 0
    if not span.get("count") or not ingested:
        return None
    return 1e3 * span["total_s"] / (ingested / 1e3)
