"""The read load of the live traffic: ``GET /score/<addr>`` in an open
loop, from a process of its own (as real clients are), so that the load
generator does not compete for the daemon's interpreter lock. It
imports nothing but the standard library, and never JAX.

It reads one JSON object from standard input, and starts when it
arrives:

    {"url": "http://127.0.0.1:8799", "t0": <perf_counter seconds>,
     "rate": 50, "addresses": ["0x...", ...]}

Read k is due at ``t0 + k / rate`` (``time.perf_counter``, the
system's monotonic clock, which the parent shares). A dispatcher hands
each read to a small pool of threads when it is due, whether or not
earlier reads have answered, and each is timed from its due time. The
result is one JSON line on standard output: per read ``[k, seconds from
due to answer, HTTP status (0 for no answer), score, revision]``.
"""

from __future__ import annotations

import json
import sys
import time
import urllib.error
import urllib.request
from concurrent.futures import ThreadPoolExecutor

THREADS = 4
TIMEOUT_S = 30.0


def read_one(url: str, k: int, address: str, due: float) -> list:
    try:
        with urllib.request.urlopen(f"{url}/score/{address}",
                                    timeout=TIMEOUT_S) as r:
            body = json.loads(r.read())
            status = r.status
    except urllib.error.HTTPError as e:
        return [k, time.perf_counter() - due, e.code, None, None]
    except (OSError, ValueError):
        return [k, time.perf_counter() - due, 0, None, None]
    return [k, time.perf_counter() - due, status, body.get("score"),
            body.get("revision")]


def main() -> int:
    job = json.loads(sys.stdin.read())
    url, t0, rate = job["url"], float(job["t0"]), float(job["rate"])
    futures = []
    with ThreadPoolExecutor(max_workers=THREADS) as pool:
        for k, address in enumerate(job["addresses"]):
            due = t0 + k / rate
            wait = due - time.perf_counter()
            if wait > 0:
                time.sleep(wait)
            futures.append(pool.submit(read_one, url, k, address, due))
    print(json.dumps([f.result() for f in futures]), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
