"""Test harness config.

Tests run on a virtual 8-device CPU mesh (the driver dry-runs the real
multi-chip path separately via ``__graft_entry__.dryrun_multichip``).

JAX_PLATFORMS=cpu pins the platform (set here for the session and for
every subprocess a test starts). The persistent compile cache stays off
(JAX_ENABLE_COMPILATION_CACHE=false, which the entry points' one cache
rule honors) unless PTPU_TEST_XLA_CACHE=1 opts in below.
float64 is enabled globally: parity tests compare against the exact
rational oracle at f64 precision (the TPU bench path stays f32).

Every test runs under a watchdog (``pytest_runtest_protocol`` below).
"""

import faulthandler
import hashlib
import os
from pathlib import Path

import pytest

# PTPU_TPU=1 skips the CPU pin so the session runs against the real TPU
# chip. It is meant ONLY for the device-prover battery —
# `PTPU_TPU=1 pytest tests/test_prover_tpu.py` is the committed
# real-hardware entry point. It is session-global (the platform must be
# chosen before jax initializes), so running the WHOLE suite under it
# is unsupported: the virtual 8-device mesh and the f64 rational-oracle
# comparisons need the CPU pin.
_REAL_TPU = os.environ.get("PTPU_TPU", "") in ("1", "true", "yes")

if os.environ.get("PTPU_TEST_XLA_CACHE") != "1":
    os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"

if not _REAL_TPU:
    # env vars still help any subprocesses tests may spawn
    os.environ["JAX_PLATFORMS"] = "cpu"
    _flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in _flags:
        os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

if not _REAL_TPU:
    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_enable_x64", True)

# persistent XLA compile cache: the prover programs inline statically
# unrolled field kernels (fieldops2.mont_mul) whose CPU compiles run
# minutes; repeat suite runs should pay them once, not every session.
# The dir is keyed by a host-CPU fingerprint: XLA:CPU cache entries are
# AOT executables whose machine features must match the loading host —
# a container re-provision onto different silicon otherwise reuses
# foreign artifacts, which XLA loads with a "could lead to SIGILL"
# warning and which segfaulted the r5 suite inside the cache
# deserializer.


def _host_fp() -> str:
    import hashlib

    try:
        with open("/proc/cpuinfo") as f:
            for line in f:
                # x86 feature line; aarch64 uses "Features"
                if line.startswith(("flags", "Features")):
                    return hashlib.sha1(line.encode()).hexdigest()[:8]
    except OSError:
        pass
    import platform

    ident = f"{platform.machine()}:{platform.processor()}"
    return hashlib.sha1(ident.encode()).hexdigest()[:8]


_CACHE_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "bench_cache",
    f"xla_cache_cpu_{_host_fp()}")
# OPT-IN only (PTPU_TEST_XLA_CACHE=1): on the 2026-08 runtime the
# cache's native (de)serialization segfaulted two full-suite runs —
# once in put_executable_and_time on a freshly-wiped dir, once in
# get_executable_and_time — in different tests. A suite that
# intermittently dies in a cache layer is worse than one that pays
# its compiles; flip the env on only after the runtime's cache path
# proves stable again.
if os.environ.get("PTPU_TEST_XLA_CACHE") == "1":
    try:
        os.makedirs(_CACHE_DIR, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", _CACHE_DIR)
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          2.0)
    except Exception:  # cache is an optimization, never a failure
        pass


# --- per-test watchdog -----------------------------------------------------
# Each test (setup, call and teardown) gets WATCHDOG_S seconds, or what
# its @pytest.mark.watchdog(seconds) says. Past that, faulthandler's own
# thread dumps every thread's stack to the session's stderr and ends the
# process; it fires even while the test is inside native code (an XLA
# compile), where no Python signal handler runs. Under xdist the
# controller reports the test failed by its name ("worker ... crashed
# while running ...") and starts a fresh worker for the rest. With
# --dist loadfile xdist hands the dead test to that worker again along
# with the rest of its file; the marker a test leaves in the session's
# shared basetemp while it runs turns that second start into a no-op,
# so the test is reported once, as failed. The limit is a hang guard,
# not a speed gate: the slowest tier-1 test takes five minutes on an
# 8-core host. A `slow` test that can run longer carries a marker of
# about three times its time measured there.

WATCHDOG_S = 600.0
_STACKS = pytest.StashKey()  # stderr as it was before output capture


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "watchdog(seconds): this test's watchdog limit, "
        f"in place of the default {WATCHDOG_S:.0f} s")
    config.stash[_STACKS] = os.fdopen(os.dup(2), "w")


def pytest_unconfigure(config):
    stacks = config.stash.get(_STACKS, None)
    if stacks is not None:
        stacks.close()


def _running_marker(item):
    """The file that says ``item`` is running in this xdist session, in
    the basetemp its workers share; None outside xdist (and for remote
    workers, which get no basetemp)."""
    basetemp = item.config.option.basetemp
    if not hasattr(item.config, "workerinput") or basetemp is None:
        return None
    digest = hashlib.sha1(item.nodeid.encode()).hexdigest()
    return Path(basetemp).parent / f"running-{digest}"


@pytest.hookimpl(tryfirst=True, specname="pytest_runtest_protocol")
def pytest_runtest_protocol_once(item, nextitem):
    """Runs before the runner; a second ``pytest_runtest_protocol``
    implementation (pytest takes only ``pytest_*`` names as hooks)."""
    marker = _running_marker(item)
    if marker is None:
        return None
    if marker.exists():
        return True  # its worker died in it; xdist reported it failed
    marker.touch()
    return None


@pytest.hookimpl(wrapper=True)
def pytest_runtest_protocol(item, nextitem):
    mark = item.get_closest_marker("watchdog")
    faulthandler.dump_traceback_later(
        mark.args[0] if mark else WATCHDOG_S, exit=True,
        file=item.config.stash[_STACKS])
    try:
        return (yield)
    finally:
        faulthandler.cancel_dump_traceback_later()
        marker = _running_marker(item)
        if marker is not None:
            marker.unlink(missing_ok=True)


# --- xdist: the longest files first ---------------------------------------
# `--dist loadfile` hands out files in order of their test count, so a
# long file of one or two tests started last and set the wall time.
# These files start first, longest first (seconds of test time each, on
# an 8-core host under -n 6); every other file follows in xdist's order.
# The list only orders the run: a file missing from it still runs.

FIRST_FILES = (
    "tests/test_secp_batch.py",               # ~450 s
    "tests/test_ingest.py",                   # ~450 s
    "tests/test_ingest_sharded.py",           # ~340 s
    "tests/test_pool.py",                     # ~170 s
    "tests/test_prover_tpu.py",               # ~140 s
    "tests/test_parallel_prover.py",          # ~120 s
    "tests/test_invariance.py",               # ~120 s
    "tests/test_prover_tpu_alternation.py",   # ~110 s
)


@pytest.hookimpl(optionalhook=True)
def pytest_xdist_make_scheduler(config, log):
    if config.getvalue("dist") != "loadfile":
        return None
    from xdist.scheduler import LoadFileScheduling

    class LongestFirst(LoadFileScheduling):
        def _assign_work_unit(self, node):
            for scope in reversed(FIRST_FILES):
                if scope in self.workqueue:
                    self.workqueue.move_to_end(scope, last=False)
            super()._assign_work_unit(node)

    return LongestFirst(config, log)


def make_signed_attestation(kp, about: bytes, domain: bytes, value: int,
                            message: bytes = b"\x00" * 32):
    """Shared fixture recipe: sign an attestation the way the Client
    does (Poseidon hash of the scalar form, wire-codec signature)."""
    from protocol_tpu.client.attestation import (
        AttestationData,
        SignatureData,
        SignedAttestationData,
    )

    att = AttestationData(about=about, domain=domain, value=value,
                          message=message)
    sig = kp.sign(int(att.to_scalar().hash()))
    return SignedAttestationData(att, SignatureData.from_signature(sig))
