"""Test config: force JAX onto a virtual 8-device CPU mesh.

The tests run without an accelerator; sharding tests run against 8 virtual
CPU devices. The chip is reached through chip_smoke.py, not from here.
"""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
xla_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in xla_flags:
    os.environ["XLA_FLAGS"] = (
        xla_flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
# Hermetic runs: no executable is read from or written to the persistent
# compile cache that a Server places (nomad_tpu/tensor/backend.py), so a
# test never passes or fails on what an earlier run left behind.
jax.config.update("jax_enable_compilation_cache", False)

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# Runtime lock diagnostics (opt-in): NOMAD_TPU_DEBUG_LOCKS=1 swaps
# threading.Lock/RLock for order-tracking wrappers BEFORE any test
# constructs a broker/raft/gossip object, so the chaos/cluster suites run
# under the lock-order detector. Default-off: zero overhead when unset.
from nomad_tpu.analysis import debug_locks as _debug_locks  # noqa: E402

_debug_locks.install_from_env()


# Build the native executor once if the toolchain is present; tests fall
# back to the Python supervisor when it isn't (same file contract).
def _ensure_native_executor():
    import shutil
    import subprocess

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    binary = os.path.join(root, "native", "bin", "nomad-executor")
    liblog = os.path.join(root, "native", "bin", "liblogstore.so")
    stamp = os.path.join(root, "native", "bin", ".build_failed")
    sources = [os.path.join(root, "native", f)
               for f in ("executor.cc", "logstore.cc", "Makefile")]
    if (os.path.exists(binary) and os.path.exists(liblog)) \
            or shutil.which("g++") is None:
        return
    # Don't re-pay a failed build on every pytest start: skip while the
    # failure stamp is newer than the source.
    try:
        if os.path.getmtime(stamp) >= max(os.path.getmtime(s)
                                          for s in sources):
            return
    except OSError:
        pass
    try:
        out = subprocess.run(["make", "-C", os.path.join(root, "native")],
                             capture_output=True, text=True, timeout=120)
        if out.returncode != 0:
            os.makedirs(os.path.dirname(stamp), exist_ok=True)
            with open(stamp, "w") as f:
                f.write(out.stderr[-4000:])
            print("WARNING: native executor build failed; driver tests use "
                  f"the Python supervisor (see {stamp})")
    except Exception:
        pass


_ensure_native_executor()


# One retry for timing-sensitive tests that OPT IN via
# @pytest.mark.timing_retry (or a module-level `pytestmark`): they assert
# distributed properties (elections, gossip convergence, task execution)
# under real threads and real sockets, and a loaded CI machine can stretch
# past any fixed margin. A genuine regression fails both attempts; a
# scheduler hiccup doesn't fail `pytest -x`. Reruns are reported loudly.
# Marker-based (not per-file) so that new deterministic logic in a file
# that merely CONTAINS some timing tests isn't laundered through a rerun.
# Deliberately UNMARKED: test_server.py, test_services.py,
# test_pipelined_worker.py — the subsystems under heaviest active change;
# a new ~50% race there must fail CI, not pass on the second try. Mark
# individual tests in those files if a specific assertion proves flaky.


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "timing_retry: retry this timing-sensitive test once on failure")
    config.addinivalue_line(
        "markers",
        "slow: multi-second storm/soak runs excluded from the tier-1 "
        "sweep (`-m 'not slow'`); run with `-m slow` or NOMAD_TPU_SOAK=1")


def pytest_sessionfinish(session, exitstatus):
    """Unmount what task chroots left mounted under this run's tmp dir.

    An agent keeps its alloc dirs, and their bind mounts of /dev, /bin and
    the rest, across shutdown so that a restart can reattach. In a test
    they then outlive the run, and when a later pytest prunes the old tmp
    dir its rmtree goes through the live /dev bind and deletes the host's
    device nodes (seen: /dev/urandom and /dev/null gone mid-session)."""
    base = getattr(getattr(session.config, "_tmp_path_factory", None),
                   "_basetemp", None)
    if base is not None:
        from nomad_tpu.client.allocdir import AllocDir

        AllocDir(str(base)).unmount_all()


def pytest_runtest_protocol(item, nextitem):
    if item.get_closest_marker("timing_retry") is None:
        return None
    from _pytest.runner import runtestprotocol

    item.ihook.pytest_runtest_logstart(nodeid=item.nodeid,
                                       location=item.location)
    reports = runtestprotocol(item, nextitem=nextitem, log=False)
    # Retry only setup/call failures; a teardown ERROR (leaked resource)
    # must surface, not be laundered through a clean second run — attempt
    # 1's teardown failures are re-logged alongside attempt 2.
    if any(r.failed for r in reports if r.when in ("setup", "call")):
        print(f"\nRETRYING (timing-sensitive): {item.nodeid}")
        teardown_errors = [r for r in reports
                           if r.when == "teardown" and r.failed]
        if hasattr(item, "_initrequest"):
            # Reset funcargs so fixtures REBUILD: without this the rerun
            # reuses attempt 1's torn-down fixture values (pytest's
            # _fillfixtures skips argnames already present) — the same
            # reset pytest-rerunfailures performs per rerun.
            item._initrequest()
        reports = teardown_errors + runtestprotocol(item, nextitem=nextitem,
                                                    log=False)
    for report in reports:
        item.ihook.pytest_runtest_logreport(report=report)
    item.ihook.pytest_runtest_logfinish(nodeid=item.nodeid,
                                        location=item.location)
    return True
