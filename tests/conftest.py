"""Test config: tests run on the CPU, as an 8-device virtual mesh.

JAX_PLATFORMS=cpu is set before JAX initializes (the chip is reached only
through chip_smoke.py), and XLA's host platform is split into 8 virtual
devices so multi-device sharding is exercised without multi-chip
hardware; the driver separately dry-runs
`__graft_entry__.dryrun_multichip`.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

# Persistent compile cache (utils/xla_cache.py rule): the kernel graphs
# (scan ladders, Miller loops) are compile-heavy; cache across test runs.
from lighthouse_tpu.utils import xla_cache  # noqa: E402

xla_cache.configure()


def pytest_configure(config):
    config.addinivalue_line("markers", "slow: long-running device tests")


def pytest_sessionfinish(session, exitstatus):
    # Under LTPU_LOCK_WITNESS=1 the whole suite doubles as a lock-order
    # soak: any production AB/BA inversion recorded by the global
    # witness fails the run here.  (Deliberate cycles in
    # tests/test_analysis.py use private Witness instances and never
    # touch the global graph.)
    from lighthouse_tpu.utils import locks

    if not locks.enabled():
        return
    cycles = locks.report().get("cycles", [])
    if cycles:
        session.exitstatus = 1
        print(f"\nlock witness recorded {len(cycles)} lock-order "
              f"cycle(s) during the suite: {cycles}")
    # Under LTPU_RACE_WITNESS=1 the suite is also a lockset soak: any
    # guarded field whose candidate lockset emptied on a write is a
    # race in production code.  (Deliberate violations in tests use
    # private RaceChecker instances.)
    if locks.race_enabled():
        races = locks.race_report().get("reports", [])
        if races:
            session.exitstatus = 1
            print(f"\nrace witness recorded {len(races)} lockset "
                  f"violation(s) during the suite: {races}")
