import os
import sys

# NOTE: deliberately NOT setting --xla_force_host_platform_device_count
# here — the dry-run (and only the dry-run) uses 512 fake devices; tests
# and benchmarks must see the host's real single device.
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax  # noqa: E402

jax.config.update("jax_enable_x64", False)


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: multi-device decode equivalence tests — CI "
        "(scripts/ci.sh, 8 forced host devices) runs them; skip "
        "locally with -m 'not slow' or scripts/ci.sh --fast",
    )


def pytest_terminal_summary(terminalreporter, exitstatus, config):
    """Per-file test-time report: cumulative call-phase seconds by test
    file, slowest first — so a new (especially multidevice) test file
    ballooning the suite is visible in every run, not discovered by
    bisecting a slow CI."""
    times: dict[str, list] = {}
    for reports in terminalreporter.stats.values():
        for rep in reports:
            if getattr(rep, "when", None) != "call":
                continue
            # nodeid, not location[0]: a wrapped test reports its
            # wrapper's code location
            entry = times.setdefault(
                rep.nodeid.split("::")[0], [0.0, 0]
            )
            entry[0] += rep.duration
            entry[1] += 1
    if not times:
        return
    terminalreporter.write_sep("-", "per-file test time (call phase)")
    for f, (t, n) in sorted(times.items(), key=lambda kv: -kv[1][0]):
        terminalreporter.write_line(f"{t:8.1f}s  {n:4d} tests  {f}")
