"""Shared fixtures + the tsan-lite sanitizer plugin.

NOTE: no XLA_FLAGS here — smoke tests and benches must see the real
1-CPU device count.

REPRO_SANITIZE=1 installs `repro.lint.runtime`'s tracked lock factories
and queue wrappers for the whole pytest session (CI's push-only
`sanitize` job runs the executor suites this way). At session end the
observed report is dumped to $REPRO_SANITIZE_OUT (default
sanitize-report.json) for the `--runtime-report` reconciliation gate,
and the session FAILS on its own if the run observed a lock-order cycle
or any blocking-under-lock event longer than $REPRO_SANITIZE_BLOCK_MS
(default 200 ms).
"""
import json
import os

import jax
import numpy as np
import pytest

_SANITIZE = os.environ.get("REPRO_SANITIZE") == "1"


def pytest_configure(config):
    if _SANITIZE:
        from repro.lint import runtime
        runtime.install()


def pytest_sessionfinish(session, exitstatus):
    if not _SANITIZE:
        return
    from repro.lint import runtime
    if not runtime.installed():
        return
    report = runtime.snapshot()
    runtime.uninstall()
    out = os.environ.get("REPRO_SANITIZE_OUT", "sanitize-report.json")
    with open(out, "w", encoding="utf-8") as fh:
        json.dump(report, fh, indent=1, sort_keys=True)
    # Gate only on locks CREATED in product code: the lint suite seeds
    # deliberate inversions in tmp fixtures, and those must fail their
    # own assertions, not the whole session. The dumped report keeps
    # everything — reconciliation re-filters by analyzed module anyway.
    src = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       os.pardir, "src")
    src = os.path.abspath(src) + os.sep

    def in_src(site):
        return site.startswith(src)

    threshold_ms = float(os.environ.get("REPRO_SANITIZE_BLOCK_MS", "200"))
    slow = [b for b in report["blocking"]
            if b["ms"] > threshold_ms and in_src(b["lock"])]
    problems = []
    for cyc in report["cycles"]:
        if all(in_src(site) for site in cyc):
            problems.append("observed lock-order cycle: "
                            + " -> ".join(cyc))
    for b in slow:
        problems.append(
            f"blocked {b['ms']:.1f}ms in {b['op']} at {b['site']} while "
            f"holding {b['lock']} (threshold {threshold_ms:.0f}ms)")
    tr = session.config.pluginmanager.get_plugin("terminalreporter")
    if problems:
        session.exitstatus = 1
        lines = ["REPRO_SANITIZE: FAIL"] + problems
    else:
        lines = [f"REPRO_SANITIZE: clean ({len(report['edges'])} lock-order "
                 f"edge(s), {len(report['blocking'])} blocking event(s) "
                 f"under threshold; report: {out})"]
    for line in lines:
        if tr is not None:
            tr.write_line(line)
        else:
            print(line)


@pytest.fixture(scope="session")
def rng():
    return np.random.RandomState(0)


@pytest.fixture(scope="session")
def jrng():
    return jax.random.PRNGKey(0)
