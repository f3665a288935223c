"""Tests of the benchmark's own code. Run from the repo root:

    python -m pytest perfbench/tests -q            # cheap ones
    python -m pytest perfbench/tests -q -m slow    # the CPU rehearsal

They live under ``perfbench/`` because the PR that defines the benchmark
may add files only there; the driver's tier-1 command collects ``tests/``
and does not count them.
"""

import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))


def pytest_configure(config):
    config.addinivalue_line("markers", "slow: starts a CPU server")
