#!/usr/bin/env python3
"""Entry point named by BENCHMARK.json: ``python3 perfbench/run.py ...``."""

import sys
import time

_T0 = time.monotonic()      # process start, as near as Python lets us see it

if __name__ == "__main__":
    from pathlib import Path
    sys.path.insert(0, str(Path(__file__).resolve().parent.parent))
    from perfbench.cli import main
    sys.exit(main(t_process_start=_T0))
