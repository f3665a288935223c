#!/usr/bin/env python3
"""Starts the program's CLI server (``api_server.main`` with the arguments
given here) with ONE thing around it changed: ``jax.profiler.start_trace``
writes under ``PERFBENCH_PROFILE_DIR`` whatever directory it is handed.

The program's ``POST /debug/profile`` names the literal ``/tmp/kgct-profile``:
outside the checkout, and shared by every checkout on the machine, so one
side of a comparison could read or remove the other side's trace. The
benchmark may not change the program, so it redirects the JAX call the
handler makes. Nothing the server computes or serves is touched. When the
program takes its profile directory from outside (first item for the
``tracing`` issue, PERF.md section 7), a ``benchmark`` PR drops this file
and ``server.py`` runs ``python -m ...api_server`` again.
"""

import os
import sys
from pathlib import Path

if __name__ == "__main__":
    sys.path.insert(0, str(Path(__file__).resolve().parent.parent))
    import jax.profiler

    profile_dir = os.environ["PERFBENCH_PROFILE_DIR"]
    start_trace = jax.profiler.start_trace

    def start_trace_in_checkout(log_dir, *args, **kwargs):
        return start_trace(profile_dir, *args, **kwargs)

    jax.profiler.start_trace = start_trace_in_checkout

    from kubernetes_gpu_cluster_tpu.serving import api_server
    sys.argv[0] = "api_server"
    api_server.main()
