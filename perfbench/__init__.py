"""perfbench: the benchmark of the served path (BENCHMARK.json names it).

Everything the yardstick needs lives in this directory: traffic generation,
the load generator, the reduction from /metrics scrapes and profiler traces
to metrics, the table of peaks, the bytes/FLOPs arithmetic, and the
comparison that decides ``correct``. From the program it takes only the CLI
server (started as a child process), its /health, /metrics and
/debug/profile endpoints, and the names the trace gives its programs.

The parent process never touches a device: a chip belongs to one process,
and that process is the server.
"""
