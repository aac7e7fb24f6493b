"""chipbench — the repo's on-chip benchmark (BENCHMARK.json names it).

Everything the yardstick needs lives here, so that a PR that changes the
program cannot move it: traffic generation, the trace reduction, the table
of peaks, the FLOPs and bytes functions, the plain references and the
comparison that decides `correct`.
"""
