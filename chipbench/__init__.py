"""The on-chip benchmark of the CP-ALS fit and the multi-tenant server.

``python chipbench/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`` runs one cell of ``BENCHMARK.json`` once on the TPU it is
started on.  Everything that measures (traffic generation, the float64
reference, the trace reduction, the peaks and the compulsory work of a
kernel) lives here, apart from the program under test in ``src/repro``.
"""
