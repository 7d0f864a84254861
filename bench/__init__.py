"""The chip benchmark of the continuous-benchmarking service.

``python3 bench/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`` runs one cell of ``BENCHMARK.json`` on the TPU it is
started on and prints one JSON result line.  Everything that belongs to
one configuration, traffic mix, per-layer metric or duet lives in a file
of its own that the harness finds by name (`bench.entries`).
"""
