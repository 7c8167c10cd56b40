"""On-chip benchmark of the H-EYE serving loop (see ``BENCHMARK.json``).

``python -m bench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>``
runs one cell on the accelerator it finds and prints one JSON result line.
"""
