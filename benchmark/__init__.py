"""The repo's benchmark: one command runs one cell once, on the chip.

``python -m benchmark.run --workload <name> --seed <n> --seconds <s>
--trace <0|1>``. Everything that belongs to one configuration, traffic
mix, cell or metric is a file of its own, found by the name that
``BENCHMARK.json`` gives it (see ``run.py``); the yardstick (traffic
generation, trace reduction, peaks, shape arithmetic, the reference) lives
here and takes from ``ray_tpu`` only the system under test.
"""
