"""The chip benchmark: one cell of ``BENCHMARK.json`` per run, on a TPU.

Run from the root of a checkout::

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

Everything that belongs to one configuration, traffic mix, entry, per-layer
metric or cell lives in a file of its own under this directory and is found
by the name ``BENCHMARK.json`` gives it.
"""
