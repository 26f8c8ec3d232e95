"""The benchmark: cells, traffic, readers and the reference they are judged by.

Run one cell with

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything that belongs to one configuration, traffic mix or per-layer
metric is a file found by its name (configs/, traffic/, metrics/,
runners/); see harness.py.
"""
