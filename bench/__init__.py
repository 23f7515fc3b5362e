"""The chip benchmark: harness, traffic, yardstick and reference.

Run a cell with ``python3 bench/run.py --workload <cell> --seed <n>
--seconds <s> --trace <0|1>`` from the root of a checkout.
"""
