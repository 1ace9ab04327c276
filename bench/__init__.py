"""The chip benchmark: one command, driven by the data in BENCHMARK.json.

``python3 -m bench.run --workload <config>.<traffic> --seed <n>
--seconds <s> --trace <0|1>`` runs one cell. Everything a cell needs is
found by name: ``configs/<config>.json`` (sizes) beside
``configs/<config>.py`` (weights from the seed and the plain reference),
``traffic/<traffic>.json`` (the mix), ``drivers/<kind>.py`` (how the
system under test is driven), ``metrics/<metric>.py`` (one reader per
per-layer metric) and ``shapes/<step>.py`` (operations and bytes).
"""
