"""The benchmark on the chip: ``python -m bench.run --workload <cell> ...``.

Everything a cell needs is found by name from ``BENCHMARK.json``: the
configuration in ``configs/<config>.json``, the traffic mix in
``traffic/<traffic>.json``, the driver of the configuration's ``kind`` in
``drivers/<kind>.py`` and each per-layer metric's reader in
``metrics/<metric>.py``.  The yardstick lives here too: the reference
interpreter, the rebuild of lane inputs from the seed, the reference
statistics fold, the trace reduction and the table of device peaks.
"""
