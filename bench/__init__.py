"""Chip benchmark for the sparse layer-pipelined CNN server.

``python bench/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`` runs one cell of ``BENCHMARK.json`` once on the TPU it
is started on. Everything that belongs to one configuration, traffic
mix or per-layer metric is a file of its own, found by the name in
``BENCHMARK.json``: ``configs/<config>.json`` (whose ``reference`` names
its network's layer table, ``networks/<network>.py``),
``traffic/<traffic>.json`` and ``metrics/<metric>.py`` (or
``metrics/<quantity>.py`` for every ``<quantity>.<cells>`` name).
"""
