"""On-chip benchmark of the pushdown engine: TPC-H query streams served
through ``repro.core.runtime.run_stream``.

``python3 bench/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`` runs one cell of ``BENCHMARK.json``. Everything that
belongs to one configuration, traffic mix or metric is a file of its own
under ``bench/configs``, ``bench/traffic`` and ``bench/metrics``, found by
the name the manifest gives it.
"""
