"""What a later PR adds for an architecture the harness has never
seen: a directory of its own (listed in ``paths`` as
``chipbench_more``) with a configuration, its builder, its plain
reference, a traffic mix and a per-layer metric with its reader.
``test_chipbench_run_loop.py`` copies it beside a copy of
``chipbench/`` and edits ``BENCHMARK.json`` only."""
