"""The on-chip benchmark's harness: cell specs, traffic, the open-loop
feeder, spans and trace reduction, and the correctness check. Everything
that belongs to one configuration, traffic mix or metric lives in files
of its own (``configs/``, ``traffic/``, ``metrics/``, ``reference/``) and
is found by the names in ``BENCHMARK.json``."""
