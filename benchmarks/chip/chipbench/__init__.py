"""Chip benchmark harness: manifest lookup, traffic generation, weights
from the seed, FLOP and byte counts, trace reduction, plain float32
references and the cell runners. ``benchmarks/chip/run.py`` is the entry
point; everything one configuration, traffic mix or per-layer metric needs
lives in files of its own, found by the names in ``BENCHMARK.json``."""
