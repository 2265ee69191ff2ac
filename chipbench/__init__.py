"""Chip benchmark of the paged serving path: configurations, traffic mixes,
model families (the program's model, weights, plain reference and
operation count of each), metric readers, driven by ``BENCHMARK.json``."""
