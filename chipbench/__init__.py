"""Chip benchmark of the paged serving path: configurations, traffic mixes,
metric readers and the plain reference, driven by ``BENCHMARK.json``."""
