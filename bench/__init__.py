"""Chip benchmark of the OSMOSIS reproduction (see ``BENCHMARK.json``)."""
