"""Chip benchmark for the CoDR serving paths (see BENCHMARK.json)."""
