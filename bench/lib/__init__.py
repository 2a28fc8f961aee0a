"""Shared machinery of the benchmark: peaks, traffic, trace reduction."""
