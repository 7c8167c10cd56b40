"""Readers of the benchmark metrics, one file per metric."""
