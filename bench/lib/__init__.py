"""Shared arithmetic of the benchmark: statistics, traces, the check."""
