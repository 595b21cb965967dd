"""Chip benchmark of the GP service-chain solver (see ``bench/run.py``)."""
