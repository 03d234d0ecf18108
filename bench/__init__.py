"""Chip benchmark of the MBP training engine: run with ``python3 bench/run.py``."""
