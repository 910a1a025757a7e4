"""Chip benchmark of the live FL round: see ``bench/run.py``."""
