"""Chip benchmark of the DEFL simulator: one cell per run (see run.py)."""
