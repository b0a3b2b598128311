"""Chip benchmark of the sparse CP-ALS system: one cell, one run (see run.py)."""
