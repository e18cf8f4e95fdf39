"""Chip benchmark of DSE-protected training and serving (see run.py)."""
