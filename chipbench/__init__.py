"""Chip benchmark of the repository's system under test (see run.py)."""
