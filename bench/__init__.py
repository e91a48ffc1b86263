"""Chip benchmark of the serving engine: one cell per run, driven by the
files under this directory (see ``run.py``)."""
