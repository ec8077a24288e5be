"""Chip benchmark of the served spatial index (see ``bench/run.py``)."""
