"""Chip benchmark of the simulation engine (``bench/run.py``)."""
