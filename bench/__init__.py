"""The on-chip benchmark of this repository: see ``bench/run.py``."""
