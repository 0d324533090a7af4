"""The on-chip benchmark of the mapper: ``python3 bench/run.py``."""
