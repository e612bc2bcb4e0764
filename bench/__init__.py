"""The chip benchmark of ATLAS: one run of one cell is ``bench/run.py``."""
