"""The chip benchmark of the served fleet: see ``bench/run.py``."""
