"""The on-chip benchmark of the LP server; ``python3 bench/run.py --help``."""
