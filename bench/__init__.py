"""Chip benchmark of the serving path; ``python3 bench/run.py --help``."""
