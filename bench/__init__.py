"""The chip benchmark of S-DOT: ``python3 bench/run.py --help``."""
