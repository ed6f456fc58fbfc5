"""Chip benchmark of the SO(3) FFT: ``python3 bench/run.py --help``."""
