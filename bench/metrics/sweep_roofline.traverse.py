"""Least HBM bytes of the window's edge passes (bench/work.py) over the
chips' busy seconds inside the queries times peak HBM bandwidth, percent."""
from bench import readers


def read(run):
    return readers.sweep_roofline(run)
