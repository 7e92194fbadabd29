"""Seconds from the start of the process to the start of the window:
imports, graph generation, partitioning, upload, calibration and the
compile (or cache load) and warm-up of every runner the window uses."""


def read(run):
    return run.setup_s
