"""Seconds the warm-up queries spent compiling or loading their runners
(``ExecutionStats.compile_time``, billed on a runner-cache miss)."""


def read(run):
    return run.compile_s
