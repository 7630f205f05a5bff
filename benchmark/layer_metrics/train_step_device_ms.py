"""Median device duration of the jitted train step's executions in the
trace."""

from lib.xplane import module_median_s


def read(collected):
    seconds = module_median_s(collected["trace"], r"jit_step")
    return None if seconds is None else seconds * 1000.0
