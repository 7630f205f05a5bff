"""Median device duration of the decode program's executions in the trace
(the XLA module of `_StepPrograms._decode_step`)."""

from lib.xplane import module_median_s


def read(collected):
    seconds = module_median_s(collected["trace"], r"decode_step")
    return None if seconds is None else seconds * 1000.0
