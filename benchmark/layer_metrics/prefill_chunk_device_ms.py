"""Median device duration of the prefill programs' executions in the trace
(`_prefill_step` and `_prefill_suffix_step`, every width, weighted by how
often each ran)."""

from lib.xplane import module_median_s


def read(collected):
    seconds = module_median_s(collected["trace"], r"prefill")
    return None if seconds is None else seconds * 1000.0
