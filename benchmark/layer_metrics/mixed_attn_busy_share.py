"""Percent of device busy time under `llm.mixer.attention.*` (projections,
full and window attention), all programs."""

from lib import laguna_costs as costs


def read(collected):
    return costs.busy_share(collected, costs.ATTENTION_SCOPES)
