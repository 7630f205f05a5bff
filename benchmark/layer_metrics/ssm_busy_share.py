"""Device time in the Mamba-2 mixer's operations over busy time."""

from lib import hybrid_costs as costs


def read(collected):
    return costs.busy_share(collected, r"^llm\.mixer\.mamba\.")
