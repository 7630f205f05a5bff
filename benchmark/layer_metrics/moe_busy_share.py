"""Device time in the routed experts' operations over busy time."""

from lib import hybrid_costs as costs


def read(collected):
    return costs.busy_share(collected, r"^llm\.moe\.routed$")
