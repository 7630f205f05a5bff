"""Device time in the routed experts' operations of the train step over
busy time."""

from lib import mellum_costs as costs


def read(collected):
    return costs.scope_share(collected, costs.MOE_SCOPE)
