"""Device time in the gated-delta-rule mixer's operations over busy time."""

from lib import olmo_hybrid_costs as costs


def read(collected):
    return costs.busy_share(collected, costs.GDN_SCOPES)
