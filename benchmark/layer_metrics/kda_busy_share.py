"""Device time in the KDA mixer's operations over busy time."""

from lib import solar_open2_costs as costs


def read(collected):
    return costs.busy_share(collected, costs.KDA_SCOPES)
