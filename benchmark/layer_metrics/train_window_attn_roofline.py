"""Operations of the visible pairs of the sliding-window layers over the
time of the operations traced under `llm.mixer.attention.window` in the
train step and the bf16 peak."""

from lib import mellum_costs as costs


def read(collected):
    return costs.attention_roofline(collected, costs.SLIDING, costs.WINDOW_SCOPE)
