"""Operations of the visible pairs of the full-attention layers over the
time of the operations traced under `llm.mixer.attention.full` in the train
step and the bf16 peak."""

from lib import mellum_costs as costs


def read(collected):
    return costs.attention_roofline(collected, costs.FULL, costs.FULL_SCOPE)
