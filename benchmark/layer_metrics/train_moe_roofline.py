"""Operations of the held assignments the traced steps made (a token through
one expert, forward and backward: 6 x its 3 x hidden x width parameters)
over the time of the operations traced under `llm.moe.routed` in the train
step and the bf16 peak. The assignments are the window's mean a step: the
counts ride the reports of the whole window, the trace holds its middle."""

from lib import mellum_costs as costs


def read(collected):
    train = collected.get("train") or {}
    model, experts = train.get("model"), train.get("experts")
    seconds = costs.step_scope_seconds(collected, costs.MOE_SCOPE) if model else None
    if not seconds or not experts or not train.get("steps"):
        return None
    held = experts["held"] / train["steps"] * costs.traced_steps(collected)
    return 100.0 * costs.expert_flops(model, held) / costs.peaks()["bf16_flops_per_s"] / seconds
