"""Operations of the held assignments the traced prefill runs made (a token
through one expert: 2 x its 3 x hidden x width parameters), over the time of
the operations traced under `llm.moe.routed` in the prefill programs
(sorting, the grouped products, the gated sum) and the bf16 peak."""

from lib import hybrid_costs as costs


def read(collected):
    shape = collected["engine_after"]["expert_shape"]
    found = costs.traced_work(
        collected, costs.PREFILL, r"^llm\.moe\.routed$",
        "prefill_expert_assignments", "prefill_chunk_dispatches",
    )
    if found is None:
        return None
    seconds, held = found
    flops = 2.0 * costs.expert_params(shape) * held
    return 100.0 * flops / costs.peaks()["bf16_flops_per_s"] / seconds
