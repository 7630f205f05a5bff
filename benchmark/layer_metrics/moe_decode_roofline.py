"""Expert matrices a decode step has to read, over the time its routed
experts took and the HBM peak. An expert no token of the step chose need not
be read; the device counts the held experts that were reached, a layer a
step (`decode_experts_touched`)."""

from lib import hybrid_costs as costs


def read(collected):
    shape = collected["engine_after"]["expert_shape"]
    found = costs.traced_work(
        collected, costs.DECODE, r"^llm\.moe\.routed$",
        "decode_experts_touched", "decode_dispatches",
    )
    if found is None:
        return None
    seconds, touched = found
    moved = touched * costs.expert_bytes(shape)
    return 100.0 * moved / costs.peaks()["hbm_bytes_per_s"] / seconds
