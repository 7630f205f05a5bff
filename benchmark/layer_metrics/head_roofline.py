"""The head's matrix read once a decode run, over the time of the operations
traced under `llm.head` in the decode program and the HBM peak. The engine
publishes the shape (`head_shape`); the weight bytes asked for are counted,
not the logits written or the norm's input, so the share cannot pass 100%
for what the head does besides. A program without `head_shape` (an older
commit) gives nothing."""

from lib import falcon_h1_costs as costs


def read(collected):
    shape = collected["engine_after"]["head_shape"]
    seconds = costs.scope_seconds(collected, costs.DECODE, costs.HEAD_SCOPE)
    traced = costs.runs(collected, costs.DECODE) if seconds else 0
    if not seconds or not traced:
        return None
    moved = costs.head_bytes(shape) * traced
    return 100.0 * moved / costs.peaks()["hbm_bytes_per_s"] / seconds
