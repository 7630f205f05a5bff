"""K and V bytes the decode dispatches asked of the full cache class, over the
time of the operations traced under `llm.mixer.attention.full` in the decode
program and the HBM peak."""

from lib import laguna_costs as costs


def read(collected):
    shape = collected["engine_after"]["attention_shape"]["full"]
    found = costs.traced_work(
        collected, costs.DECODE, costs.FULL_SCOPE,
        "decode_context_tokens", "decode_dispatches",
    )
    if found is None:
        return None
    seconds, tokens = found
    moved = costs.decode_read_bytes(tokens, shape)
    return 100.0 * moved / costs.peaks()["hbm_bytes_per_s"] / seconds
