"""K and V bytes the decode dispatches asked of the window cache class (each
lane's context up to the horizon), over the time of the operations traced
under `llm.mixer.attention.window` in the decode program and the HBM peak."""

from lib import laguna_costs as costs


def read(collected):
    shape = collected["engine_after"]["attention_shape"]["window"]
    found = costs.traced_work(
        collected, costs.DECODE, costs.WINDOW_SCOPE,
        "decode_window_tokens", "decode_dispatches",
    )
    if found is None:
        return None
    seconds, tokens = found
    moved = costs.decode_read_bytes(tokens, shape)
    return 100.0 * moved / costs.peaks()["hbm_bytes_per_s"] / seconds
