"""Recurrent-state bytes a decode step has to read and write, over the time
the state update took and the HBM peak. The engine counts the bytes (every
decoding lane's slot, read and written once: `decode_state_bytes`); the
trace gives the seconds of the operations traced under
`llm.mixer.mamba.update` in the decode program (conv step, state update,
read-out, gated norm), whatever implements them."""

from lib import hybrid_costs as costs


def read(collected):
    found = costs.traced_work(
        collected, costs.DECODE, r"^llm\.mixer\.mamba\.update$",
        "decode_state_bytes", "decode_dispatches",
    )
    if found is None:
        return None
    seconds, moved = found
    return 100.0 * moved / costs.peaks()["hbm_bytes_per_s"] / seconds
