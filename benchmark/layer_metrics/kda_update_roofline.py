"""Recurrent-state bytes a decode step has to read and write, over the time
the KDA one-token update took and the HBM peak. The engine counts the bytes
(every decoding lane's slot, read and written once: `decode_state_bytes`,
from the shapes the model declares); the trace gives the seconds of the
operations traced under `llm.mixer.kda.update` in the decode program (the
decay of every key row, both read-outs, the state's update, the gated norm),
whatever implements them. A program without such a scope or counter (an
older commit, another model) gives None."""

from lib import solar_open2_costs as costs


def read(collected):
    found = costs.traced_work(
        collected, costs.DECODE, costs.UPDATE_SCOPE,
        "decode_state_bytes", "decode_dispatches",
    )
    if found is None:
        return None
    seconds, moved = found
    return 100.0 * moved / costs.peaks()["hbm_bytes_per_s"] / seconds
