"""The chunked delta rule's operations for the tokens the traced prefill
runs fed (`lib/solar_open2_costs.scan_flops`: causal halves of the two
decayed products, the solve by substitution, the state's read-outs and
update; nothing recomputed, the decay a channel counted as no product), over
the time of the operations traced under `llm.mixer.kda.scan` (decayed
products, solve, state walk, state out, gated norm) in the prefill programs
and the bfloat16 peak. Real tokens are counted, not a bucket's padding. A
program without such a scope or counter (an older commit, another model)
gives None."""

from lib import solar_open2_costs as costs


def read(collected):
    shape = collected["engine_after"]["recurrent_shape"]
    found = costs.traced_work(
        collected, costs.PREFILL, costs.SCAN_SCOPE,
        "prefill_scan_tokens", "prefill_chunk_dispatches",
    )
    if found is None:
        return None
    seconds, tokens = found
    return 100.0 * costs.scan_flops(tokens, shape) / costs.peaks()["bf16_flops_per_s"] / seconds
