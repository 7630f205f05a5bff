"""Operations of the visible (query, key) pairs of the chunks' sliding-window
layers, over the time of the operations traced under
`llm.mixer.attention.window` in the prefill programs and the bf16 peak. Real
tokens and the mask's own pairs are counted, not a bucket's padding or a
tile's."""

from lib import laguna_costs as costs


def read(collected):
    shape = collected["engine_after"]["attention_shape"]["window"]
    found = costs.traced_work(
        collected, costs.PREFILL, costs.WINDOW_SCOPE,
        "prefill_window_pairs", "prefill_chunk_dispatches",
    )
    if found is None:
        return None
    seconds, pairs = found
    flops = costs.prefill_pair_flops(pairs, shape)
    return 100.0 * flops / costs.peaks()["bf16_flops_per_s"] / seconds
