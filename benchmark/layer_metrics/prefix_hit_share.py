"""Of the prompt tokens the engine admitted in the window, the share it took
from the prefix cache and did not compute: `prefix_cache_hit_tokens` over
hits plus `prefill_tokens` (the tokens fed through a prefill program)."""


def read(collected):
    window = collected["engine_window"]
    hits = window["prefix_cache_hit_tokens"]
    return 100.0 * hits / (hits + window["prefill_tokens"])
