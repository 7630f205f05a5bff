"""Operations and bytes of the two attention classes of a `laguna` model
(full layers, sliding-window layers), from shapes alone; the rest of a
layer (routed experts, scopes, peaks) is `lib/hybrid_costs.py`'s.

The shapes are the ones the engine's `stats()` publishes for such a model
(`attention_shape`, by cache class: `full`, `window`); the counts are its
counters. Each function counts what the algorithm needs, whatever
implements it: what a decode step was asked to read, not what a block rounds
up to, and the (query, key) pairs a mask leaves visible, not a tile's.
"""

from __future__ import annotations

from lib.hybrid_costs import (  # noqa: F401  (one import for the readers)
    DECODE,
    PREFILL,
    busy_share,
    peaks,
    traced_work,
)

FULL_SCOPE = r"^llm\.mixer\.attention\.full$"
WINDOW_SCOPE = r"^llm\.mixer\.attention\.window$"
ATTENTION_SCOPES = r"^llm\.mixer\.attention\."


def token_bytes(shape: dict) -> int:
    """K and V of one cached token over the class's layers."""
    return (
        shape["num_layers"] * 2 * shape["num_heads"] * shape["head_dim"]
        * shape["kv_itemsize"]
    )


def decode_read_bytes(tokens: float, shape: dict) -> float:
    """What decode dispatches that asked for `tokens` cached positions (a
    lane's context, or as much of it as the class's horizon lets a query
    see) read of the class's pools, in all its layers."""
    return tokens * token_bytes(shape)


def window_pairs(offset: int, tokens: int, window: int) -> int:
    """Visible (query, key) pairs of a chunk of `tokens` queries at
    positions offset .. offset + tokens - 1 in one sliding-window layer:
    the query at position p sees min(p + 1, window) keys."""
    last = offset + tokens
    ramp_end = min(max(window - 1, offset), last)  # positions below see p + 1
    ramp = (ramp_end * (ramp_end + 1) - offset * (offset + 1)) // 2 if ramp_end > offset else 0
    return ramp + (last - ramp_end) * window


def prefill_pair_flops(pairs: float, shape: dict) -> float:
    """QK^T and PV over `pairs` visible (query, key) pairs of one layer,
    in every layer of the class: 2 x 2 x heads x head size a pair."""
    return 4.0 * pairs * shape["num_query_heads"] * shape["head_dim"] * shape["num_layers"]
