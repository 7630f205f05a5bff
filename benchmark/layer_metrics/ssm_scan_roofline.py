"""The chunked scan's operations and bytes for the tokens the traced prefill
runs fed, against the time of the operations traced under
`llm.mixer.mamba.scan` (convolution, scan, gated norm) in the prefill
programs. Real tokens are counted, not a bucket's padding."""

from lib import hybrid_costs as costs
from lib.flops import roofline_share


def read(collected):
    shape = collected["engine_after"]["recurrent_shape"]
    found = costs.traced_work(
        collected, costs.PREFILL, r"^llm\.mixer\.mamba\.scan$",
        "prefill_scan_tokens", "prefill_chunk_dispatches",
    )
    if found is None:
        return None
    seconds, tokens = found
    layer = costs.ssd_scan_cost(
        tokens, shape["num_heads"], shape["head_dim"], shape["state_size"],
        shape["chunk_size"], shape["conv_dim"], shape["conv_itemsize"],
    )
    share = roofline_share(
        layer["flops"] * shape["num_layers"], layer["bytes"] * shape["num_layers"],
        seconds, costs.peaks(),
    )
    return 100.0 * share["share"]
