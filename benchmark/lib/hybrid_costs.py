"""Operations and bytes of the parts of a `granitemoehybrid` layer, from
shapes alone, and the split of a trace's device time by part.

The shapes are the ones the engine's `stats()` publishes for such a model
(`recurrent_shape`, `expert_shape`); the counts are its counters. Each
function counts the work the algorithm needs, whatever implements the part,
so a later kernel can be judged on the same number.

`scope_seconds` reads the map `device_report()["op_scopes"]` (program ->
HLO instruction -> the `jax.named_scope` it was traced under) against the
trace's seconds by operation. A program that publishes no such map (an
older commit) gives None, and so does every reader built on it.
"""

from __future__ import annotations

import re
from typing import Optional

DECODE = r"^jit__decode_step$"
PREFILL = r"^jit__prefill(_suffix)?_step$"


def state_slot_bytes(shape: dict) -> int:
    """One sequence's recurrent state over every Mamba layer: the float32
    state [heads, head size, state size] and the convolution's tail."""
    state = shape["num_heads"] * shape["head_dim"] * shape["state_size"]
    tail = (shape["conv_width"] - 1) * shape["conv_dim"]
    return shape["num_layers"] * (
        state * shape["state_itemsize"] + tail * shape["conv_itemsize"]
    )


def expert_params(shape: dict) -> int:
    """One routed expert: gate and up [D, 2F], down [F, D]."""
    return 3 * shape["hidden_size"] * shape["expert_width"]


def expert_bytes(shape: dict) -> int:
    return expert_params(shape) * shape["weight_itemsize"]


def parameter_count(model: dict) -> int:
    """Parameters of a configuration's `model` section as it is held:
    the layers of `layer_types`, `experts_held` routed experts a layer."""
    d = model["hidden_size"]
    d_inner = model["mamba_n_heads"] * model["mamba_d_head"]
    conv_dim = d_inner + 2 * model["mamba_n_groups"] * model["mamba_d_state"]
    heads = model["mamba_n_heads"]
    mamba = (
        d * (d_inner + conv_dim + heads) + d_inner * d
        + conv_dim * model["mamba_d_conv"] + conv_dim + 3 * heads + d_inner
    )
    kv = model["num_key_value_heads"] * (d // model["num_attention_heads"])
    attention = 2 * d * d + 2 * d * kv
    per_layer = (
        2 * d + d * model["num_local_experts"]
        + len(model["experts_held"]) * 3 * d * model["intermediate_size"]
        + 3 * d * model["shared_intermediate_size"]
    )
    mixers = sum(mamba if kind == "mamba" else attention for kind in model["layer_types"])
    return mixers + len(model["layer_types"]) * per_layer + model["vocab_size"] * d + d


def ssd_scan_cost(tokens: float, heads: int, head_dim: int, state_size: int,
                  chunk: int, conv_dim: int, act_bytes: int = 2) -> dict:
    """The chunked scan of one Mamba-2 layer over `tokens` tokens, between
    in_proj and out_proj (convolution, scan, gated norm). Operations: inside
    a chunk the causal half of C B^T (state_size x chunk a token) and of its
    product with x (heads x head_dim x chunk), and 2 x 2 x heads x head_dim x
    state_size a token for the chunk's contribution to the state and the
    carried state's read-out. Bytes: xBC and the gate in, dt in, y out, and
    the float32 state read and written once a chunk."""
    inner = heads * head_dim
    flops = tokens * (state_size * chunk + inner * chunk + 4.0 * inner * state_size)
    moved = tokens * ((conv_dim + 2 * inner) * act_bytes + heads * 4)
    moved += (tokens / chunk) * 2.0 * inner * state_size * 4
    return {"flops": flops, "bytes": moved}


def scope_seconds(collected: dict, program: str, scope: str) -> Optional[float]:
    """Device seconds of the traced operations of the programs matching
    `program` that belong to the scopes matching `scope`."""
    scopes = (collected.get("device_report") or {}).get("op_scopes")
    trace = collected.get("trace")
    if not scopes or not trace:
        return None
    program_rx, scope_rx = re.compile(program), re.compile(scope)
    seconds = 0.0
    for name, spent in trace["op_seconds"].items():
        module, _, op = name.partition("/")
        if not program_rx.search(module):
            continue
        found = scopes.get(module, {}).get(op.split(" ")[0])
        if found is not None and scope_rx.search(found):
            seconds += spent
    return seconds or None


def traced_work(collected: dict, program: str, scope: str, counter: str,
                dispatches: str):
    """(device seconds of `scope` in the programs matching `program`, the
    work they did): `counter`'s mean a dispatch over the window (`stats()`
    counts over the whole window, the trace holds its middle seconds)
    times the programs' runs in the trace. None where any part is missing
    or nought."""
    window = collected["engine_window"]
    seconds = scope_seconds(collected, program, scope)
    traced = runs(collected, program) if seconds else 0
    if not seconds or not traced or not window[dispatches]:
        return None
    return seconds, window[counter] / window[dispatches] * traced


def busy_share(collected: dict, scope: str) -> Optional[float]:
    """Percent of device busy time in the operations of `scope`, all
    programs."""
    seconds = scope_seconds(collected, r"^jit__", scope)
    busy = (collected.get("trace") or {}).get("busy_s")
    return 100.0 * seconds / busy if seconds and busy else None


def runs(collected: dict, program: str) -> float:
    """Executions in the traced window of the programs matching `program`, a
    run that an edge of the window cuts by its share inside: the seconds the
    work is held against are cut the same way (`lib/xplane.window_runs`)."""
    from lib.xplane import module_name, window_runs

    rx = re.compile(program)
    return sum(
        window_runs(m) for name, m in collected["trace"]["modules"].items()
        if rx.search(module_name(name))
    )


def peaks():
    import jax

    from lib.peaks import peaks_for

    return peaks_for(jax.devices()[0].device_kind)
