"""What the device reports of both paths are built from: which part of a
layer a compiled program's instructions belong to, and the bytes a device
holds of a tree of arrays.

A model names the parts of its layers with `jax.named_scope` (its `SCOPES`:
"llm.moe.routed", "llm.mixer.attention.full", ...), and XLA keeps the path
of scopes an instruction was traced under in its `op_name` metadata.
`scopes_of` reads that back from a compiled program's text, which is what
lets a reader split a trace's device time by part whatever implements the
part. Both paths use it: the serving runner's `device_report()`
(`ray_tpu.llm.hybrid_runner`) and the training side's
(`ray_tpu.train.step_device_report`).
"""

from __future__ import annotations

import re
from typing import Dict

_INSTRUCTION = re.compile(r"^\s*(?:ROOT )?%?(?P<name>[^\s=]+) = ")
_OP_NAME = re.compile(r'op_name="(?P<path>[^"]*)"')
# A scope as a path's part has it: bare in a forward instruction, inside
# the transformations' names in a backward one, `transpose(jvp(llm.head))`.
_SCOPE = re.compile(r"(?:[A-Za-z_]+\()*(?P<scope>llm\.[\w.]+)\)*")


def scopes_of(hlo_text: str) -> Dict[str, str]:
    """HLO instruction name -> the innermost part of a layer (a
    `jax.named_scope` whose name starts with "llm.": the model's SCOPES) its
    `op_name` metadata passes through, for the instructions that have one.
    A backward instruction's path names the scope inside the
    transformations it came by (`transpose(jvp(llm.moe.routed))`,
    `jvp(llm.moe.routed)`) and belongs to that scope. A fusion carries its
    root's metadata, so an operation fused across two parts counts to its
    root's."""
    out: Dict[str, str] = {}
    for line in hlo_text.splitlines():
        found = _INSTRUCTION.match(line)
        path = _OP_NAME.search(line)
        if not found or not path:
            continue
        inside = [
            part["scope"]
            for part in map(_SCOPE.fullmatch, path["path"].split("/")) if part
        ]
        if inside:
            out[found["name"]] = inside[-1]
        elif path["path"].startswith("ragged-dot"):
            # XLA:TPU expands a ragged dot into a kernel and its metadata
            # call under a name of their own, without the scope; these
            # programs have no ragged dot but the routed experts'.
            out[found["name"]] = "llm.moe.routed"
    return out


def bytes_by_device(arrays) -> dict:
    """Bytes a device holds of `arrays` (`addressable_shards`, so a
    replicated leaf counts on every chip that holds it)."""
    out: dict = {}
    for array in arrays:
        for shard in array.addressable_shards:
            key = f"{shard.device.platform}:{shard.device.id}"
            out[key] = out.get(key, 0) + int(shard.data.nbytes)
    return out
