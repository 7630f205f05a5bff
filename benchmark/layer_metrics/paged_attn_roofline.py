"""Share of the HBM roofline that the paged attention kernel reaches in the
decode program.

The engine counts what its decode dispatches asked the kernel to read
(`stats()`: `decode_dispatches`, `decode_context_tokens`, the sum of
`context_lens`) and says the shape that turns tokens into bytes
(`attention_shape`). The trace gives the kernel's device time: the
`tpu_custom_call` operations of `jit__decode_step`, one a layer a run. The
window's mean dispatch, in every layer, times the decode runs in the traced
window (a run that its edge cuts by its share inside, as its seconds are) is
the work (`lib/flops.py`); over the published peak (`lib/peaks.py`) and the
kernel's seconds it is the share. A program without the counters (before PR
24) gives nothing to read.
"""

import re

from lib.flops import paged_decode_attention_cost, roofline_share
from lib.peaks import peaks_for
from lib.xplane import module_name, window_runs

KERNEL = re.compile(r"^jit__decode_step/.* tpu_custom_call$")


def read(collected):
    import jax

    trace, window = collected["trace"], collected["engine_window"]
    shape = collected["engine_after"]["attention_shape"]
    kernel_s = sum(s for name, s in trace["op_seconds"].items() if KERNEL.match(name))
    runs = sum(
        window_runs(m) for name, m in trace["modules"].items()
        if module_name(name) == "jit__decode_step"
    )
    if not kernel_s or not runs or not window["decode_dispatches"]:
        return None
    context_tokens = window["decode_context_tokens"] / window["decode_dispatches"]
    layer = paged_decode_attention_cost(
        context_tokens, shape["num_heads"], shape["head_dim"], shape["kv_itemsize"]
    )
    work = shape["num_layers"] * runs
    share = roofline_share(
        layer["flops"] * work, layer["bytes"] * work, kernel_s,
        peaks_for(jax.devices()[0].device_kind),
    )
    return 100.0 * share["share"]
