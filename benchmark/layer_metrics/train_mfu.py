"""Model FLOP/s utilization: operations the forward and backward passes
require per token (`lib/flops.py`: 6 per matmul weight plus causal attention;
recomputation never counts) times tokens per second per chip, over the
published bf16 peak of the chip (`lib/peaks.py`). In a traced run the rate is
that of the steps before the profiler started."""


def read(collected):
    train = collected["train"]
    achieved = train["flops_per_token"] * train["untraced_tokens_per_s"]
    return 100.0 * achieved / collected["peaks"]["bf16_flops_per_s"]
