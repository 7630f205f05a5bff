"""Share of device busy time in the Mosaic custom calls of
`ops/flash_attention.py`: the `tpu_custom_call` operations of the train step
(forward and backward kernels of every layer; the step holds no other
kernel)."""

from lib.xplane import op_share

KERNEL = r"^jit_step/.* tpu_custom_call$"


def read(collected):
    share = op_share(collected["trace"], KERNEL)
    return None if share is None else 100.0 * share
