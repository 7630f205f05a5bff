"""Share of device busy time in the Mosaic custom calls of
`ops/paged_flash.py`: the `tpu_custom_call` operations of the programs that
attend through the block table (decode, partial prefill, verify)."""

from lib.xplane import op_share

KERNEL = r"^jit__(decode|prefill_suffix|verify)_step/.* tpu_custom_call$"


def read(collected):
    share = op_share(collected["trace"], KERNEL)
    return None if share is None else 100.0 * share
