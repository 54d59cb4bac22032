"""Operations and bytes of the work a call must do, from the
configuration's shapes and the cell's FastForward plan; the counts of
each architecture are in bench/archs/<arch>.py.

Nothing there reads a counter of the program: a prefill row is
(pos0, n_tokens, is_dense, final) as the scheduler hands it to the
runtime, and the counts are those of the model's mathematics, whatever
computes it. A multiply-add is 2 operations. Padding rows and tokens
past a row's length are no work: what a kernel spends on them shows as
lost share.
"""
from __future__ import annotations


def least_time(flops: float, nbytes: float, peak: dict) -> float:
    return max(flops / peak["flops_bf16"], nbytes / peak["hbm_bytes_s"])
