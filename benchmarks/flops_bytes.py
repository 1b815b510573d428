"""Operations and bytes that the algorithm needs, from shapes alone.

Kept with the benchmark so that no PR that claims a gain can move them.
"Needs" means the published mathematics: causal attention counts only the
keys at or before each query, recomputation under ``remat`` counts nothing,
and a decode step needs each weight and each live cache row once, whatever
the program actually moves. The counts of one model family live in
``families/<family>.py`` (``train_flops_per_token``, ``decode_step``,
``prefill`` ...), over the published key names of its configurations; this
file keeps what is of no family.
"""

from __future__ import annotations

BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}  # of one element, by the configuration's dtype


def roofline_pct(ops: float, nbytes: float, seconds: float, peak: dict):
    """(share in %, which bound) of the least time the chip could take."""
    t_ops = ops / peak["bf16_flops_per_s"]
    t_mem = nbytes / peak["hbm_bytes_per_s"]
    bound = "memory" if t_mem >= t_ops else "compute"
    return 100.0 * max(t_ops, t_mem) / seconds, bound
