"""Operations and bytes that the dense decoder's steps need, counted from
the configuration and the live lengths: what the algorithm needs, not what
a datapath happens to move.  The same count holds for the XLA gather path
and the Pallas kernel, so a datapath that reads less is read as faster,
not as doing less work.

Decode, per step: every weight read once per sub-step; each row's live
K/V read once and its new K/V written; 2 operations per weight per row
plus attention (q.k and p.v) over the live length.  Prefill: 2 operations
per weight per computed prompt token plus causal attention over its
position; the LM head only for rows that emit a token.
"""
from __future__ import annotations

BF16 = 2
F32 = 4


def weight_bytes(spec) -> int:
    """Bytes of all weights as served: bf16 matmuls and head, f32 norms."""
    norms = (2 * spec.layers + 1) * spec.d_model + (2 * spec.layers * spec.head_dim if spec.qk_norm else 0)
    return (spec.layers * spec.layer_params + spec.d_model * spec.vocab) * BF16 + norms * F32


def token_flops(spec) -> int:
    """Operations of one token through every layer's matmuls (no attention, no head)."""
    return 2 * spec.layers * spec.layer_params


def head_flops(spec) -> int:
    return 2 * spec.d_model * spec.vocab


def attn_flops(spec, ctx: int) -> int:
    """q.k and p.v of one query over ``ctx`` positions, in every layer."""
    return 4 * spec.layers * spec.heads * spec.head_dim * ctx


def decode_step(spec, kv_lens: list[int], substeps: int) -> tuple[int, int]:
    """(operations, bytes) of one decode tick: ``kv_lens`` holds the live
    length of each decoded token; ``substeps`` tokens per row are decoded
    in the tick, each sub-step reading every weight once."""
    flops = sum(token_flops(spec) + head_flops(spec) + attn_flops(spec, n) for n in kv_lens)
    kv = spec.layers * spec.kv_bytes_per_token_layer
    nbytes = substeps * weight_bytes(spec) + sum(kv_lens) * kv + len(kv_lens) * kv
    return flops, nbytes


def prefill_flops(spec, spans: list[tuple[int, int]], heads: int) -> int:
    """Operations of the prompt positions ``[start, end)`` computed in one
    prefill tick, plus the LM head of the ``heads`` rows that emit a token."""
    flops = heads * head_flops(spec)
    for start, end in spans:
        n = end - start
        # positions start .. end-1 each attend over position + 1 entries
        flops += n * token_flops(spec) + attn_flops(spec, (start + end + 1) * n // 2)
    return flops


def least_time(flops: float, nbytes: float, peak: dict) -> tuple[float, str]:
    """The least seconds the chip needs, and which bound sets it."""
    t_flops = flops / peak["bf16_flops_per_s"]
    t_bytes = nbytes / peak["hbm_bytes_per_s"]
    return (t_bytes, "hbm") if t_bytes >= t_flops else (t_flops, "flops")
