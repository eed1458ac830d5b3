"""Operations and bytes worked out from shapes: what the algorithm needs,
not what a kernel happens to compute (no padded rows, no masked-out half
of causal attention, no recomputation under remat).

``c`` is a size dict as ``reference.dims`` gives it.
"""
from __future__ import annotations


def layer_matmul_params(c: dict) -> int:
    d, H, Hkv, hd, ff = c["d"], c["H"], c["Hkv"], c["hd"], c["ff"]
    return d * H * hd + 2 * d * Hkv * hd + H * hd * d + 3 * d * ff


def matmul_params(c: dict, head: bool) -> int:
    """Parameters that multiply each token: every layer's kernels, and the
    lm_head when its logits are used.  Embedding lookups multiply nothing."""
    return c["L"] * layer_matmul_params(c) + (c["d"] * c["V"] if head else 0)


def causal_attention(c: dict, offset: int, n: int) -> int:
    """Forward attention operations of ``n`` queries at positions
    ``offset .. offset + n - 1``, each over its causal prefix, all layers:
    2 matmuls x 2 operations x heads x head size x keys."""
    keys = n * offset + n * (n + 1) // 2
    return c["L"] * 4 * c["H"] * c["hd"] * keys


def qft_step(c: dict, batch: int, seq: int, ce_proportion: float) -> int:
    """One QFT step: teacher forward, student forward and backward (the
    backward twice the forward), for the work the loss uses."""
    per_seq = (8 * matmul_params(c, head=ce_proportion > 0) * seq
               + 4 * causal_attention(c, 0, seq))
    return batch * per_seq


def prefill_chunk(c: dict, offset: int, n: int) -> int:
    """A prompt chunk of ``n`` real tokens after ``offset`` cached ones;
    only its last token's logits are needed."""
    return (2 * matmul_params(c, head=False) * n + 2 * c["d"] * c["V"]
            + causal_attention(c, offset, n))


def decode_token(c: dict, length: int) -> int:
    """One decoded token whose attention reads ``length`` cached keys."""
    return (2 * matmul_params(c, head=True)
            + c["L"] * 4 * c["H"] * c["hd"] * length)


def decode_attention(c: dict, lengths, kv_bytes: int = 1) -> tuple[int, int]:
    """One decode-attention call per layer over slots of the given live
    lengths, all layers: (operations, bytes).  Bytes: each live K and V
    row once at ``kv_bytes`` per element, plus q and the output in f32 and
    the per-head scales."""
    L, H, Hkv, hd = c["L"], c["H"], c["Hkv"], c["hd"]
    total = sum(lengths)
    ops = L * 4 * H * hd * total
    nbytes = L * (2 * Hkv * hd * kv_bytes * total
                  + len(lengths) * (2 * H * hd * 4 + 2 * Hkv * 4))
    return ops, nbytes
