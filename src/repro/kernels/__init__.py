"""Pallas TPU kernels for QFT's perf-critical compute:
quant_matmul (deployed W4 int8-dot matmul), decode_attention (flash-decode
over the serving KV cache: slot-masked over the monolithic cache, paged over
the int8 page pool), fake_quant (training offline subgraph), flash_attention
(long-context prefill). ops.py = jit wrappers; ref.py = oracles."""
from .ops import qlinear_deployed, fused_fake_quant, attention_prefill
from .quant_matmul import quant_matmul, default_interpret
from .decode_attention import (decode_attention, decode_tiles_ok,
                               paged_decode_attention, paged_decode_tiles_ok)
from .fake_quant import fake_quant_kernel
from .flash_attention import flash_attention
