"""Architecture & shape registry: ``--arch <id>`` × input-shape cells.

10 assigned architectures (each with its own shape set) + the paper-faithful
CNN. ``input_specs`` returns ShapeDtypeStruct stand-ins (no allocation) for
every model input; modality frontends (audio frames, vision patches) are
stubbed as precomputed embeddings per the assignment.
"""
from __future__ import annotations

import dataclasses
import importlib
from typing import Any

import jax
import jax.numpy as jnp

ARCH_IDS = [
    "qwen2-vl-7b", "deepseek-v2-236b", "qwen2-moe-a2.7b", "zamba2-7b",
    "qwen3-32b", "command-r-plus-104b", "qwen3-8b", "phi4-mini-3.8b",
    "seamless-m4t-medium", "mamba2-1.3b",
]

_MODULES = {
    "qwen2-vl-7b": "qwen2_vl_7b",
    "deepseek-v2-236b": "deepseek_v2_236b",
    "qwen2-moe-a2.7b": "qwen2_moe_a2_7b",
    "zamba2-7b": "zamba2_7b",
    "qwen3-32b": "qwen3_32b",
    "command-r-plus-104b": "command_r_plus_104b",
    "qwen3-8b": "qwen3_8b",
    "phi4-mini-3.8b": "phi4_mini_3_8b",
    "seamless-m4t-medium": "seamless_m4t_medium",
    "mamba2-1.3b": "mamba2_1_3b",
    "paper-cnn": "paper_cnn",
}


@dataclasses.dataclass(frozen=True)
class ShapeSpec:
    name: str
    seq_len: int
    global_batch: int
    kind: str                  # train | prefill | decode


SHAPES = {
    "train_4k": ShapeSpec("train_4k", 4096, 256, "train"),
    "prefill_32k": ShapeSpec("prefill_32k", 32768, 32, "prefill"),
    "decode_32k": ShapeSpec("decode_32k", 32768, 128, "decode"),
    "long_500k": ShapeSpec("long_500k", 524288, 1, "decode"),
}

# long_500k needs sub-quadratic attention → SSM / hybrid only (DESIGN.md §6).
_SUBQUADRATIC = {"zamba2-7b", "mamba2-1.3b"}


def get_module(arch: str):
    return importlib.import_module(f"repro.configs.{_MODULES[arch]}")


def get_config(arch: str, smoke: bool = False, reduced: bool = False):
    """``smoke``: the CPU-sized test preset.  ``reduced``: one chip's share
    of a stated deployment at published widths (the module's ``REDUCED``,
    with the cut keys in ``reduced`` and the deployment in ``DEPLOYMENT``)."""
    m = get_module(arch)
    if reduced:
        if not hasattr(m, "REDUCED"):
            raise ValueError(f"{arch} has no one-chip REDUCED config")
        return m.REDUCED
    return m.SMOKE if smoke else m.CONFIG


def skip_reason(arch: str, shape: str) -> str | None:
    if shape == "long_500k" and arch not in _SUBQUADRATIC:
        return "full-attention arch: 500k decode needs sub-quadratic attention"
    return None


def cell_list(include_skips: bool = False) -> list[tuple[str, str, str | None]]:
    out = []
    for a in ARCH_IDS:
        for s in SHAPES:
            r = skip_reason(a, s)
            if r is None or include_skips:
                out.append((a, s, r))
    return out


# --------------------------------------------------------------------------
# input_specs — ShapeDtypeStruct stand-ins for every model input
# --------------------------------------------------------------------------

def input_specs(arch: str, shape: str, cfg=None) -> dict[str, Any]:
    """Inputs for the step function of this (arch, shape) cell.

    train/prefill: full-sequence batch.  decode: one new token per sequence
    (the KV cache itself is built separately — see launch.dryrun).
    """
    cfg = cfg or get_config(arch)
    sp = SHAPES[shape]
    B, S = sp.global_batch, sp.seq_len
    f32, i32 = jnp.float32, jnp.int32
    d = cfg.d_model

    def tok(b, s):
        return jax.ShapeDtypeStruct((b, s), i32)

    fam = cfg.family
    if sp.kind == "decode":
        batch: dict[str, Any] = {"tokens": tok(B, 1)}
        if fam == "vlm":
            batch["positions"] = jax.ShapeDtypeStruct((B, 3, 1), i32)
        if fam == "encdec":
            pass  # cross-KV comes from the cache; decoder token only
        return batch

    if fam == "vlm":
        # dynamic-resolution stub: ¼ of the context is image patches
        s_img = S // 4
        return {"tokens": tok(B, S - s_img),
                "patch_embeds": jax.ShapeDtypeStruct((B, s_img, d), jnp.bfloat16),
                "positions": jax.ShapeDtypeStruct((B, 3, S), i32)}
    if fam == "encdec":
        # audio stub: S encoder frames, S//8 decoder (text) tokens
        return {"frames": jax.ShapeDtypeStruct((B, S, d), jnp.bfloat16),
                "tokens": tok(B, max(S // 8, 16))}
    return {"tokens": tok(B, S)}
