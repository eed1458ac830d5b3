"""Architecture registry: ``--arch <id>`` → its config module.

10 assigned architectures + the paper-faithful CNN. Modality frontends
(audio frames, vision patches) are stubbed as precomputed embeddings.
"""
from __future__ import annotations

import importlib

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
