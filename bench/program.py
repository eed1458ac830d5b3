"""The system under test, as the benchmark reaches it: the registry's model
configuration cut as the configuration file says, and checks that what the
program will run is what the file states."""
from __future__ import annotations

import dataclasses

from .harness import ROOT, BenchError

#: configuration-file key -> ModelConfig field
_FIELDS = {"num_hidden_layers": "n_layers", "hidden_size": "d_model",
           "num_attention_heads": "n_heads",
           "num_key_value_heads": "n_kv_heads", "head_dim": "head_dim",
           "intermediate_size": "d_ff", "vocab_size": "vocab",
           "rope_theta": "rope_theta", "qk_norm": "qk_norm",
           "tie_word_embeddings": "tie_embeddings"}


def import_program():
    import sys
    src = str(ROOT / "src")
    if src not in sys.path:
        sys.path.insert(0, src)


def model_config(config: dict):
    """``registry`` entry with ``replace`` applied (padding re-derived),
    refused unless every size matches the file's ``model``."""
    import_program()
    from repro.configs.registry import get_config
    prog = config["program"]
    base = get_config(prog["registry"])
    cfg = dataclasses.replace(base, **prog.get("replace", {}),
                              n_heads_padded=0, n_kv_heads_padded=0,
                              vocab_padded=0)
    model = config["model"]
    for key, field in _FIELDS.items():
        want = model.get(key, False if key in ("qk_norm",
                                               "tie_word_embeddings") else None)
        got = getattr(cfg, field)
        if want is None or got != type(got)(want):
            raise BenchError(f"the program's {field}={got!r} departs from the "
                             f"configuration's {key}={want!r}")
    if (cfg.family, cfg.mlp, cfg.bias) != ("dense", "swiglu", False):
        raise BenchError(f"{cfg.name}: the reference covers dense SwiGLU "
                         f"models without biases only")
    return cfg


def quant_config(quant: dict):
    """The program's QuantConfig for the traffic's ``quant`` entry."""
    import_program()
    from repro.core import qconfig
    qcfg = getattr(qconfig, quant["mode"])()
    for key in ("w_bits", "a_bits", "embed_bits"):
        if getattr(qcfg, key) != quant[key]:
            raise BenchError(f"{quant['mode']} has {key}="
                             f"{getattr(qcfg, key)}, the traffic states "
                             f"{quant[key]}")
    return qcfg


def check_plan(plan, quant: dict) -> None:
    """Every kernel at ``w_bits``, embedding and head at ``embed_bits``: the
    grid the reference quantizes on."""
    for path, spec in plan.entries:
        if path == "kv_cache":
            continue
        want = (quant["embed_bits"] if path in ("embed", "lm_head")
                else quant["w_bits"])
        if spec.w_bits != want:
            raise BenchError(f"the plan puts {path} at {spec.w_bits} bits; "
                             f"the reference quantizes it at {want}")


def same_tree(ours, theirs, what: str) -> None:
    """Refuse unless the benchmark's tree has the program's structure,
    shapes and dtypes."""
    import jax
    a = jax.tree_util.tree_structure(ours)
    b = jax.tree_util.tree_structure(theirs)
    if a != b:
        raise BenchError(f"{what}: the benchmark's tree {a} is not the "
                         f"program's {b}")
    for x, y in zip(jax.tree.leaves(ours), jax.tree.leaves(theirs)):
        if (x.shape, x.dtype) != (y.shape, y.dtype):
            raise BenchError(f"{what}: leaf {x.shape} {x.dtype} is "
                             f"{y.shape} {y.dtype} in the program")
