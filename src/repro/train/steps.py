"""The QFT step functions — driven by train/qft_trainer and the serve
engine.

train_step  = teacher forward (FP, stop-grad) + student forward (fake-quant,
              offline subgraph inside) + backbone-L2 distillation + Adam.
prefill/decode = the deployed inference graph (serve/).
"""
from __future__ import annotations


import jax
import jax.numpy as jnp

from ..core.distill import qft_loss
from ..core.qconfig import QuantConfig
from ..core.sampling import sample_tokens, split_keys
from ..models import forward, init_model
from ..models.config import ModelConfig
from ..optim.adam import Adam


def abstract_train_state(cfg: ModelConfig, qcfg: QuantConfig | None,
                         opt: Adam):
    """ShapeDtypeStruct stand-ins for (student, opt_state) — what the static
    analyzer (repro.analysis) traces ``make_train_step`` against.  The
    teacher tree shares the student's avals.  No allocation."""
    key = jax.ShapeDtypeStruct((2,), jnp.uint32)
    student = jax.eval_shape(lambda k: init_model(k, cfg, qcfg), key)
    opt_state = jax.eval_shape(opt.init, student)
    return student, opt_state


def make_train_step(cfg: ModelConfig, qcfg: QuantConfig | None, opt: Adam,
                    ce_proportion: float = 0.0,
                    grad_compress=None, grad_mask=None,
                    microbatches: int = 1, plan=None):
    """Returns train_step(student, opt_state, teacher, batch) -> (s, o, metrics).

    ``grad_compress``: optional (compress → decompress residual) hook from
    train/compression.py (int8 gradient all-reduce with error feedback).
    ``grad_mask``: optional fn(path, g) -> g — zero out DoF subsets for the
    paper's frozen-scales ablations (Figs. 8, 9).
    ``microbatches``: gradient accumulation — splits the batch on axis 0 and
    lax.scans the fwd/bwd, dividing live activation memory by the count
    (§Perf: the memory-term lever for 100B+ QFT).
    ``plan``: the resolved core.plan.QuantPlan — the student forward
    fake-quants each tensor at its plan bits (train≡export invariant); the
    FP teacher forward never reads it.
    """

    def loss_fn(student, teacher, batch):
        s_out = forward(student, cfg, qcfg, batch, plan=plan)
        t_out = forward(teacher, cfg, None, batch)
        loss = qft_loss(s_out["hidden"], t_out["hidden"],
                        s_out["logits"] if ce_proportion > 0 else None,
                        t_out["logits"] if ce_proportion > 0 else None,
                        ce_proportion=ce_proportion)
        return loss

    def grads_of(student, teacher, batch):
        if microbatches <= 1:
            return jax.value_and_grad(loss_fn)(student, teacher, batch)
        mb = {k: v.reshape((microbatches, v.shape[0] // microbatches)
                           + v.shape[1:]) for k, v in batch.items()}
        zero = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), student)

        def body(acc, b):
            l, g = jax.value_and_grad(loss_fn)(student, teacher, b)
            acc = jax.tree.map(lambda a, x: a + x.astype(jnp.float32)
                               / microbatches, acc, g)
            return acc, l / microbatches

        grads, losses = jax.lax.scan(body, zero, mb)
        return jnp.sum(losses), grads

    def train_step(student, opt_state, teacher, batch):
        loss, grads = grads_of(student, teacher, batch)
        if grad_mask is not None:
            grads = jax.tree_util.tree_map_with_path(grad_mask, grads)
        if grad_compress is not None:
            grads, opt_state = grad_compress(grads, opt_state)
        student, opt_state = opt.update(grads, opt_state, student)
        gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                             for g in jax.tree.leaves(grads)))
        return student, opt_state, {"loss": loss, "grad_norm": gnorm}

    return train_step


def make_prefill_step(cfg: ModelConfig, qcfg: QuantConfig | None, plan=None):
    """prefill_step(params, cache, batch) -> (next_token_logits, cache).

    ``plan`` matters only for fake-quant (student) serving, qcfg not None —
    deployed artifacts run with qcfg=None and carry real quantized weights.

    The continuous-batching serve engine drives this step for chunked
    per-slot prefill of the **SSM-family** configs (ssm, hybrid): batch-1
    cache, one *exact-length* prompt chunk per call — never padded, because
    a recurrence consumes every token it sees, so pad tokens can't be
    masked out the way attention masks them.  Exact lengths mean one
    compiled trace per distinct remainder length (the documented
    recompile-vs-correctness fallback); attention families use
    :func:`make_bucketed_prefill_step` instead, whose trace count is fixed.
    Prefilling each request alone is what makes its tokens independent of
    what shares the decode batch (tests/test_serve_scheduler.py).
    """

    def prefill_step(params, cache, batch):
        out = forward(params, cfg, qcfg, batch, cache=cache, plan=plan)
        return out["logits"][:, -1], out["cache"]

    return prefill_step


def make_bucketed_prefill_step(cfg: ModelConfig, qcfg: QuantConfig | None,
                               plan=None):
    """prefill_step(params, cache, batch, real_len) -> (logits, cache), for
    right-padded prompt chunks (attention families only).

    The recompile-storm fix: the engine pads every prompt piece up to a
    fixed bucket menu (serve.kv_cache.prefill_buckets), so the number of
    compiled prefill traces is bounded by the menu size no matter what
    prompt lengths arrive.  ``real_len`` is a *traced* int32 scalar — the
    true token count inside the padded chunk; a static argument would
    recompile per length, defeating the fix.

    Correctness under padding: causal attention means real queries never
    attend to the trailing pad keys, and the pad rows written into the
    cache sit at positions >= the slot's final ``pos`` — positions the
    decode mask (``kv_len = pos + 1``) never exposes.  The forward advances
    ``pos`` by the padded length, so it is rolled back to the true length
    here; the returned logits row is the last *real* token's.
    """

    def prefill_step(params, cache, batch, real_len):
        B = batch["tokens"].shape[1]
        out = forward(params, cfg, qcfg, batch, cache=cache, plan=plan)
        logits = jax.lax.dynamic_slice_in_dim(
            out["logits"], real_len - 1, 1, axis=1)[:, 0]
        new_cache = dict(out["cache"])
        new_cache["pos"] = new_cache["pos"] - (B - real_len)
        return logits, new_cache

    return prefill_step


# ---------------------------------------------------------------------------
# Slot-masked decode — the continuous-batching serve engine's step function
# (per-slot prefill reuses make_prefill_step above, batch-1 and chunked)
# ---------------------------------------------------------------------------

def make_slot_decode_step(cfg: ModelConfig, qcfg: QuantConfig | None,
                          plan=None, use_pallas: bool = False,
                          interpret: bool | None = None):
    """Slot-masked decode over the full slot pool — ONE shape-stable call.

    slot_decode_step(params, cache, state) -> (cache, state, emitted, emit)

    ``state``: {cur [S], done [S], counts [S], budget [S], eos [S],
    key [S, 2], temp [S], top_k [S], top_p [S]} — all device-resident, so
    the engine's decode loop needs exactly one host transfer per step
    (fetch (emitted, emit, done)) regardless of slot count.  Dead slots
    (done) still run through the forward — keeping the decode shape static
    across admissions/evictions — but their emissions are masked and their
    bookkeeping frozen.

    Emission order matches the legacy wave engine: the step emits the
    *current* token (prefill's draw on admission, last step's draw after),
    updates done from eos/budget, then decodes to produce the next.

    The next token is drawn DEVICE-SIDE (core/sampling.sample_tokens) from
    each slot's own PRNG key, temperature, top_k and top_p — the per-slot
    key splits once per step, so a request's k-th draw depends only on its
    own (seed, k) and never on batch composition.  ``temp == 0`` (the
    Request default) is exact greedy argmax through this same traced step;
    the sampling work runs only in a step where a live (emitting) slot
    samples, so a greedy step sorts nothing.  The categorical adds zero
    host-transfer surfaces (the one-transfer invariant is re-proved over
    this step, branches included, by ``repro check``).

    ``use_pallas``/``interpret`` come from the engine's DeployPlan and route
    the vector-pos decode attention through the flash-decode kernel
    (models/attention.decode_route); the masked-XLA path is the oracle and
    the tokens must be bit-identical either way (serve conformance tier).
    """

    def slot_decode_step(params, cache, state):
        cur, done = state["cur"], state["done"]
        emit = ~done
        counts = state["counts"] + emit
        done = done | (emit & (cur == state["eos"])) \
                    | (counts >= state["budget"])
        out = forward(params, cfg, qcfg, {"tokens": cur[:, None]},
                      cache=cache, plan=plan, use_pallas=use_pallas,
                      interpret=interpret)
        draw_keys, next_keys = split_keys(state["key"])
        new_cur = sample_tokens(out["logits"][:, -1], draw_keys,
                                state["temp"], state["top_k"],
                                state["top_p"], live=emit)
        new_state = {"cur": new_cur, "done": done, "counts": counts,
                     "budget": state["budget"], "eos": state["eos"],
                     "key": next_keys, "temp": state["temp"],
                     "top_k": state["top_k"], "top_p": state["top_p"]}
        return out["cache"], new_state, cur, emit

    return slot_decode_step
