"""Device-side stochastic decoding primitives for the serving engine.

One function, :func:`sample_tokens`, maps ``(logits [S, V], keys,
temperature, top_k, top_p, live) -> tokens [S]`` entirely on device
(:func:`sample_token` is its one-row call), so the categorical draw can
live *inside* the jitted slot-decode step (train/steps.make_slot_decode_step)
without adding a host-transfer surface — the engine's one-transfer-per-step
invariant survives sampling untouched (proved structurally by
``repro check trace.one-transfer``).

Semantics (all knobs per request, all disabled by default):

temperature  ``0`` (the default) is exact greedy argmax — the degenerate
             path through the SAME traced step: greedy and sampled requests
             share one compiled program, whose sampling work sits under a
             ``lax.cond`` that a step with no live sampling slot skips.
             ``> 0`` scales logits by ``1/temperature`` before truncation.
top_k        keep the ``k`` highest-logit tokens (``0`` disables).  Ties at
             the k-th logit are all kept, so the support is a function of
             the logit VALUES, not of sort order — draws cannot depend on
             how a sort broke a tie.
top_p        keep the smallest prefix of probability-sorted tokens whose
             mass reaches ``p`` (``1.0`` disables), then renormalize over
             that support (implicitly, via the categorical over masked
             logits).  Tokens tied with the boundary probability are all
             kept, same rationale as top_k.

Determinism: every draw is keyed.  The per-request chain starts at
``jax.random.PRNGKey(request.seed)``; the engine splits it once per emitted
token (install consumes the first split for the prefill draw, each decode
step one more).  A request's k-th token therefore depends only on its own
(logits, seed, k) — never on batch composition — which is what the sampling
conformance tier (tests/test_serve_scheduler.py) asserts bit-exactly.

All ops are element-wise/sort/cumsum + ``jax.random`` (threefry) — pure
device computation, jit-invariant, and each row's draw is keyed by its own
key alone: a row of ``sample_tokens`` draws exactly what ``sample_token``
draws for that row by itself (tests/test_sampling.py).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

#: temperature floor for the scaled-logits path; greedy rows are
#: selected by ``temperature > 0`` so this never changes a returned token,
#: it only keeps their discarded draws finite at temperature == 0
_TEMP_FLOOR = 1e-6

_NEG_INF = float("-inf")


def top_k_mask(logits: jax.Array, k: jax.Array | int) -> jax.Array:
    """Logits with everything below the k-th largest masked to ``-inf``.

    ``k <= 0`` or ``k >= vocab`` disables the mask.  ``k`` may be a traced
    scalar (per-slot values under vmap) — the k-th value is fetched with a
    dynamic gather, not a static index.  Ties at the k-th logit are kept.
    """
    v = logits.shape[-1]
    k = jnp.asarray(k, jnp.int32)
    kth = jnp.take(jnp.sort(logits, axis=-1)[..., ::-1],
                   jnp.clip(k - 1, 0, v - 1), axis=-1)
    active = (k > 0) & (k < v)
    return jnp.where(~active | (logits >= kth), logits, _NEG_INF)


def top_p_mask(logits: jax.Array, p: jax.Array | float) -> jax.Array:
    """Logits outside the top-p (nucleus) support masked to ``-inf``.

    The support is the shortest probability-sorted prefix with cumulative
    mass >= ``p`` — the boundary token that crosses ``p`` is included, and
    so is every token TIED with the boundary probability (the support is
    defined by a probability threshold, never by sort position).  ``p >= 1``
    disables the mask; ``p <= 0`` degenerates to the single most-probable
    token.  The categorical over the masked logits renormalizes the kept
    mass implicitly.
    """
    p = jnp.asarray(p, logits.dtype)
    probs = jax.nn.softmax(logits, axis=-1)
    sorted_p = jnp.sort(probs, axis=-1)[..., ::-1]
    cum = jnp.cumsum(sorted_p, axis=-1)
    # sorted position i is in the prefix iff the mass BEFORE it is < p;
    # maximum() keeps the argmax in-support even at p == 0
    prefix = (cum - sorted_p) < jnp.maximum(p, _TEMP_FLOOR)
    # probability threshold: the smallest kept probability (ties included)
    p_min = jnp.min(jnp.where(prefix, sorted_p, jnp.inf), axis=-1,
                    keepdims=True)
    return jnp.where((p >= 1.0) | (probs >= p_min), logits, _NEG_INF)


@jax.jit
def sample_tokens(logits: jax.Array, keys: jax.Array,
                  temperature: jax.Array | float,
                  top_k: jax.Array | int = 0,
                  top_p: jax.Array | float = 1.0,
                  live: jax.Array | None = None) -> jax.Array:
    """Next-token draws for ``S`` slots: ``logits [S, V]``, ``keys [S, 2]``,
    per-slot (or scalar) ``temperature``, ``top_k``, ``top_p`` -> int32 [S].

    ``temperature == 0`` returns the exact argmax (bit-identical to the
    pre-sampling greedy engine); ``> 0`` draws from the temperature-scaled,
    top-k- then top-p-truncated categorical with the row's own key.

    ``live [S]`` (all rows when None) marks the rows whose token is used.
    The scaling, both masks and the categorical sit under one ``lax.cond``
    that runs only when a live row samples, so a greedy step sorts nothing;
    inside it, each mask runs only when a live sampling row has it enabled
    (a disabled mask returns its input unchanged, so skipping it is exact).
    A row outside ``live`` may get its argmax in place of its draw.  The
    cond is why this is batched and not ``vmap(sample_token)``: under vmap
    a cond becomes a select that runs both branches.  Jitted, so that an
    eager call traces and compiles the conds once per shape.
    """
    s, v = logits.shape
    temperature = jnp.broadcast_to(jnp.asarray(temperature, jnp.float32), (s,))
    top_k = jnp.broadcast_to(jnp.asarray(top_k, jnp.int32), (s,))
    top_p = jnp.broadcast_to(jnp.asarray(top_p, jnp.float32), (s,))
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    sampled = temperature > 0.0
    asks = sampled if live is None else sampled & live

    def keep(x, _):
        return x

    def draw():
        scaled = (logits.astype(jnp.float32)
                  / jnp.maximum(temperature, _TEMP_FLOOR)[:, None])
        scaled = jax.lax.cond(jnp.any(asks & (top_k > 0) & (top_k < v)),
                              jax.vmap(top_k_mask), keep, scaled, top_k)
        masked = jax.lax.cond(jnp.any(asks & ~(top_p >= 1.0)),
                              jax.vmap(top_p_mask), keep, scaled, top_p)
        drawn = jax.vmap(lambda k, x: jax.random.categorical(k, x, axis=-1))(
            keys, masked).astype(jnp.int32)
        return jnp.where(sampled, drawn, greedy)

    return jax.lax.cond(jnp.any(asks), draw, lambda: greedy)


def sample_token(logits: jax.Array, key: jax.Array,
                 temperature: jax.Array | float,
                 top_k: jax.Array | int = 0,
                 top_p: jax.Array | float = 1.0) -> jax.Array:
    """One next-token draw from one slot's logits ``[V]`` (int32 scalar):
    :func:`sample_tokens` on one live row."""
    return sample_tokens(logits[None], jnp.asarray(key)[None], temperature,
                         top_k, top_p)[0]


def split_keys(keys: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Advance a ``[S, 2]`` uint32 per-slot key matrix one step: returns
    ``(draw_keys [S, 2], next_keys [S, 2])``."""
    pairs = jax.vmap(lambda k: jax.random.split(k, 2))(keys)
    return pairs[:, 0], pairs[:, 1]
