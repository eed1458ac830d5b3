"""Property tests for the device-side sampling primitives (core/sampling).

These are the serve engine's decoding semantics in isolation: truncation
supports defined by VALUE thresholds (ties included, never sort order),
``temperature=0`` an exact argmax, and draws invariant under jit and under
slot stacking — the property that makes per-request sampling immune
to batch composition (tests/test_serve_scheduler.py proves the end-to-end
version through the engine).

Hypothesis cases randomize logit shapes and knob values; the deterministic
tests beneath them always run, so the file is never vacuous when the
optional dependency is absent.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import sample_token, sample_tokens, split_keys, top_k_mask, \
    top_p_mask

try:                     # optional dev dependency — only the @given tests
    from hypothesis import given, settings, strategies as st
except ImportError:      # skip, not the whole module
    def given(*a, **kw):
        return lambda f: pytest.mark.skip(
            reason="optional dev dependency (pip install .[dev])")(f)

    def settings(**kw):
        return lambda f: f

    class st:            # noqa: N801 — mirrors the hypothesis module name
        @staticmethod
        def data():
            return None


def keyed(seed: int):
    return jax.random.PRNGKey(seed)


_NEG_INF = float("-inf")


# ---------------------------------------------------------------------------
# Truncation supports (numpy reference semantics, ties included)
# ---------------------------------------------------------------------------

def np_top_k_support(logits: np.ndarray, k: int) -> np.ndarray:
    """Boolean support of a tie-inclusive top-k: everything >= the k-th
    largest VALUE survives (0 or >= vocab disables)."""
    v = logits.shape[-1]
    if k <= 0 or k >= v:
        return np.ones_like(logits, bool)
    kth = np.sort(logits)[::-1][k - 1]
    return logits >= kth


def np_top_p_support(logits: np.ndarray, p: float) -> np.ndarray:
    """Boolean support of a tie-inclusive nucleus: the shortest sorted
    prefix reaching mass p, plus every token tied with its boundary."""
    if p >= 1.0:
        return np.ones_like(logits, bool)
    probs = np.exp(logits - logits.max())
    probs = probs / probs.sum()
    order = np.argsort(-probs, kind="stable")
    cum = np.cumsum(probs[order])
    cut = int(np.searchsorted(cum, min(max(p, 1e-6), 1.0)))  # prefix end
    p_min = probs[order[min(cut, len(order) - 1)]]
    return probs >= p_min


@settings(max_examples=50, deadline=None)
@given(st.data())
def test_top_k_support_matches_reference(data):
    v = data.draw(st.integers(2, 24), label="vocab")
    logits = np.asarray(
        data.draw(st.lists(st.floats(-8, 8, allow_nan=False, width=32),
                           min_size=v, max_size=v), label="logits"),
        np.float32)
    k = data.draw(st.integers(0, v + 2), label="k")
    got = np.asarray(top_k_mask(jnp.asarray(logits), k))
    # XLA compares float32 subnormals as zero (it flushes them), NumPy does
    # not: logits [0.0, 1.29e-42] at k = 1 tie on the device, which keeps
    # both.  The reference's support is taken over the values as the device
    # compares them; the kept values themselves pass through unflushed.
    flushed = np.where(np.abs(logits) < np.finfo(np.float32).tiny,
                       np.float32(0), logits)
    want = np.where(np_top_k_support(flushed, k), logits, _NEG_INF)
    np.testing.assert_array_equal(got, want)


@settings(max_examples=50, deadline=None)
@given(st.data())
def test_top_p_support_matches_reference(data):
    v = data.draw(st.integers(2, 24), label="vocab")
    logits = np.asarray(
        data.draw(st.lists(st.floats(-8, 8, allow_nan=False, width=32),
                           min_size=v, max_size=v), label="logits"),
        np.float32)
    p = data.draw(st.floats(0.05, 1.0), label="p")
    got_support = np.isfinite(np.asarray(top_p_mask(jnp.asarray(logits), p)))
    want_support = np_top_p_support(logits, p)
    # float32 softmax/cumsum can disagree with the float64 reference about
    # the exact boundary token when cumulative mass grazes p; the supports
    # must agree whenever the boundary is unambiguous at float32 precision
    probs = np.exp(logits - logits.max()) / np.exp(logits - logits.max()).sum()
    order = np.argsort(-probs, kind="stable")
    cum = np.cumsum(probs[order])
    ambiguous = np.any(np.abs(cum - p) < 1e-5)
    # near-equal probabilities are a second ambiguity source: float32 may
    # see an exact tie (kept together) where float64 resolves an ordering
    gaps = np.abs(probs[:, None] - probs[None, :])
    ambiguous |= bool(np.any(gaps[~np.eye(v, dtype=bool)] < 1e-6))
    if not ambiguous:
        np.testing.assert_array_equal(got_support, want_support)
    # and unconditionally: the kept mass reaches p, and the support is
    # downward-closed in probability (no kept token less probable than a
    # dropped one) — the two properties that define a nucleus
    kept = probs[got_support]
    assert kept.sum() >= min(p, 1.0) - 1e-5
    if got_support.any() and (~got_support).any():
        assert kept.min() >= probs[~got_support].max() - 1e-7


def test_top_k_keeps_boundary_ties():
    """Three-way tie at the k-th value: ALL tied tokens stay in support —
    the mask is a function of logit values, not of sort tie-breaking."""
    logits = jnp.asarray([3.0, 1.0, 1.0, 1.0, 0.0], jnp.float32)
    kept = np.isfinite(np.asarray(top_k_mask(logits, 2)))
    np.testing.assert_array_equal(kept, [True, True, True, True, False])


def test_top_p_keeps_boundary_ties():
    """Tokens tied with the boundary probability are all kept, wherever
    a sort happened to place them."""
    # probs ~ [.4, .2, .2, .2]; p=.5 → prefix is {.4, one .2}, and the
    # tie-inclusion pulls in BOTH remaining .2 tokens
    logits = jnp.log(jnp.asarray([0.4, 0.2, 0.2, 0.2], jnp.float32))
    kept = np.isfinite(np.asarray(top_p_mask(logits, 0.5)))
    np.testing.assert_array_equal(kept, [True, True, True, True])


def test_top_p_masked_mass_renormalizes():
    """The categorical over masked logits IS the renormalized truncated
    distribution: softmax(masked) == probs restricted to the support,
    divided by the kept mass."""
    logits = jnp.asarray([2.0, 1.0, 0.5, -1.0, -3.0], jnp.float32)
    p = 0.8
    masked = top_p_mask(logits, p)
    support = np.isfinite(np.asarray(masked))
    probs = np.asarray(jax.nn.softmax(logits))
    want = np.where(support, probs, 0.0) / probs[support].sum()
    got = np.asarray(jax.nn.softmax(masked))
    np.testing.assert_allclose(got, want, atol=1e-6)


# ---------------------------------------------------------------------------
# sample_token semantics
# ---------------------------------------------------------------------------

@settings(max_examples=40, deadline=None)
@given(st.data())
def test_temperature_zero_is_exact_argmax(data):
    v = data.draw(st.integers(2, 32), label="vocab")
    logits = jnp.asarray(
        data.draw(st.lists(st.floats(-8, 8, allow_nan=False, width=32),
                           min_size=v, max_size=v), label="logits"),
        jnp.float32)
    seed = data.draw(st.integers(0, 2**31 - 1), label="seed")
    tok = sample_token(logits, keyed(seed), 0.0)
    assert int(tok) == int(jnp.argmax(logits))


@settings(max_examples=40, deadline=None)
@given(st.data())
def test_top_k_one_is_greedy_for_any_key(data):
    """top_k=1 truncates to the argmax alone (no ties drawn), so every key
    draws the greedy token even at high temperature."""
    v = data.draw(st.integers(2, 32), label="vocab")
    # unique logits: a k=1 tie would legitimately allow either tied token
    base = np.asarray(
        data.draw(st.lists(st.floats(-8, 8, allow_nan=False, width=32),
                           min_size=v, max_size=v, unique=True),
                  label="logits"), np.float32)
    logits = jnp.asarray(base)
    seed = data.draw(st.integers(0, 2**31 - 1), label="seed")
    temp = data.draw(st.floats(0.1, 4.0), label="temp")
    tok = sample_token(logits, keyed(seed), temp, top_k=1)
    assert int(tok) == int(jnp.argmax(logits))


def test_temperature_to_zero_converges_to_argmax():
    """As temperature → 0 the sampled distribution collapses onto the
    argmax: below a modest temperature every draw IS the argmax."""
    logits = jnp.asarray([0.3, 1.1, 0.9, -0.4], jnp.float32)
    best = int(jnp.argmax(logits))
    for temp in (0.05, 0.01, 0.001):
        toks = [int(sample_token(logits, keyed(s), temp)) for s in range(32)]
        if all(t == best for t in toks):
            return
    raise AssertionError("draws never collapsed onto the argmax")


def test_draws_stay_inside_truncated_support():
    """10k draws from a stacked-knob config never leave the top-k∩top-p
    support (and hit more than one token — it is still a distribution)."""
    logits = jnp.asarray([2.0, 1.8, 1.0, 0.0, -1.0, -9.0], jnp.float32)
    support = np.isfinite(np.asarray(
        top_p_mask(top_k_mask(logits, 4), 0.9)))
    keys = jax.random.split(keyed(0), 10_000)
    toks = np.asarray(jax.vmap(
        lambda k: sample_token(logits, k, 1.0, top_k=4, top_p=0.9))(keys))
    assert support[toks].all()
    assert len(np.unique(toks)) > 1


# ---------------------------------------------------------------------------
# Invariance: jit and slot stacking (the engine's actual call shapes)
# ---------------------------------------------------------------------------

@settings(max_examples=25, deadline=None)
@given(st.data())
def test_draw_invariant_under_jit(data):
    v = data.draw(st.integers(2, 24), label="vocab")
    logits = jnp.asarray(
        data.draw(st.lists(st.floats(-6, 6, allow_nan=False, width=32),
                           min_size=v, max_size=v), label="logits"),
        jnp.float32)
    seed = data.draw(st.integers(0, 2**31 - 1), label="seed")
    temp = data.draw(st.floats(0.0, 3.0), label="temp")
    k = data.draw(st.integers(0, v), label="k")
    p = data.draw(st.floats(0.1, 1.0), label="p")
    eager = sample_token(logits, keyed(seed), temp, k, p)
    jitted = jax.jit(sample_token)(logits, keyed(seed), temp, k, p)
    assert int(eager) == int(jitted)


@settings(max_examples=25, deadline=None)
@given(st.data())
def test_draw_invariant_under_slot_vmap(data):
    """Stacking S slots into one batched call (what the decode step does)
    draws exactly what S independent per-slot calls would — the property
    that makes batch composition invisible to any one request."""
    S = data.draw(st.integers(1, 5), label="slots")
    v = data.draw(st.integers(2, 16), label="vocab")
    logits = jnp.asarray(np.asarray(
        data.draw(st.lists(st.lists(st.floats(-6, 6, allow_nan=False,
                                              width=32),
                                    min_size=v, max_size=v),
                           min_size=S, max_size=S), label="logits"),
        np.float32))
    seeds = data.draw(st.lists(st.integers(0, 2**31 - 1),
                               min_size=S, max_size=S), label="seeds")
    temps = jnp.asarray(data.draw(
        st.lists(st.floats(0.0, 3.0), min_size=S, max_size=S),
        label="temps"), jnp.float32)
    ks = jnp.asarray(data.draw(
        st.lists(st.integers(0, 16), min_size=S, max_size=S), label="ks"),
        jnp.int32)
    ps = jnp.asarray(data.draw(
        st.lists(st.floats(0.1, 1.0), min_size=S, max_size=S), label="ps"),
        jnp.float32)
    keys = jnp.stack([keyed(s) for s in seeds])
    stacked = sample_tokens(logits, keys, temps, ks, ps)
    solo = [sample_token(logits[i], keys[i], temps[i], ks[i], ps[i])
            for i in range(S)]
    assert [int(t) for t in stacked] == [int(t) for t in solo]


# ---------------------------------------------------------------------------
# The batched sampler against the per-slot one it replaced
# ---------------------------------------------------------------------------

def _oracle_sample_token(logits, key, temperature, top_k=0, top_p=1.0):
    """The one-row sampler as the slot-decode step ran it under vmap before
    the sampled work moved under a cond: both masks and the categorical on
    every row, then a select of the argmax for greedy rows."""
    temperature = jnp.asarray(temperature, jnp.float32)
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    scaled = logits.astype(jnp.float32) / jnp.maximum(temperature, 1e-6)
    masked = top_p_mask(top_k_mask(scaled, top_k), top_p)
    drawn = jax.random.categorical(key, masked, axis=-1).astype(jnp.int32)
    return jnp.where(temperature > 0.0, drawn, greedy)


oracle_sample_tokens = jax.jit(jax.vmap(_oracle_sample_token))

_S, _V = 6, 50
_LIVE = [True] * _S
_GREEDY = dict(temp=[0.0] * _S, k=[0] * _S, p=[1.0] * _S, live=_LIVE)
#: per-slot (temperature, top_k, top_p, live) pools; dead rows hold the
#: stale parameters of the request that last held the slot
POOLS = {
    "all_greedy": _GREEDY,
    "one_live_sampled": {**_GREEDY, "temp": [0, 0, 0.9, 0, 0, 0],
                         "k": [0, 0, 7, 0, 0, 0], "p": [1, 1, .8, 1, 1, 1]},
    "top_k_only": {**_GREEDY, "temp": [1.3, 0, 0.8, 1.0, 0, 2.0],
                   "k": [3, 5, 1, 0, 9, 40]},
    "top_p_only": {**_GREEDY, "temp": [1.1, 0.6, 0, 1.0, 2.0, 0.9],
                   "p": [0.5, 0.9, 0.3, 1.0, 0.95, 0.2]},
    "temperature_only": {**_GREEDY, "temp": [0.7, 1.0, 0, 1.5, 3.0, 0.2]},
    "dead_stale_all_greedy": {
        "temp": [0, 1.2, 0, 0.9, 0, 0], "k": [0, 5, 0, 3, 0, 0],
        "p": [1, 0.5, 1, 0.7, 1, 1],
        "live": [True, False, True, False, True, True]},
    "dead_stale_live_sampler": {
        "temp": [0, 1.2, 1.4, 0.9, 0, 0], "k": [0, 5, 0, 3, 0, 0],
        "p": [1, 0.5, 1, 0.7, 1, 1],
        "live": [True, False, True, False, True, True]},
}


@pytest.mark.parametrize("pool", sorted(POOLS))
def test_sample_tokens_matches_per_slot_oracle(pool):
    """On every live row the batched sampler returns what the per-slot
    sampler returned, bit for bit: greedy rows their argmax, sampled rows
    the same keyed draw from the same scaled, masked logits."""
    cfg = POOLS[pool]
    logits = 3.0 * jax.random.normal(keyed(17), (_S, _V), jnp.float32)
    keys = jnp.stack([keyed(1000 + i) for i in range(_S)])
    temp = jnp.asarray(cfg["temp"], jnp.float32)
    k = jnp.asarray(cfg["k"], jnp.int32)
    p = jnp.asarray(cfg["p"], jnp.float32)
    live = np.asarray(cfg["live"])
    got = np.asarray(jax.jit(sample_tokens)(logits, keys, temp, k, p,
                                            live=jnp.asarray(live)))
    want = np.asarray(oracle_sample_tokens(logits, keys, temp, k, p))
    np.testing.assert_array_equal(got[live], want[live])
    greedy = np.asarray(jnp.argmax(logits, axis=-1))
    drawing = live & (np.asarray(temp) > 0)
    np.testing.assert_array_equal(got[~drawing & live], greedy[~drawing & live])
    if drawing.sum() > 1:             # the sampled branch really ran
        assert (got[drawing] != greedy[drawing]).any()


@pytest.mark.parametrize("temp,k,p", [(0.0, 0, 1.0), (0.8, 0, 1.0),
                                      (1.1, 5, 1.0), (0.7, 0, 0.6),
                                      (1.3, 4, 0.8)])
def test_sample_token_matches_per_slot_oracle(temp, k, p):
    """The install's one-row draw is the batched sampler on one live row."""
    logits = 2.0 * jax.random.normal(keyed(5), (_V,), jnp.float32)
    for seed in range(8):
        got = sample_token(logits, keyed(seed), temp, k, p)
        want = _oracle_sample_token(logits, keyed(seed), temp, k, p)
        assert int(got) == int(want), seed


def test_split_keys_matches_per_slot_splits():
    """split_keys advances every slot's chain exactly as a per-slot
    jax.random.split would — the decode step's key threading is the solo
    chain, slot-stacked."""
    keys = jnp.stack([keyed(s) for s in (0, 7, 123)])
    draw, nxt = split_keys(keys)
    for i in range(3):
        d, n = jax.random.split(keys[i])
        np.testing.assert_array_equal(np.asarray(draw[i]), np.asarray(d))
        np.testing.assert_array_equal(np.asarray(nxt[i]), np.asarray(n))
