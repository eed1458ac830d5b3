"""Continuous-batching serving engine for QFT-quantized models.

A :class:`Scheduler` owns an arrival-ordered request queue and a fixed pool
of decode slots backed by one preallocated slot-indexed KV cache
(``serve.deploy.init_slot_cache``).  For the standard-KV families the cache
is **paged int8** by default (serve/kv_cache.py): fixed-size pages from a
shared per-layer pool, a per-slot page table, per-slot/per-kv-head MMSE
scales fitted at install — admission is gated by free *pages* (worst-case
reservation, FIFO), so memory scales with actual context lengths, not
``max_slots * max_len``.  ``ServeConfig(kv_mode="monolithic")`` keeps the
full-precision monolithic layout (the conformance oracle).  Admission
prefills a request ALONE (batch 1, chunked, chunk lengths bucketed to a
fixed menu so compiled prefill traces are bounded) and scatters/quantizes
the finished cache into its slot; a finished slot is refilled by the next
queued request at the next step.  The decode step is ONE jitted
shape-stable call over all slots (dead slots masked, see
train/steps.make_slot_decode_step) with exactly one host transfer per
step — PR 2's device-side-bookkeeping invariant.

Because every request is prefilled alone and decode slots never interact,
a request's output tokens are bit-identical whether it is served alone, in
a static batch, or interleaved under continuous batching — the conformance
contract of tests/test_serve_scheduler.py.

Decoding is per-request seeded sampling (core/sampling.py): each Request
carries ``temperature/top_k/top_p/seed``, the categorical draw runs
device-side inside the jitted slot-decode step (per-slot PRNG key chains
ride the slot state), and ``temperature=0`` — the default — is exact greedy
through the same compiled program.  Tokens can be consumed as they land via
``Engine.stream`` (per-rid iterator) or a ``submit(on_token=...)`` callback;
both transfer token ownership to the consumer the way ``step()`` transfers
finished results, so a long-running server's memory stays bounded.

Weights are the deployment artifact (int4-packed) from serve/deploy.py; on
TPU the matmuls route through kernels/quant_matmul.
"""
from __future__ import annotations

import collections
import dataclasses
import functools
import math
from typing import Callable

import jax
import jax.numpy as jnp

from ..core.fakequant import quantize
from ..core.mmse import ppq_scale
from ..core.qconfig import QuantConfig
from ..core.sampling import sample_token
from ..models import init_cache
from ..models.attention import decode_route
from ..models.config import ModelConfig
from ..train.steps import (make_bucketed_prefill_step, make_prefill_step,
                           make_slot_decode_step)
from .deploy import (DeployPlan, deploy_view, export_for_layers,
                     init_slot_cache, init_slot_state, make_deploy_plan,
                     plan_from_artifact)
from .kv_cache import (BUCKETED_PREFILL_FAMILIES, KVSpec, PageAllocator,
                       bucket_for, resolve_kv_spec)
from .spans import span

#: the totals Engine.stats() reports since reset(); each is what step()'s
#: spans of the same phase carry as arguments, summed
STEP_COUNTERS = ("steps", "prefill_chunks", "prefill_tokens",
                 "prefill_bucket_tokens", "installs", "decode_steps",
                 "decode_live_slot_rows", "decode_kv_pages",
                 "decode_sampled_steps", "tokens_emitted", "retires")


@dataclasses.dataclass
class Request:
    """One serving request.  The sampling knobs are per request and default
    to exact greedy (``temperature=0``); ``seed`` makes sampled decoding
    bit-reproducible — the same request with the same seed emits the same
    tokens regardless of what shares the batch (conformance tier)."""
    prompt: list[int]
    max_new_tokens: int = 32
    eos_id: int = -1                  # -1: never stop early
    temperature: float = 0.0          # 0: greedy argmax (exact)
    top_k: int = 0                    # 0: disabled
    top_p: float = 1.0                # 1: disabled
    seed: int = 0                     # PRNG chain root for sampled draws
    rid: int | None = None            # arrival order; assigned by submit()


@dataclasses.dataclass
class ServeConfig:
    max_slots: int = 8                # fixed decode slot pool
    max_len: int = 512                # per-slot KV capacity
    prefill_chunk: int = 128          # tokens prefilled per slot per step
    #: "paged" — int8 paged KV for the standard-KV families (dense/moe/vlm;
    #: others fall back to monolithic automatically); "monolithic" — the
    #: full-precision [max_slots, max_len] preallocation (the conformance
    #: oracle and the ladder's baseline).
    kv_mode: str = "paged"
    kv_page_size: int = 16            # tokens per KV page
    #: page-pool size; 0 → capacity-equivalent auto
    #: (max_slots * ceil(max_len / kv_page_size))
    kv_pages: int = 0
    slots: dataclasses.InitVar[int | None] = None   # legacy alias

    def __post_init__(self, slots):
        if slots is not None:
            self.max_slots = slots


def _tree_bytes(tree) -> int:
    """Byte size of every array leaf, from shape/dtype metadata only — no
    device sync, works on concrete arrays and eval_shape structs alike."""
    return sum(math.prod(leaf.shape) * jnp.dtype(leaf.dtype).itemsize
               for leaf in jax.tree_util.tree_leaves(tree)
               if hasattr(leaf, "shape") and hasattr(leaf, "dtype"))


class Scheduler:
    """Host-side continuous-batching scheduler: FIFO queue + slot pool.

    Pure bookkeeping (no jax) — admission order is arrival order, freed
    slots are reused lowest-index first so scheduling is deterministic.
    """

    def __init__(self, max_slots: int):
        self.max_slots = max_slots
        self.queue: collections.deque[Request] = collections.deque()
        self.free: list[int] = sorted(range(max_slots), reverse=True)
        self.running: dict[int, int] = {}          # slot -> rid
        self._next_rid = 0

    def submit(self, req: Request) -> int:
        rid = self._next_rid
        self._next_rid += 1
        self.queue.append(dataclasses.replace(req, rid=rid))
        return rid

    def admit(self, can_admit: Callable[[Request], bool] | None = None
              ) -> list[tuple[int, Request]]:
        """Pop queued requests into free slots: [(slot, request), ...].

        ``can_admit`` gates each admission on resources beyond the slot
        itself (the paged engine's free-page check).  Admission stops at the
        FIRST request the predicate rejects — strictly FIFO, so a large
        request at the head waits for pages instead of being starved by
        smaller requests jumping the queue behind it.
        """
        out = []
        while self.free and self.queue:
            if can_admit is not None and not can_admit(self.queue[0]):
                break
            slot = self.free.pop()
            req = self.queue.popleft()
            self.running[slot] = req.rid
            out.append((slot, req))
        return out

    def evict(self, slot: int) -> int:
        """Release a finished slot back to the pool; returns its rid."""
        rid = self.running.pop(slot)
        self.free.append(slot)
        self.free.sort(reverse=True)
        return rid

    @property
    def pending(self) -> int:
        """Requests submitted but not yet finished (queued + running)."""
        return len(self.queue) + len(self.running)


def _activate_state(state, slot, last_logits, budget, eos, temperature,
                    top_k, top_p, seed):
    """Activate ``slot`` in the decode state.  The request's PRNG chain is
    rooted here: ``PRNGKey(seed)`` splits into the first draw (the prefill's
    next-token sample — greedy argmax when ``temperature == 0``) and the
    carry key the decode step advances, so a request's k-th token is a
    function of its own (seed, k) only."""
    draw, carry = jax.random.split(jax.random.PRNGKey(seed))
    first = sample_token(last_logits, draw, temperature, top_k, top_p)
    return {"cur": state["cur"].at[slot].set(first),
            "done": state["done"].at[slot].set(False),
            "counts": state["counts"].at[slot].set(0),
            "budget": state["budget"].at[slot].set(budget),
            "eos": state["eos"].at[slot].set(eos),
            "key": state["key"].at[slot].set(carry),
            "temp": state["temp"].at[slot].set(
                jnp.asarray(temperature, jnp.float32)),
            "top_k": state["top_k"].at[slot].set(
                jnp.asarray(top_k, jnp.int32)),
            "top_p": state["top_p"].at[slot].set(
                jnp.asarray(top_p, jnp.float32))}


def _install_step(cache, state, slot_cache, slot, last_logits, plen,
                  budget, eos, temperature, top_k, top_p, seed):
    """Scatter a finished batch-1 prefill into slot row ``slot`` of the big
    (monolithic) cache and activate the slot.  The whole slot row is
    overwritten, so any garbage the masked decode wrote into a dead slot is
    erased on admission."""

    def leaf(path, big, small):
        if getattr(path[-1], "key", None) == "pos":
            # big: per-slot vector [S]; small: the batch-1 scalar == plen
            return big.at[slot].set(plen)
        if big.shape == small.shape:              # max_slots == 1
            return small.astype(big.dtype)
        axis = next(i for i in range(big.ndim)
                    if big.shape[i] != small.shape[i])
        start = tuple(slot if i == axis else 0 for i in range(big.ndim))
        return jax.lax.dynamic_update_slice(big, small.astype(big.dtype),
                                            start)

    cache = jax.tree_util.tree_map_with_path(leaf, cache, slot_cache)
    state = _activate_state(state, slot, last_logits, budget, eos,
                            temperature, top_k, top_p, seed)
    return cache, state


_INSTALL = jax.jit(_install_step, donate_argnums=(0, 1))


def _paged_install_step(cache, state, slot_cache, slot, pages, last_logits,
                        plen, budget, eos, temperature, top_k, top_p, seed,
                        *, page_size, mmse_iters):
    """Quantize a finished batch-1 prefill into the slot's reserved KV pages
    and activate the slot — the KV tensor class's MMSE init.

    Per layer and per kv-head, an int8 scale is PPQ-fitted (core/mmse, the
    same alternating-projection MMSE every weight tensor gets at init) over
    the slot's *true* prefill rows — rows past ``plen`` (bucketed-prefill
    padding) are zeroed first, which is exactly neutral in the PPQ
    projections (a zero row contributes zero to numerator and denominator).
    The fitted scales are frozen for the slot's lifetime: decode-time tokens
    are quantized on-line with the same scales inside the decode jaxpr, so
    the scales ride the one-transfer step as plain cache leaves.

    ``pages`` is the slot's page list padded to the FIXED page-table width
    with the trash page — one compiled trace regardless of how many pages
    the request reserved (unreserved rows scatter into the trash page, whose
    contents are never exposed by any slot's length mask).
    """
    k_buf, v_buf = slot_cache["k"], slot_cache["v"]  # [L, 1, T, Hkv, hd]
    L, _, T, Hkv, hd = k_buf.shape
    n_pg = pages.shape[0]                            # == max_pages_per_slot
    Tv = n_pg * page_size

    def fit_and_scatter(buf, pool):
        x = buf[:, 0].astype(jnp.float32)            # [L, T, Hkv, hd]
        valid = (jnp.arange(T) < plen)[None, :, None, None]
        x = jnp.where(valid, x, 0.0)
        s = ppq_scale(x, 8, axes=(1, 3), iters=mmse_iters)  # [L,1,Hkv,1]
        q = quantize(x, s, 8).astype(jnp.int8)
        if Tv > T:
            q = jnp.pad(q, ((0, 0), (0, Tv - T), (0, 0), (0, 0)))
        q = q[:, :Tv].reshape(L, n_pg, page_size, Hkv, hd)
        return pool.at[:, pages].set(q), s[:, 0, :, 0]      # [L, Hkv]

    new_k, ks = fit_and_scatter(k_buf, cache["k"])
    new_v, vs = fit_and_scatter(v_buf, cache["v"])
    cache = {"k": new_k, "v": new_v,
             "k_scale": cache["k_scale"].at[:, slot].set(ks),
             "v_scale": cache["v_scale"].at[:, slot].set(vs),
             "pt": cache["pt"].at[slot].set(pages),
             "pos": cache["pos"].at[slot].set(plen)}
    state = _activate_state(state, slot, last_logits, budget, eos,
                            temperature, top_k, top_p, seed)
    return cache, state


_PAGED_INSTALL = functools.partial(
    jax.jit, static_argnames=("page_size", "mmse_iters"),
    donate_argnums=(0, 1))(_paged_install_step)


def _retire_slot(cache, slot, trash):
    """Point an evicted slot's page-table row at the trash page (and zero its
    pos).  The masked decode step writes EVERY slot's current token
    unconditionally — after eviction the slot's old pages may be reallocated
    to another request, so its writes must be redirected before the next
    step or they would alias the new owner's data."""
    return {**cache,
            "pt": cache["pt"].at[slot].set(trash),
            "pos": cache["pos"].at[slot].set(0)}


_RETIRE = jax.jit(_retire_slot, donate_argnums=(0,))


@functools.lru_cache(maxsize=32)
def _serve_steps(cfg: ModelConfig, use_pallas: bool = False,
                 interpret: bool | None = None):
    """Jitted serving step functions, shared across Engine instances of the
    same (ModelConfig, kernel-route) pair (conformance tests build many
    engines per config, routed and unrouted).  ``use_pallas``/``interpret``
    come from the engine's DeployPlan and only affect the slot decode step —
    per-slot prefill is scalar-pos batch-1 and never routes.

    Two prefill steps: the exact-length one (SSM-family fallback) and the
    bucketed pad-and-mask one (attention families) whose compiled-trace
    count is bounded by the bucket menu, not by prompt lengths."""
    prefill = jax.jit(make_prefill_step(cfg, None), donate_argnums=(1,))
    prefill_b = jax.jit(make_bucketed_prefill_step(cfg, None),
                        donate_argnums=(1,))
    decode = jax.jit(
        make_slot_decode_step(cfg, None, use_pallas=use_pallas,
                              interpret=interpret),
        donate_argnums=(1, 2))
    return prefill, prefill_b, decode


def serve_trace_surfaces(cfg: ModelConfig, plan: DeployPlan | None = None,
                         scfg: ServeConfig | None = None) -> dict:
    """Abstract serving surfaces for the static analyzer (repro.analysis).

    Returns the *un-jitted* step functions the engine compiles in
    ``_serve_steps`` plus ShapeDtypeStruct avals for every input (slot cache
    + decode state), so ``jax.make_jaxpr`` can prove structural invariants —
    one host-transfer surface per decode step, kernel routing vs
    ``decode_route`` — for any registry config without building an Engine,
    allocating a cache, or touching a device.
    """
    scfg = scfg if scfg is not None else ServeConfig()
    use_pallas = bool(plan.use_pallas) if plan is not None else False
    interpret = plan.interpret if plan is not None else None
    S = scfg.max_slots
    decode_fn = make_slot_decode_step(cfg, None, use_pallas=use_pallas,
                                      interpret=interpret)
    prefill_fn = make_prefill_step(cfg, None)
    prefill_bucketed_fn = make_bucketed_prefill_step(cfg, None)
    # the same KV-layout decision the engine makes: the analyzer traces the
    # decode step over the paged int8 cache for the families that serve it
    qcfg = plan.qcfg if plan is not None else None
    kv = resolve_kv_spec(cfg, scfg, getattr(qcfg, "kv_bits", 8))
    cache = jax.eval_shape(
        lambda: init_slot_cache(cfg, S, scfg.max_len, kv=kv))
    # eval_shape over the real initializer: the analyzer's avals can never
    # drift from the state the engine actually feeds the decode step (the
    # sampling leaves — key/temp/top_k/top_p — ride along automatically)
    state = jax.eval_shape(lambda: init_slot_state(S))
    return {"decode_fn": decode_fn, "prefill_fn": prefill_fn,
            "prefill_bucketed_fn": prefill_bucketed_fn,
            "cache": cache, "state": state, "scfg": scfg, "kv": kv}


def _attn_layer_count(cfg: ModelConfig) -> int:
    """Attention invocations per slot-decode step — the denominator of the
    kernel-route counters in Engine.stats()."""
    if cfg.family == "hybrid":
        # one shared-attn invocation per group of attn_every mamba layers
        return cfg.n_layers // (cfg.attn_every or 1)
    if cfg.family in ("dense", "moe", "vlm"):
        return cfg.n_layers
    return 0          # ssm: no attention; mla_moe: MLA path, never routes


class TokenStream:
    """Iterator over one request's tokens, in emission order.

    Returned by :meth:`Engine.stream`.  Iterating drives the engine — when
    the buffer is empty and the request hasn't finished, ``__next__`` runs
    ``engine.step()`` ticks until a token lands (requests finished by those
    ticks for OTHER callers are stashed in the engine's collected store, so
    a foreign ``generate()``/``result()`` still sees them).  Token ownership
    transfers to the stream at emission: the engine keeps no copy, and the
    engine's reference to the stream is dropped once the final token is
    buffered — a long-running server's memory stays bounded no matter how
    many streams have completed.  The iterator yields exactly the token list
    ``generate()`` would have returned for the same request.
    """

    def __init__(self, engine: "Engine", rid: int):
        self._engine = engine
        self.rid = rid
        self._buf: collections.deque[int] = collections.deque()
        self._finished = False

    @property
    def finished(self) -> bool:
        """True once the final token was emitted (it may still be buffered
        here, un-iterated — ``finished`` is about the engine, not the
        iterator)."""
        return self._finished

    def _push(self, token: int, fin: bool) -> None:
        """Engine-side delivery of one emitted token (``fin``: the last)."""
        self._buf.append(token)
        self._finished = self._finished or fin

    def __iter__(self) -> "TokenStream":
        return self

    def __next__(self) -> int:
        steps = 0
        # same wedge guard as Engine.generate: all outstanding work serially
        limit = 64 + 2 * sum(self._engine._work.values())
        while not self._buf:
            if self._finished:
                raise StopIteration
            self._engine._step_collecting()
            steps += 1
            if steps > limit:
                raise RuntimeError(
                    f"stream for rid {self.rid} made no progress after "
                    f"{steps} engine steps")
        return self._buf.popleft()


class Engine:
    """Serves a deployment artifact under its DeployPlan.

    Construct either from trained student params (exports inline) or — the
    pipeline path — from an already-exported artifact via ``from_artifact``.

    The serving API is ``submit`` (enqueue, returns an arrival-ordered
    request id; pass ``on_token`` to consume tokens as they land) + ``step``
    (one scheduler tick: admissions, one prefill chunk per prefilling slot,
    one masked decode step; returns the requests finished this tick).
    ``stream`` submits and returns a :class:`TokenStream` iterator;
    ``generate`` is a thin submit-all-then-drain.
    """

    def __init__(self, cfg: ModelConfig, qcfg: QuantConfig, student_params,
                 scfg: ServeConfig | None = None,
                 plan: DeployPlan | None = None):
        if plan is None:
            # resolve the QuantPlan from the student tree so per-tensor bits
            # and packing come from plan lookups, not bare-name heuristics
            plan = make_deploy_plan(qcfg, arch=cfg.name, family=cfg.family,
                                    params=student_params, model_cfg=cfg)
        exported = jax.jit(lambda p: export_for_layers(p, plan))(student_params)
        self._setup(cfg, plan, exported, scfg)

    @classmethod
    def from_artifact(cls, cfg: ModelConfig, plan: DeployPlan, exported,
                      scfg: ServeConfig | None = None) -> "Engine":
        """Build the engine from an exported artifact + its deploy plan
        (no re-export; what launch/serve and the pipeline's serve-smoke use).

        If the caller's DeployPlan carries no resolved QuantPlan (e.g. it was
        rebuilt from a bare QuantConfig), the plan serialized inside the
        artifact at export time is reconstructed — the artifact is the source
        of truth for its own per-tensor decisions."""
        if plan.quant_plan is None:
            qp = plan_from_artifact(exported)
            if qp is not None:
                plan = dataclasses.replace(plan, quant_plan=qp)
        self = cls.__new__(cls)
        self._setup(cfg, plan, exported, scfg)
        return self

    def _setup(self, cfg: ModelConfig, plan: DeployPlan, exported,
               scfg: ServeConfig | None) -> None:
        self.cfg = cfg
        # fresh per-engine config: a dataclass default instance would be
        # shared (and mutable) across every Engine in the process
        self.scfg = scfg if scfg is not None else ServeConfig()
        if self.scfg.max_slots < 1 or self.scfg.prefill_chunk < 1:
            raise ValueError(f"ServeConfig needs max_slots >= 1 and "
                             f"prefill_chunk >= 1, got {self.scfg}")
        self.plan = plan
        self.qcfg = plan.qcfg
        # MoE capacity footgun: the slot-decode step routes max_slots tokens
        # at once, and a worst-case batch sends them all to one expert.  A
        # capacity below that silently DROPS tokens — outputs that are wrong
        # and vary with batch composition — so refuse to build the engine.
        moe = getattr(cfg, "moe", None)
        if moe is not None:
            T = self.scfg.max_slots
            cap = max(int(T * moe.top_k / max(moe.n_experts, 1)
                          * moe.capacity_factor), 1)
            if cap < T:
                min_cf = moe.n_experts / max(moe.top_k, 1)
                raise ValueError(
                    f"MoE capacity_factor={moe.capacity_factor} cannot hold "
                    f"a worst-case decode batch: all max_slots={T} tokens "
                    f"may route to one expert, but per-expert capacity is "
                    f"int({T}*top_k/n_experts*cf)={cap} < {T}, so tokens "
                    f"would be silently dropped (wrong outputs that depend "
                    f"on batch composition). Use capacity_factor >= "
                    f"{min_cf:g} (= n_experts/top_k) or fewer slots.")
        self._kv: KVSpec | None = resolve_kv_spec(
            cfg, self.scfg, getattr(plan.qcfg, "kv_bits", 8))
        self._mmse_iters = getattr(plan.qcfg, "mmse_iters", 10)
        self._bucketed = cfg.family in BUCKETED_PREFILL_FAMILIES
        self.params = jax.jit(lambda e: deploy_view(e, plan))(exported)
        self.exported = exported
        self._prefill, self._prefill_b, self._decode = _serve_steps(
            cfg, bool(plan.use_pallas), plan.interpret)
        # live-buffer accounting (stats()): everything is sized from array
        # shapes+dtypes, so the numbers are machine-independent and cost no
        # device sync.  The per-prefill batch-1 cache is sized via
        # eval_shape — no throwaway allocation.
        self._params_bytes = _tree_bytes(self.params)
        self._artifact_bytes = _tree_bytes(exported)
        self._prefill_slot_bytes = _tree_bytes(
            jax.eval_shape(lambda: init_cache(cfg, 1, self.scfg.max_len)))
        self.reset()

    # ------------------------------------------------------------ lifecycle
    def reset(self) -> None:
        """Fresh serving state: empty queue, all slots free, zeroed cache.
        Compiled step functions are retained — resetting is cheap."""
        S = self.scfg.max_slots
        self.sched = Scheduler(S)
        self.cache = init_slot_cache(self.cfg, S, self.scfg.max_len,
                                     kv=self._kv)
        self.state = init_slot_state(S)
        self._pager = (None if self._kv is None
                       else PageAllocator(self._kv.n_pages))
        self._slot_pages: dict[int, list[int]] = {}  # slot -> reserved pages
        self._slot_pos: dict[int, int] = {}       # live slot -> its pos
        self._peak_slots = 0
        self._prefilling: dict[int, dict] = {}    # slot -> prefill progress
        self._alive: set[int] = set()
        self._sampling: set[int] = set()          # live slots with temp > 0
        self._results: dict[int, list[int]] = {}  # in-flight token streams
        self._collected: dict[int, list[int]] = {}  # finished, drained by a
                                                    # foreign generate() call
        self._consumers: dict[int, TokenStream | Callable[[int, bool], None]]\
            = {}                                  # rid -> stream / callback
        self._work: dict[int, int] = {}           # rid -> step-count estimate
        self._counts = dict.fromkeys(STEP_COUNTERS, 0)
        self._cache_bytes = _tree_bytes(self.cache) + _tree_bytes(self.state)
        self._peak_live_bytes = (self._params_bytes + self._artifact_bytes
                                 + self._cache_bytes)

    # ---------------------------------------------------------- accounting
    def _live_bytes(self) -> int:
        return (self._params_bytes + self._artifact_bytes + self._cache_bytes
                + len(self._prefilling) * self._prefill_slot_bytes)

    def stats(self) -> dict[str, int]:
        """Cheap accounting snapshot: the serving benchmark logs it before
        and after its window (``bench/kinds/serve.py``), and operators read
        it as a running engine's counters.

        Buffer sizes are computed from array shapes/dtypes (params + the
        exported artifact the engine retains + the slot cache & decode
        state + one batch-1 cache per prefilling slot) rather than sampled
        from the OS, so they cost no device sync and read the same on every
        machine.  ``peak_live_bytes`` is high-watermarked at every step()
        (prefill concurrency is the only dynamic term; everything else is
        fixed at reset()).

        ``decode_attn_pallas_layers`` / ``decode_attn_ref_layers`` report the
        per-layer kernel route of the slot decode step: how many attention
        invocations go through a flash-decode Pallas kernel (the paged one
        for a paged cache) vs the masked-XLA reference, per
        models/attention.decode_route — the same predicate the forward uses,
        so the counters can't drift from the actual trace.

        The totals since reset() (``STEP_COUNTERS``) count what step() did:
        ``steps``; ``prefill_chunks``, with their real ``prefill_tokens``
        and the ``prefill_bucket_tokens`` computed for them (bucket
        padding included); ``installs``; ``decode_steps``, with
        ``decode_live_slot_rows`` the live slots summed over them (against
        ``max_slots`` rows each) and ``decode_kv_pages`` the KV pages those
        slots' attention reads, ``ceil(length / page_size)`` a slot (0 for
        a monolithic cache; against ``max_slots * kv_pages_per_slot`` for
        the padded view); ``decode_sampled_steps``, the decode steps with a
        live slot whose request samples (``temperature > 0``): the steps
        that run the sampler's sort, softmax and categorical;
        ``tokens_emitted``; ``retires``.  Each is the sum of the matching
        argument of step()'s spans, but ``decode_sampled_steps`` counts the
        ``engine.decode`` spans whose ``sampled`` is above 0.
        """
        n_attn = _attn_layer_count(self.cfg)
        kv = self._kv
        depth = kv.view_len if kv is not None else self.scfg.max_len
        routed = (n_attn if decode_route(
            self.cfg, depth, self.plan.use_pallas,
            page_size=None if kv is None else kv.page_size) else 0)
        live = self._live_bytes()
        return {
            "decode_attn_pallas_layers": routed,
            "decode_attn_ref_layers": n_attn - routed,
            "params_bytes": self._params_bytes,
            "artifact_bytes": self._artifact_bytes,
            # already at KV precision: the paged cache's int8 pools + scale
            # + page-table leaves are what _tree_bytes sums
            "slot_cache_bytes": self._cache_bytes,
            "prefill_bytes": len(self._prefilling) * self._prefill_slot_bytes,
            "live_bytes": live,
            "peak_live_bytes": max(self._peak_live_bytes, live),
            "queue_depth": len(self.sched.queue),
            "slots_active": len(self._alive),
            "slots_prefilling": len(self._prefilling),
            "max_slots": self.scfg.max_slots,
            "peak_slots_active": max(self._peak_slots, len(self._alive)),
            # page occupancy (0s for a monolithic cache)
            "kv_page_size": 0 if self._kv is None else self._kv.page_size,
            "kv_pages_total": 0 if self._kv is None else self._kv.n_pages,
            "kv_pages_free": 0 if self._pager is None else self._pager.n_free,
            **self._counts,
        }

    # ------------------------------------------------------------ serve API
    def _validate(self, request: Request) -> None:
        p = request.prompt
        if not isinstance(p, (list, tuple)) or len(p) == 0:
            raise ValueError(
                f"request prompt must be a non-empty token list, got {p!r}")
        if request.max_new_tokens < 1:
            raise ValueError(
                f"max_new_tokens must be >= 1, got {request.max_new_tokens}")
        need = len(p) + request.max_new_tokens
        if need > self.scfg.max_len:
            raise ValueError(
                f"request needs {need} cache positions ({len(p)} prompt + "
                f"{request.max_new_tokens} new) but ServeConfig.max_len is "
                f"{self.scfg.max_len}; raise max_len or shorten the request")
        if self._kv is not None:
            n_need = self._kv.pages_for(need)
            if n_need > self._kv.n_pages:
                raise ValueError(
                    f"request needs {n_need} KV pages ({need} tokens at "
                    f"page size {self._kv.page_size}) but the page pool "
                    f"has only {self._kv.n_pages}; raise ServeConfig."
                    f"kv_pages or shorten the request")
        if not (request.temperature >= 0.0
                and math.isfinite(request.temperature)):
            raise ValueError(
                f"temperature must be finite and >= 0 (0 = greedy), got "
                f"{request.temperature}")
        if request.top_k < 0:
            raise ValueError(
                f"top_k must be >= 0 (0 disables), got {request.top_k}")
        if not (0.0 < request.top_p <= 1.0):
            raise ValueError(
                f"top_p must be in (0, 1] (1 disables), got {request.top_p}")

    def _enqueue(self, request: Request) -> int:
        self._validate(request)
        rid = self.sched.submit(request)
        self._work[rid] = (-(-len(request.prompt) // self.scfg.prefill_chunk)
                           + request.max_new_tokens)
        return rid

    def submit(self, request: Request,
               on_token: Callable[[int, bool], None] | None = None) -> int:
        """Enqueue a request; returns its arrival-ordered id.

        With ``on_token``, every emitted token is pushed to the callback as
        ``on_token(token, done)`` (``done`` true on the final token) instead
        of being buffered — the engine keeps no copy and the finished
        request does NOT appear in ``step()``'s returned dict (ownership
        went to the callback)."""
        rid = self._enqueue(request)
        if on_token is not None:
            self._consumers[rid] = on_token
        else:
            self._results[rid] = []
        return rid

    def stream(self, request: Request) -> TokenStream:
        """Submit ``request`` and return a :class:`TokenStream` yielding its
        tokens in emission order (iteration drives the engine as needed)."""
        rid = self._enqueue(request)
        ts = TokenStream(self, rid)
        self._consumers[rid] = ts
        return ts

    def pending(self) -> int:
        """Submitted-but-unfinished request count (drive step() while > 0)."""
        return self.sched.pending

    def result(self, rid: int) -> list[int]:
        """Tokens for a request: in-flight progress for a pending rid, or —
        once, popping it — a finished request whose tokens were drained by
        someone else's generate() call.  Finished requests are otherwise
        handed to the step() caller and not retained (bounded memory)."""
        if rid in self._results:
            return list(self._results[rid])
        return self._collected.pop(rid)

    def step(self) -> dict[int, list[int]]:
        """One scheduler tick.  Returns {rid: tokens} for requests that
        finished this tick — ownership transfers to the caller (the engine
        drops its copy, keeping a long-running server's memory bounded).

        1. admission: free slots pull from the queue (arrival order);
        2. chunked prefill: each prefilling slot advances one prompt chunk
           in its own batch-1 cache; finished prefills are scattered into
           the slot cache and the slot activates;
        3. decode: ONE jitted call over all slots + ONE host transfer;
        4. delivery: each live slot's token goes to its consumer, and each
           finished slot is retired.

        Each phase is a profiler span (serve/spans.py; nothing is recorded
        while no profiler session runs), nested as the code nests:
        ``engine.step`` holds ``engine.admit`` (``admitted``), one
        ``engine.prefill`` per prompt chunk (``rid``, ``slot``, ``tokens``,
        ``bucket``: the real and the computed length), one
        ``engine.install`` per finished prefill (``rid``, ``slot``,
        ``plen``), ``engine.decode`` (``live``, ``slots``, ``kv_pages``,
        ``sampled``: the live slots whose request samples),
        ``engine.sync`` (the transfer) and ``engine.deliver`` (``emitted``,
        ``finished``), which holds one ``engine.retire`` (``slot``) per
        finished slot.
        The same numbers are added to the totals in ``stats()``.
        """
        scfg, count = self.scfg, self._counts
        with span("engine.step"):
            count["steps"] += 1
            with span("engine.admit") as sp:
                admitted = self._admit()
                sp.set_metadata(admitted=admitted)
            # prefill concurrency peaks right after admission, before installs
            self._peak_live_bytes = max(self._peak_live_bytes,
                                        self._live_bytes())

            for slot in sorted(self._prefilling):
                st = self._prefilling[slot]
                req, off = st["req"], st["off"]
                k = min(scfg.prefill_chunk, len(req.prompt) - off)
                # pad-and-mask to the fixed bucket menu: compiled prefill
                # traces are bounded by the menu, not by prompt lengths
                b = bucket_for(k, scfg.prefill_chunk) if self._bucketed else k
                count["prefill_chunks"] += 1
                count["prefill_tokens"] += k
                count["prefill_bucket_tokens"] += b
                with span("engine.prefill", rid=req.rid, slot=slot, tokens=k,
                          bucket=b):
                    chunk = list(req.prompt[off: off + k])
                    if self._bucketed:
                        toks = jnp.asarray([chunk + [0] * (b - k)], jnp.int32)
                        logits, st["cache"] = self._prefill_b(
                            self.params, st["cache"], {"tokens": toks},
                            jnp.asarray(k, jnp.int32))
                    else:
                        toks = jnp.asarray([chunk], jnp.int32)
                        logits, st["cache"] = self._prefill(
                            self.params, st["cache"], {"tokens": toks})
                st["off"] = off + k
                if st["off"] == len(req.prompt):
                    count["installs"] += 1
                    with span("engine.install", rid=req.rid, slot=slot,
                              plen=len(req.prompt)):
                        self._install(slot, st, logits[0])
                    self._alive.add(slot)
                    if req.temperature > 0:
                        self._sampling.add(slot)
                    del self._prefilling[slot]
            self._peak_slots = max(self._peak_slots, len(self._alive))

            finished: dict[int, list[int]] = {}
            if not self._alive:
                return finished
            live = len(self._alive)
            pages = self._decode_pages()
            count["decode_steps"] += 1
            count["decode_live_slot_rows"] += live
            count["decode_kv_pages"] += pages
            sampled = len(self._sampling)
            count["decode_sampled_steps"] += int(sampled > 0)
            with span("engine.decode", live=live, slots=scfg.max_slots,
                      kv_pages=pages, sampled=sampled):
                self.cache, self.state, emitted, emit = self._decode(
                    self.params, self.cache, self.state)
            with span("engine.sync"):
                toks_h, emit_h, done_h = jax.device_get(  # qft: noqa[QFT003]
                    (emitted, emit, self.state["done"]))  # the step's ONE sync
            with span("engine.deliver") as sp:
                n_emitted = n_finished = 0
                for slot in sorted(self._alive):
                    rid = self.sched.running[slot]
                    if emit_h[slot]:
                        n_emitted += 1
                        self._deliver(rid, int(toks_h[slot]),
                                      bool(done_h[slot]))
                    if done_h[slot]:
                        n_finished += 1
                        with span("engine.retire", slot=slot):
                            self._retire(slot)
                        del self._work[rid]
                        toks = self._finish_rid(rid)
                        if toks is not None:
                            finished[rid] = toks
                count["tokens_emitted"] += n_emitted
                count["retires"] += n_finished
                sp.set_metadata(emitted=n_emitted, finished=n_finished)
            return finished

    def _admit(self) -> int:
        """Admission: move queued requests into free slots, each with a
        fresh batch-1 prefill cache (and, paged, its reserved pages).
        Returns how many were admitted."""
        can = None
        reserved: dict[int, list[int]] = {}      # rid -> pages, this round
        if self._pager is not None:
            # admit by free pages, reserving AT the admission decision —
            # Scheduler.admit approves several requests per round, so a
            # check-then-allocate-later gate would approve two requests
            # against the same free pages (strictly FIFO; see
            # Scheduler.admit for the no-starvation contract)
            def can(r: Request) -> bool:
                n = self._pages_needed(r)
                if not self._pager.can_alloc(n):
                    return False
                reserved[r.rid] = self._pager.alloc(n)
                return True
        admitted = self.sched.admit(can)
        for slot, req in admitted:
            st = {"req": req, "off": 0,
                  "cache": init_cache(self.cfg, 1, self.scfg.max_len)}
            if self._pager is not None:
                st["pages"] = reserved.pop(req.rid)
            self._prefilling[slot] = st
        assert not reserved       # every reservation was claimed by a slot
        return len(admitted)

    def _install(self, slot: int, st: dict, last_logits) -> None:
        """Scatter a finished prefill (``st``) into ``slot`` of the slot
        cache and activate the slot: into its reserved pages, quantized,
        when paged; into its row of the monolithic cache otherwise."""
        req = st["req"]
        if self._kv is not None:
            pages = st["pages"]
            padded = pages + [self._kv.trash_page] * (
                self._kv.max_pages_per_slot - len(pages))
            self.cache, self.state = _PAGED_INSTALL(
                self.cache, self.state, st["cache"], slot,
                jnp.asarray(padded, jnp.int32), last_logits,
                len(req.prompt), req.max_new_tokens, req.eos_id,
                req.temperature, req.top_k, req.top_p, req.seed,
                page_size=self._kv.page_size, mmse_iters=self._mmse_iters)
            self._slot_pages[slot] = pages
            self._slot_pos[slot] = len(req.prompt)
        else:
            self.cache, self.state = _INSTALL(
                self.cache, self.state, st["cache"], slot, last_logits,
                len(req.prompt), req.max_new_tokens, req.eos_id,
                req.temperature, req.top_k, req.top_p, req.seed)

    def _retire(self, slot: int) -> None:
        """Hand a finished slot back to the scheduler; paged, redirect its
        page-table row to the trash page before the next decode step, then
        return its pages to the pool for reuse."""
        self.sched.evict(slot)
        self._alive.discard(slot)
        self._sampling.discard(slot)
        if self._pager is not None:
            self.cache = _RETIRE(self.cache, slot, self._kv.trash_page)
            self._pager.release(self._slot_pages.pop(slot))
            del self._slot_pos[slot]

    def _decode_pages(self) -> int:
        """KV pages this decode step's attention reads over the live slots,
        ``ceil((pos + 1) / page_size)`` each (the token written at ``pos``
        included), advancing every live slot's ``pos``; 0 when the cache
        is not paged."""
        if self._kv is None:
            return 0
        P, pages = self._kv.page_size, 0
        for slot, pos in self._slot_pos.items():
            pages += pos // P + 1
            self._slot_pos[slot] = pos + 1
        return pages

    def _pages_needed(self, req: Request) -> int:
        return self._kv.pages_for(len(req.prompt) + req.max_new_tokens)

    def _deliver(self, rid: int, token: int, fin: bool) -> None:
        """Route one emitted token: stream buffer / callback for consumer
        rids, the engine-owned in-flight list otherwise."""
        consumer = self._consumers.get(rid)
        if consumer is None:
            self._results[rid].append(token)
        elif isinstance(consumer, TokenStream):
            consumer._push(token, fin)
        else:
            consumer(token, fin)

    def _finish_rid(self, rid: int) -> list[int] | None:
        """Release a finished rid.  Consumer rids already own every token —
        drop the engine's consumer reference (bounded memory) and return
        None so step() does not re-report them; buffered rids hand their
        token list to the step() caller."""
        if self._consumers.pop(rid, None) is not None:
            return None
        return self._results.pop(rid)

    def _step_collecting(self) -> None:
        """One tick with any finished buffered requests stashed in the
        collected store — what a TokenStream uses to drive the engine, so
        requests it finishes for other callers stay retrievable via
        ``result()``."""
        self._collected.update(self.step())

    def generate(self, requests: list[Request]) -> list[list[int]]:
        """Serve a list of requests to completion (submit-all + drain).

        Any request count works — requests beyond the slot pool queue and
        are admitted as slots free up."""
        if not requests:
            raise ValueError("Engine.generate needs a non-empty request "
                             "list; got an empty one")
        for r in requests:       # all-or-nothing: a bad request mid-list
            self._validate(r)    # must not leave earlier ones enqueued
        rids = set(self.submit(r) for r in requests)
        # generous upper bound over ALL outstanding work (the drain also
        # finishes requests submitted earlier through submit()): every
        # prompt chunk + every decode step could happen serially; past it
        # something is wedged — fail, don't hang
        limit = 64 + 2 * sum(self._work.values())
        collected: dict[int, list[int]] = {}
        steps = 0
        while self.pending():
            collected.update(self.step())
            steps += 1
            if steps > limit:
                raise RuntimeError(
                    f"serve loop made no progress after {steps} steps "
                    f"({self.pending()} requests still pending)")
        # foreign rids drained alongside ours stay retrievable via result()
        self._collected.update(
            (rid, toks) for rid, toks in collected.items()
            if rid not in rids)
        return [collected[rid] for rid in sorted(rids)]
