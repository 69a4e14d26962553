"""Compiled generation fast path: prefill + scanned decode in ONE jitted
executable.

The legacy loop (`ServeEngine.generate` / the seed `InferenceSession.
generate`) dispatched one jitted decode step per prompt token AND per new
token, plus a host-side `jax.random.split` and an implicit device sync per
sampled token — per-step Python/dispatch overhead dominated exactly as the
Jetson profiling literature predicts (arXiv:2508.08430).  Here the whole
generation — cache init, prompt prefill, `lax.scan` decode with on-device
sampling — is a single XLA computation, jitted once per
(plan, batch, prompt-length, n_new) and cached by the caller:

* **Prefill** — ``repro.models.transformer.prefill`` runs the prompt
  through ``exchange_attention`` once and bulk-writes the KV cache
  (attention families).  Recurrent families (hybrid/ssm), and PRISM plans
  under ``prefill_mode="auto"`` (whose compressed prefill is intentionally
  not equivalent to exact per-token decode), use ``prefill_by_decode`` — a
  teacher-forced ``lax.scan`` of ``decode_step``: still one executable,
  just sequential math.
* **Decode** — ``lax.scan`` of ``decode_step`` + on-device sampling with a
  threaded PRNG key (no host round-trips); the cache lives in the scan
  carry so XLA updates it in place.

``dispatch_count()`` counts invocations of compiled generation callables —
the regression tests assert it stays O(1) in prompt length and n_new.
"""
from __future__ import annotations

from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core.exchange import ExchangeConfig, ExchangeMode
from repro.models import transformer as tfm
from repro.models.mla import pad_lanes

_STATS = {"dispatches": 0, "builds": 0}


def dispatch_count() -> int:
    """Compiled generation callables invoked so far (one per generate)."""
    return _STATS["dispatches"]


def build_count() -> int:
    return _STATS["builds"]


def sample_token(logits: jnp.ndarray, key, temperature: float = 0.0):
    """[B, 1, V] → [B, 1] token ids (greedy at T=0)."""
    if temperature <= 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    scaled = logits / temperature
    return jax.random.categorical(key, scaled, axis=-1).astype(jnp.int32)


def resolve_prefill_mode(cfg: ModelConfig, xcfg: ExchangeConfig,
                         mode: str = "auto") -> str:
    """Pick the prefill implementation: "single_pass" or "scan".

    "auto" chooses single-pass when the family supports it AND the
    full-sequence math is exact w.r.t. the decode path.  PRISM plans
    prefill through compressed segment means — the paper's
    distributed-prefill semantics, but not token-for-token equal to the
    legacy decode loop — so "auto" keeps them scanned; pass
    ``prefill_mode="single_pass"`` explicitly for the compressed prefill.
    (MoE layers are dropless: a prompt routes in one pass exactly as its
    tokens do one at a time.)
    """
    if mode == "scan":
        return "scan"
    supported = tfm.supports_prefill(cfg)
    if mode == "single_pass":
        if not supported:
            raise ValueError(f"family {cfg.family!r} has no single-pass "
                             f"prefill (supported: {tfm.PREFILL_FAMILIES})")
        return "single_pass"
    if mode != "auto":
        raise ValueError(f"prefill_mode {mode!r}: one of "
                         f"'auto' | 'single_pass' | 'scan'")
    exact = (xcfg.mode in (ExchangeMode.LOCAL, ExchangeMode.VOLTAGE)
             or xcfg.seq_axis is None or xcfg.seq_shards == 1)
    return "single_pass" if (supported and exact) else "scan"


def prefill_by_decode(params, prompt_tokens: jnp.ndarray, cache,
                      cfg: ModelConfig, xcfg: ExchangeConfig):
    """Teacher-forced prompt consumption as ONE ``lax.scan`` of
    ``decode_step`` → (last logits [B, 1, V], primed cache).

    Compiled fallback where single-pass prefill doesn't apply; identical
    math to the legacy per-token loop, minus T0 dispatches.
    """
    B, T0 = prompt_tokens.shape

    def step(carry, xs):
        c, _ = carry
        tok, idx = xs
        logits, c = tfm.decode_step(params, {"tokens": tok[:, None]}, c,
                                    idx, cfg, xcfg)
        return (c, logits), None

    logits0 = jnp.zeros((B, 1, cfg.vocab_size), jnp.float32)
    (cache, logits), _ = jax.lax.scan(
        step, (cache, logits0),
        (prompt_tokens.T, jnp.arange(T0, dtype=jnp.int32)))
    return logits, cache


def decode_scan(params, cache, tok0: jnp.ndarray, start_index, key,
                cfg: ModelConfig, xcfg: ExchangeConfig,
                temperature: float, n_steps: int):
    """``n_steps`` autoregressive steps from ``tok0`` at ``start_index``,
    sampling on device with a threaded key → (tokens [B, n_steps], cache).
    """
    B = tok0.shape[0]
    if n_steps <= 0:
        return jnp.zeros((B, 0), jnp.int32), cache

    def step(carry, _):
        tok, c, idx, k = carry
        logits, c = tfm.decode_step(params, {"tokens": tok}, c, idx, cfg,
                                    xcfg)
        k, sub = jax.random.split(k)
        nxt = sample_token(logits, sub, temperature)[:, 0:1]
        return (nxt, c, idx + 1, k), nxt[:, 0]

    (_, cache, _, _), toks = jax.lax.scan(
        step, (tok0, cache, jnp.asarray(start_index, jnp.int32), key),
        None, length=n_steps)
    return toks.T, cache                               # [B, n_steps]


def build_generate_fn(cfg: ModelConfig, xcfg: ExchangeConfig, *,
                      n_new: int, temperature: float = 0.0,
                      prefill_mode: str = "auto") -> Callable:
    """One jitted end-to-end generation callable.

    Returns ``fn(params, prompt_tokens [B, T0], extras, key) → [B, n_new]``
    (``extras``: the audio/vlm memory inputs, ``{}`` otherwise).  The whole
    pipeline — cache init, prefill, sampled decode scan — is a single XLA
    computation: a constant number of dispatches regardless of T0 / n_new,
    and the cache never round-trips through Python between tokens.
    """
    mode = resolve_prefill_mode(cfg, xcfg, prefill_mode)

    def gen(params, prompt_tokens, extras, key):
        B, T0 = prompt_tokens.shape
        cache = tfm.init_decode_cache(cfg, B, T0 + n_new)
        if cfg.family in ("audio", "vlm"):
            cache = tfm.prefill_memory(
                params, {"tokens": prompt_tokens, **extras}, cfg, xcfg,
                cache)
        if mode == "single_pass":
            logits, cache = tfm.prefill(
                params, {"tokens": prompt_tokens, **extras}, cache, cfg,
                xcfg)
        else:
            logits, cache = prefill_by_decode(params, prompt_tokens, cache,
                                              cfg, xcfg)
        key, sub = jax.random.split(key)
        tok = sample_token(logits, sub, temperature)[:, 0:1]
        rest, _ = decode_scan(params, cache, tok, T0, key, cfg, xcfg,
                              temperature, n_new - 1)
        return jnp.concatenate([tok, rest], axis=1)

    jitted = jax.jit(gen)
    _STATS["builds"] += 1

    def counted(params, prompt_tokens, extras, key):
        _STATS["dispatches"] += 1
        return jitted(params, prompt_tokens, extras, key)

    counted.jitted = jitted
    counted.prefill_mode = mode
    return counted


# ---------------------------------------------------------------------------
# slot-pool serving primitives (continuous batching)
# ---------------------------------------------------------------------------
#
# The serving runtime (repro.serving) keeps ONE pooled decode cache with a
# slot per in-flight request; requests are admitted into free slots between
# decode chunks.  Three primitives make that work while staying token-exact
# with a per-request `generate`:
#
# * `cache_batch_axes`   — which axis of each cache leaf is the batch/slot
#                          axis (the stacked scan layout moves it around).
# * `build_prefill_fn`   — prime ONE request's cache at the pool length and
#                          sample its first token (same math as `generate`).
# * `build_decode_chunk_fn` — `n_steps` decode steps over ALL slots in one
#                          jitted executable, each slot at its OWN position
#                          (a per-slot vmap of `decode_step` with a
#                          threaded per-slot PRNG key).

def cache_batch_axes(cfg: ModelConfig):
    """Pytree (matching ``init_decode_cache``) of ints: the batch axis of
    every cache leaf.  Derived structurally — the axis whose size follows
    the requested batch — so new families need no per-family table."""
    a = jax.eval_shape(lambda: tfm.init_decode_cache(cfg, 1, 8))
    b = jax.eval_shape(lambda: tfm.init_decode_cache(cfg, 2, 8))

    def axis(x, y):
        for i, (p, q) in enumerate(zip(x.shape, y.shape)):
            if p != q:
                return i
        raise ValueError(f"no batch axis in cache leaf {x.shape}")
    return jax.tree_util.tree_map(axis, a, b)


SLOT_POOL_FAMILIES = ("dense", "moe", "hybrid", "ssm")


def supports_slot_pool(cfg: ModelConfig) -> bool:
    """Tokens-only generative families can be slot-pooled.  audio/vlm
    caches carry per-request memory tensors whose shapes depend on the
    request extras, and non-generative families (vit) have no decode cache
    at all — neither can share one pooled pytree."""
    return cfg.family in SLOT_POOL_FAMILIES


def build_prefill_fn(cfg: ModelConfig, xcfg: ExchangeConfig, *,
                     total_len: int, prefill_mode: str = "auto",
                     with_logits: bool = False) -> Callable:
    """One jitted request-admission executable.

    ``fn(params, prompt_tokens [B, T0], extras, key, temp) → (tok0 [B, 1],
    cache, key')`` — cache init at ``total_len`` (the pool's max length),
    prompt prefill, and the first sampled token, exactly the front half of
    ``build_generate_fn`` (same key threading, so a slot primed here and
    decoded by chunks reproduces ``generate`` token-for-token).  ``temp``
    is a traced scalar (≤0 = greedy), not a compile-time constant — serving
    traffic carries per-request temperatures and must not recompile the
    prefill per distinct value.  ``with_logits=True`` appends the raw
    last-position logits [B, 1, V] to the return (the paged prefix cache
    stores them so a later full-prefix hit can re-sample its own first
    token without re-running the prefill).
    """
    mode = resolve_prefill_mode(cfg, xcfg, prefill_mode)

    def pf(params, prompt_tokens, extras, key, temp):
        B, T0 = prompt_tokens.shape
        cache = tfm.init_decode_cache(cfg, B, total_len)
        if cfg.family in ("audio", "vlm"):
            cache = tfm.prefill_memory(
                params, {"tokens": prompt_tokens, **extras}, cfg, xcfg,
                cache)
        if mode == "single_pass":
            logits, cache = tfm.prefill(
                params, {"tokens": prompt_tokens, **extras}, cache, cfg,
                xcfg)
        else:
            logits, cache = prefill_by_decode(params, prompt_tokens, cache,
                                              cfg, xcfg)
        key, sub = jax.random.split(key)
        greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        sampled = jax.random.categorical(
            sub, logits / jnp.maximum(temp, 1e-6), axis=-1).astype(jnp.int32)
        tok = jnp.where(temp > 0.0, sampled, greedy)[:, 0:1]
        if with_logits:
            return tok, cache, key, logits
        return tok, cache, key

    jitted = jax.jit(pf)
    _STATS["builds"] += 1

    def counted(params, prompt_tokens, extras, key, temp):
        _STATS["dispatches"] += 1
        return jitted(params, prompt_tokens, extras, key, temp)

    counted.jitted = jitted
    counted.prefill_mode = mode
    return counted


def build_admit_fn(cfg: ModelConfig) -> Callable:
    """Fused slot admission: ONE jitted executable scatters a primed B=1
    request cache into row ``slot`` of the pool AND updates the four
    per-slot state vectors (current token, write position, PRNG key,
    temperature).  Issuing these as separate eager ops cost ~5 device
    dispatches per admission — measurably more than the prefill itself.

    ``fn(pool, tok, lengths, keys, temps, req_cache, slot, tok0 [1,1],
    length0, key0, temp0) → (pool, tok, lengths, keys, temps)``.
    """
    axes = cache_batch_axes(cfg)

    def admit(pool, tok, lengths, keys, temps, req_cache, slot, tok0,
              length0, key0, temp0):
        pool = jax.tree_util.tree_map(
            lambda p, r, a: jax.lax.dynamic_update_slice_in_dim(
                p, r.astype(p.dtype), slot, axis=a),
            pool, req_cache, axes)
        tok = tok.at[slot].set(tok0[0, 0])
        lengths = lengths.at[slot].set(length0)
        keys = keys.at[slot].set(key0)
        temps = temps.at[slot].set(temp0)
        return pool, tok, lengths, keys, temps

    return jax.jit(admit)


def build_decode_chunk_fn(cfg: ModelConfig, xcfg: ExchangeConfig, *,
                          n_steps: int,
                          max_len: Optional[int] = None) -> Callable:
    """One jitted continuous-batching decode chunk over a slot pool.

    ``fn(params, pool_cache, tok [S], lengths [S], keys [S], temps [S]) →
    (tokens [S, n_steps], pool_cache, lengths, keys)``: a ``lax.scan`` of a
    per-slot ``vmap`` of ``decode_step``, each slot reading/writing its own
    cache row at its own position with its own PRNG key and sampling
    temperature — per-slot math is identical to a B=1 ``generate`` decode
    (greedy at ``temps[i] <= 0``, categorical otherwise, key split every
    step either way), so pooled decoding stays token-exact per request
    regardless of what shares the pool.  Slots that are free (or already
    finished) keep decoding harmlessly: their writes stay inside their own
    row and admission re-primes the whole row.
    """
    axes = cache_batch_axes(cfg)

    def one(params, tok, cache_slot, idx, key, temp):
        cache_b = jax.tree_util.tree_map(
            lambda t, a: jnp.expand_dims(t, a), cache_slot, axes)
        logits, c = tfm.decode_step(params, {"tokens": tok[None, None]},
                                    cache_b, idx, cfg, xcfg)
        key, sub = jax.random.split(key)
        row = logits[0, 0]
        greedy = jnp.argmax(row, axis=-1).astype(jnp.int32)
        sampled = jax.random.categorical(
            sub, row / jnp.maximum(temp, 1e-6)).astype(jnp.int32)
        nxt = jnp.where(temp > 0.0, sampled, greedy)
        c = jax.tree_util.tree_map(
            lambda t, a: jnp.squeeze(t, axis=a), c, axes)
        return nxt, c, key

    vone = jax.vmap(one, in_axes=(None, 0, axes, 0, 0, 0),
                    out_axes=(0, axes, 0))

    def chunk(params, cache, tok, lengths, keys, temps):
        def step(carry, _):
            tok, cache, lengths, keys = carry
            nxt, cache, keys = vone(params, tok, cache, lengths, keys,
                                    temps)
            lengths = lengths + 1
            if max_len is not None:
                lengths = jnp.minimum(lengths, max_len)
            return (nxt, cache, lengths, keys), nxt

        (tok, cache, lengths, keys), toks = jax.lax.scan(
            step, (tok, cache, lengths, keys), None, length=n_steps)
        return toks.T, cache, lengths, keys

    jitted = jax.jit(chunk)
    _STATS["builds"] += 1

    def counted(params, cache, tok, lengths, keys, temps):
        _STATS["dispatches"] += 1
        return jitted(params, cache, tok, lengths, keys, temps)

    counted.jitted = jitted
    return counted


# ---------------------------------------------------------------------------
# paged-pool serving primitives (block KV cache + prefix caching)
# ---------------------------------------------------------------------------
#
# The paged runtime (repro.serving.pages) replaces per-slot dense caches
# with ONE shared pool of fixed-size KV pages; each request owns a row of a
# [rows, max_pages] page table.  Admission still primes a B=1 dense cache
# with the ordinary prefill executable (page-aligned length), then ONE
# fused scatter moves it into the request's pages.  Decode is ONE jitted
# executable per (plan, rows, max_pages): all rows step together against
# the shared pool (per-row vmap would fork the pool), with per-row
# positions/keys/temps — the per-row sampling math is identical to
# `build_decode_chunk_fn`'s, so paged serving stays token-exact vs
# `session.generate`.

def build_paged_admit_fn(cfg: ModelConfig) -> Callable:
    """Fused paged admission: scatter a primed B=1 dense request cache
    (page-aligned length P0·ps) into pool pages ``page_ids`` [P0] AND set
    the row's state vector entries, in ONE executable (compiled per P0,
    like the prefill is per prompt length).

    ``fn(pool, tok, lengths, keys, temps, req_cache, page_ids, row, tok0,
    length0, key0, temp0) → (pool, tok, lengths, keys, temps)``.
    """

    def admit(pool, tok, lengths, keys, temps, req_cache, page_ids, row,
              tok0, length0, key0, temp0):
        def scatter(p, r):
            # p: [L, P, ps, W] pool leaf; r: [L, 1, P0*ps, Hk, dh] (K/V,
            # W = Hk·dh) or [L, 1, P0*ps, w] (latent; a k_pe is narrower
            # than the pool's: padded)
            ps = p.shape[2]
            P0 = r.shape[2] // ps
            r = r.reshape(r.shape[0], P0, ps, -1)
            return p.at[:, page_ids].set(
                pad_lanes(r, p.shape[-1]).astype(p.dtype))

        pool = jax.tree_util.tree_map(scatter, pool, req_cache)
        tok = tok.at[row].set(tok0[0, 0])
        lengths = lengths.at[row].set(length0)
        keys = keys.at[row].set(key0)
        temps = temps.at[row].set(temp0)
        return pool, tok, lengths, keys, temps

    # the pool is donated: it is orders of magnitude larger than anything
    # else here and every caller rebinds the returned pool, so XLA can
    # scatter in place instead of copying the whole pool per admission
    jitted = jax.jit(admit, donate_argnums=(0,))
    _STATS["builds"] += 1

    def counted(*args):
        _STATS["dispatches"] += 1
        return jitted(*args)

    counted.jitted = jitted
    return counted


def build_paged_hit_fn(cfg: ModelConfig) -> Callable:
    """Fused full-prefix-hit admission: no prefill runs — the request's
    first token is sampled from the prefix entry's *cached* last-position
    logits with the request's own key (the same split/argmax/categorical
    sequence ``build_prefill_fn`` applies, so a hit stays token-exact vs a
    miss), and the row state vectors are set in the same executable.

    ``fn(tok, lengths, keys, temps, row, logits [1,1,V], length0, key0,
    temp0) → (tok, lengths, keys, temps)``.
    """

    def hit(tok, lengths, keys, temps, row, logits, length0, key0, temp0):
        key0, sub = jax.random.split(key0)
        greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        sampled = jax.random.categorical(
            sub, logits / jnp.maximum(temp0, 1e-6),
            axis=-1).astype(jnp.int32)
        t0 = jnp.where(temp0 > 0.0, sampled, greedy)[0, 0]
        tok = tok.at[row].set(t0)
        lengths = lengths.at[row].set(length0)
        keys = keys.at[row].set(key0)
        temps = temps.at[row].set(temp0)
        return tok, lengths, keys, temps

    jitted = jax.jit(hit)
    _STATS["builds"] += 1

    def counted(*args):
        _STATS["dispatches"] += 1
        return jitted(*args)

    counted.jitted = jitted
    return counted


def build_paged_suffix_fn(cfg: ModelConfig, xcfg: ExchangeConfig, *,
                          n_suffix: int) -> Callable:
    """Partial-prefix-hit admission: the shared prefix pages are already
    hot, so only the ``n_suffix`` remaining prompt tokens run — a
    teacher-forced ``lax.scan`` of ``decode_step_paged`` writing straight
    into the request's pages, then the first-token sampling tail of
    ``build_prefill_fn``.  Scanned prefill is token-exact vs single-pass
    for the families the page pool serves (the `test_generate_parity_local`
    equivalence), so hit admissions reproduce miss admissions exactly.

    ``fn(params, pool, row_table [1, MP], suffix [1, n], start_len [1],
    key, temp) → (tok0 [1, 1], pool, key', logits [1, 1, V])``.
    """

    def pf(params, pool, row_table, suffix, start_len, key, temp):
        def step(carry, xs):
            pool, _ = carry
            t, i = xs
            logits, pool, _ = tfm.decode_step_paged(
                params, {"tokens": t[:, None]}, pool, row_table,
                start_len + i, cfg, xcfg)
            return (pool, logits), None

        logits0 = jnp.zeros((1, 1, cfg.vocab_size), jnp.float32)
        (pool, logits), _ = jax.lax.scan(
            step, (pool, logits0),
            (suffix.T, jnp.arange(n_suffix, dtype=jnp.int32)))
        key, sub = jax.random.split(key)
        greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        sampled = jax.random.categorical(
            sub, logits / jnp.maximum(temp, 1e-6), axis=-1).astype(jnp.int32)
        tok = jnp.where(temp > 0.0, sampled, greedy)[:, 0:1]
        return tok, pool, key, logits

    # donated pool: in-place page writes instead of a pool-sized copy per
    # scan carry (the caller always rebinds the returned pool)
    jitted = jax.jit(pf, donate_argnums=(1,))
    _STATS["builds"] += 1

    def counted(*args):
        _STATS["dispatches"] += 1
        return jitted(*args)

    counted.jitted = jitted
    return counted


def build_paged_decode_chunk_fn(cfg: ModelConfig, xcfg: ExchangeConfig, *,
                                n_steps: int) -> Callable:
    """One jitted continuous-batching decode chunk over the paged pool.

    ``fn(params, pool, page_table [S, MP], caps [S], tok [S], lengths [S],
    keys [S], temps [S]) → (tokens [S, n_steps], pool, lengths, keys,
    expert tokens)``; the last is [n_steps, moe_layers, n_held] int32, the
    tokens each held expert computed at each step (None without experts):
    it stays on the device unless a caller reads it.
    All rows advance together through ``decode_step_paged`` (the pool is
    shared state); per-row sampling applies exactly the per-slot math of
    ``build_decode_chunk_fn``.  ``caps`` [S] is each row's last writable
    position (pages assigned · page_size − 1): rows whose requests are done
    or freed keep decoding harmlessly, their writes clamped inside their
    own last page (or the trash page) — active rows are never clamped
    because the runtime allocates pages covering the whole chunk first.
    """

    def samp(row, key, temp):
        key, sub = jax.random.split(key)
        greedy = jnp.argmax(row, axis=-1).astype(jnp.int32)
        sampled = jax.random.categorical(
            sub, row / jnp.maximum(temp, 1e-6)).astype(jnp.int32)
        return jnp.where(temp > 0.0, sampled, greedy), key

    def chunk(params, pool, page_table, caps, tok, lengths, keys, temps):
        def step(carry, _):
            tok, pool, lengths, keys = carry
            pos = jnp.minimum(lengths, caps)
            logits, pool, counts = tfm.decode_step_paged(
                params, {"tokens": tok[:, None]}, pool, page_table, pos,
                cfg, xcfg)
            nxt, keys = jax.vmap(samp)(logits[:, 0], keys, temps)
            return (nxt, pool, pos + 1, keys), (nxt, counts)

        (tok, pool, lengths, keys), (toks, counts) = jax.lax.scan(
            step, (tok, pool, lengths, keys), None, length=n_steps)
        return toks.T, pool, lengths, keys, counts

    # donated pool: the chunk runs every scheduler step, and an undonated
    # pool costs a full pool copy at the jit boundary each time
    jitted = jax.jit(chunk, donate_argnums=(1,))
    _STATS["builds"] += 1

    def counted(*args):
        _STATS["dispatches"] += 1
        return jitted(*args)

    counted.jitted = jitted
    return counted


def generate(params, prompt_tokens: jnp.ndarray, n_new: int,
             cfg: ModelConfig, xcfg: ExchangeConfig, *,
             batch_extras: Optional[Dict[str, Any]] = None, seed: int = 0,
             temperature: float = 0.0, prefill_mode: str = "auto",
             _cache: Optional[Dict] = None) -> jnp.ndarray:
    """Convenience one-shot wrapper (sessions/engines keep their own
    compiled-fn caches; pass ``_cache`` dict to reuse executables)."""
    B, T0 = prompt_tokens.shape
    if n_new <= 0:
        return jnp.zeros((B, 0), jnp.int32)
    key = (B, T0, int(n_new), float(temperature), prefill_mode)
    fns = _cache if _cache is not None else {}
    if key not in fns:
        fns[key] = build_generate_fn(cfg, xcfg, n_new=n_new,
                                     temperature=temperature,
                                     prefill_mode=prefill_mode)
    return fns[key](params, prompt_tokens, dict(batch_extras or {}),
                    jax.random.key(seed))
