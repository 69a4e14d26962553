"""Compiled generation fast path: parity with the legacy per-token loop,
single-pass prefill correctness, and the O(1)-dispatch regression."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import ExecutionPlan, InferenceSession
from repro.api import generation as gen
from repro.configs import get_config
from repro.models import registry
from repro.models import transformer as tfm


def _cfg(arch="llama3.2-1b", **over):
    return get_config(arch).reduced(vocab_size=64, **over)


@pytest.fixture(scope="module")
def dense():
    cfg = _cfg()
    return cfg, registry.init_params(cfg, seed=0)


def legacy_generate(params, prompt, n_new, cfg, xcfg, seed=0, T=0.0,
                    extras=None):
    """The seed implementation: one jitted decode dispatch per prompt token
    and per new token, host-side key splits — the parity oracle."""
    dec = jax.jit(lambda p, b, c, i: tfm.decode_step(p, b, c, i, cfg, xcfg))
    B, T0 = prompt.shape
    cache = tfm.init_decode_cache(cfg, B, T0 + n_new)
    if cfg.family in ("audio", "vlm"):
        cache = tfm.prefill_memory(params, {"tokens": prompt,
                                            **(extras or {})}, cfg, xcfg,
                                   cache)
    key = jax.random.key(seed)
    tok = prompt[:, :1]
    out = []
    for t in range(T0 + n_new - 1):
        logits, cache = dec(params, {"tokens": tok}, cache, t)
        if t + 1 < T0:
            tok = prompt[:, t + 1:t + 2]
        else:
            key, sub = jax.random.split(key)
            tok = gen.sample_token(logits, sub, T)[:, 0:1]
            out.append(tok)
        if len(out) >= n_new:
            break
    return jnp.concatenate(out, axis=1)


def _prompt(B=2, T0=5, seed=0):
    return jnp.asarray(np.random.RandomState(seed).randint(0, 64, (B, T0)))


# --- parity: compiled engine == legacy loop --------------------------------

@pytest.mark.parametrize("prefill_mode", ["single_pass", "scan"])
def test_generate_parity_local(dense, prefill_mode):
    cfg, params = dense
    xcfg = ExecutionPlan.local().to_exchange_config()
    prompt = _prompt()
    ref = legacy_generate(params, prompt, 6, cfg, xcfg)
    fn = gen.build_generate_fn(cfg, xcfg, n_new=6, prefill_mode=prefill_mode)
    got = fn(params, prompt, {}, jax.random.key(0))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


def test_generate_parity_prism_sim(dense):
    cfg, params = dense
    xcfg = ExecutionPlan.prism_sim(L=4).to_exchange_config()
    prompt = _prompt(B=1, T0=4)
    ref = legacy_generate(params, prompt, 5, cfg, xcfg)
    fn = gen.build_generate_fn(cfg, xcfg, n_new=5)
    assert fn.prefill_mode == "scan"    # compressed prefill is opt-in
    got = fn(params, prompt, {}, jax.random.key(0))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


def test_generate_parity_temperature(dense):
    """Sampled decode threads the PRNG key exactly like the legacy loop."""
    cfg, params = dense
    xcfg = ExecutionPlan.local().to_exchange_config()
    prompt = _prompt()
    ref = legacy_generate(params, prompt, 6, cfg, xcfg, seed=3, T=1.0)
    fn = gen.build_generate_fn(cfg, xcfg, n_new=6, temperature=1.0)
    got = fn(params, prompt, {}, jax.random.key(3))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


@pytest.mark.parametrize("arch", ["gemma2-27b", "deepseek-v2-236b",
                                  "hymba-1.5b", "xlstm-350m"])
def test_generate_parity_families(arch):
    """Windowed local/global dense, MLA MoE, hybrid and recurrent families
    all route through the engine (single-pass or scanned fallback)."""
    cfg = _cfg(arch)
    params = registry.init_params(cfg, seed=0)
    xcfg = ExecutionPlan.local().to_exchange_config()
    prompt = _prompt(B=1, T0=4, seed=len(arch))
    ref = legacy_generate(params, prompt, 4, cfg, xcfg)
    fn = gen.build_generate_fn(cfg, xcfg, n_new=4)
    # dropless MoE routes a prompt in one pass as it does token by token
    want = "single_pass" if tfm.supports_prefill(cfg) else "scan"
    assert fn.prefill_mode == want
    got = fn(params, prompt, {}, jax.random.key(0))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


# --- single-pass prefill vs the full forward -------------------------------

@pytest.mark.parametrize("plan", [ExecutionPlan.local(),
                                  ExecutionPlan.prism_sim(L=4)])
def test_prefill_matches_forward_last_logits(dense, plan):
    """prefill() is forward_lm run once + bulk cache write: its logits must
    equal the full forward's last position under the SAME exchange (for
    prism_sim that is the compressed PRISM math, by design)."""
    cfg, params = dense
    xcfg = plan.to_exchange_config()
    T0 = 8                              # divisible into shards*L segments
    prompt = _prompt(B=1, T0=T0, seed=2)
    cache = tfm.init_decode_cache(cfg, 1, T0 + 2)
    logits, cache = tfm.prefill(params, {"tokens": prompt}, cache, cfg, xcfg)
    full, _ = tfm.forward_lm(params, {"tokens": prompt}, cfg, xcfg)
    np.testing.assert_allclose(np.asarray(logits[:, 0]),
                               np.asarray(full[:, -1]), atol=1e-5, rtol=1e-5)


def test_moe_single_pass_prefill_matches_forward():
    """MoE single-pass prefill reproduces the full forward (dropless
    routing: the same math at any sequence length)."""
    cfg = _cfg("deepseek-v2-236b")
    params = registry.init_params(cfg, seed=0)
    xcfg = ExecutionPlan.local().to_exchange_config()
    prompt = _prompt(B=1, T0=6, seed=7)
    cache = tfm.init_decode_cache(cfg, 1, 8)
    logits, _ = tfm.prefill(params, {"tokens": prompt}, cache, cfg, xcfg)
    full, _ = tfm.forward_lm(params, {"tokens": prompt}, cfg, xcfg)
    np.testing.assert_allclose(np.asarray(logits[:, 0]),
                               np.asarray(full[:, -1]), atol=1e-5, rtol=1e-5)


def test_prefill_cache_matches_teacher_forced(dense):
    """Bulk-written prompt K/V == the cache T0 sequential decode steps
    build (decode continues identically from either)."""
    cfg, params = dense
    xcfg = ExecutionPlan.local().to_exchange_config()
    T0, S = 5, 8
    prompt = _prompt(B=1, T0=T0, seed=4)
    c_bulk = tfm.init_decode_cache(cfg, 1, S)
    _, c_bulk = tfm.prefill(params, {"tokens": prompt}, c_bulk, cfg, xcfg)
    c_seq = tfm.init_decode_cache(cfg, 1, S)
    for t in range(T0):
        _, c_seq = tfm.decode_step(params, {"tokens": prompt[:, t:t + 1]},
                                   c_seq, t, cfg, xcfg)
    for a, b in zip(jax.tree_util.tree_leaves(c_bulk),
                    jax.tree_util.tree_leaves(c_seq)):
        np.testing.assert_allclose(
            np.asarray(a, np.float32)[:, :, :T0],
            np.asarray(b, np.float32)[:, :, :T0], atol=2e-2)


def test_prefill_rejects_recurrent_families():
    cfg = _cfg("xlstm-350m")
    assert not tfm.supports_prefill(cfg)
    with pytest.raises(ValueError, match="single-pass"):
        tfm.prefill({}, {"tokens": jnp.ones((1, 4), jnp.int32)}, {}, cfg,
                    ExecutionPlan.local().to_exchange_config())
    with pytest.raises(ValueError, match="single-pass"):
        gen.resolve_prefill_mode(cfg,
                                 ExecutionPlan.local().to_exchange_config(),
                                 "single_pass")


# --- O(1) dispatch regression ----------------------------------------------

def test_generation_dispatch_count_constant(dense):
    """The whole generation must execute a CONSTANT number of jitted
    callables (here: exactly one) regardless of prompt length and n_new —
    the seed implementation issued T0 + n_new - 1 of them."""
    cfg, params = dense
    sess = InferenceSession(cfg, params, [ExecutionPlan.local()])
    counts = []
    for T0, n_new in ((3, 4), (9, 4), (3, 24), (9, 24)):
        before = gen.dispatch_count()
        out = sess.generate(_prompt(B=1, T0=T0), n_new=n_new)
        counts.append(gen.dispatch_count() - before)
        assert out.shape == (1, n_new)
    assert counts == [1, 1, 1, 1], counts


def test_generation_executables_cached(dense):
    """Repeat shapes reuse the compiled executable; new shapes add one."""
    cfg, params = dense
    sess = InferenceSession(cfg, params, [ExecutionPlan.local()])
    before = gen.build_count()
    sess.generate(_prompt(), n_new=4)
    sess.generate(_prompt(seed=9), n_new=4)      # same shape, new data
    assert gen.build_count() - before == 1
    sess.generate(_prompt(), n_new=5)            # new shape
    assert gen.build_count() - before == 2


def test_generate_n_new_zero(dense):
    cfg, params = dense
    sess = InferenceSession(cfg, params, [ExecutionPlan.local()])
    assert sess.generate(_prompt(), n_new=0).shape == (2, 0)


def test_codec_default_generation_token_exact(dense):
    """The codec refactor must not perturb generation: a prism_sim plan
    (implicit segment_means codec) and the same plan spelled with the
    codec explicit share one identity and produce identical tokens."""
    cfg, params = dense
    implicit = ExecutionPlan.prism_sim(L=2, cr=4.0)
    explicit = ExecutionPlan("prism_sim", 4.0, 2, "seq", 2,
                             codec="segment_means")
    assert explicit == implicit and explicit.key == implicit.key
    sess = InferenceSession(cfg, params, [implicit])
    prompt = _prompt()
    out = sess.generate(prompt, n_new=4, plan=implicit)
    ref = legacy_generate(params, prompt, 4, cfg,
                          implicit.to_exchange_config())
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))
