"""DeepSeek-V2-Lite's mechanisms: YaRN rope as published, the query
projected without a low-rank step, the dropless held-experts layer (the
shares of disjoint held ranges add up to the uncut layer; single-pass
prefill equals the scanned decode), the paged latent decode kernel
against its reference, and MoE models served through the paged pool."""
import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import ExecutionPlan, InferenceSession
from repro.api import generation as gen
from repro.configs import MoECfg, YaRNCfg, get_config
from repro.kernels import dispatch as kdsp
from repro.kernels.flash_decode import latent
from repro.models import mla as mla_mod
from repro.models import moe as moe_mod
from repro.models import registry
from repro.models import transformer as tfm
from repro.models.layers import rope_tables

XLOC = ExecutionPlan.local().to_exchange_config()
V2_LITE_YARN = YaRNCfg(factor=40.0, original_max_position_embeddings=4096,
                       beta_fast=32.0, beta_slow=1.0, mscale=0.707,
                       mscale_all_dim=0.707)


# --- YaRN ---------------------------------------------------------------

def _deepseek_yarn(dim, base, factor, orig, beta_fast, beta_slow, mscale,
                   mscale_all_dim, positions):
    """DeepseekV2YarnRotaryEmbedding and the attention's softmax scale,
    written out from the published modeling code (one half of the
    cat(freqs, freqs) table)."""
    def find_dim(rot):
        return (dim * math.log(orig / (rot * 2 * math.pi))) / \
            (2 * math.log(base))
    low = max(math.floor(find_dim(beta_fast)), 0)
    high = min(math.ceil(find_dim(beta_slow)), dim - 1)

    def get_mscale(scale, m):
        return 1.0 if scale <= 1 else 0.1 * m * math.log(scale) + 1.0
    freq_extra = 1.0 / (base ** (np.arange(0, dim, 2, dtype=np.float32)
                                 / dim))
    freq_inter = 1.0 / (factor * base ** (np.arange(0, dim, 2,
                                                    dtype=np.float32) / dim))
    lo, hi = low, high if high != low else high + 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float32) - lo) / (hi - lo),
                   0, 1)
    inv_freq_mask = 1.0 - ramp
    inv_freq = freq_inter * (1 - inv_freq_mask) + freq_extra * inv_freq_mask
    freqs = np.outer(np.asarray(positions, np.float32), inv_freq)
    m = get_mscale(factor, mscale) / get_mscale(factor, mscale_all_dim)
    scale = (128 + dim) ** -0.5 * get_mscale(factor, mscale_all_dim) ** 2
    return np.cos(freqs) * m, np.sin(freqs) * m, scale


@pytest.mark.parametrize("y", [
    V2_LITE_YARN,
    YaRNCfg(factor=8.0, original_max_position_embeddings=2048,
            beta_fast=16.0, beta_slow=2.0, mscale=1.0, mscale_all_dim=0.5),
], ids=["v2-lite", "other"])
def test_yarn_tables_match_deepseek(y):
    positions = np.asarray([0, 1, 7, 4095, 4096, 40000, 163839])
    cos, sin, scale = _deepseek_yarn(64, 10000.0, y.factor,
                                     y.original_max_position_embeddings,
                                     y.beta_fast, y.beta_slow, y.mscale,
                                     y.mscale_all_dim, positions)
    got_c, got_s = rope_tables(jnp.asarray(positions), 64, 10000.0, y)
    # float32 angles of up to 1.6e5 radians: ~1e-2 absolute, as torch's
    np.testing.assert_allclose(np.asarray(got_c), cos, atol=2e-2)
    np.testing.assert_allclose(np.asarray(got_s), sin, atol=2e-2)
    np.testing.assert_allclose(np.asarray(got_c)[:3], cos[:3], atol=1e-5)
    cfg = get_config("deepseek-v2-lite").mla
    assert mla_mod.softmax_scale(cfg, y) == pytest.approx(scale, rel=1e-6)


def test_v2_lite_softmax_scale_is_published():
    """192^-0.5 · (0.1 · 0.707 · ln 40 + 1)^2, and cos/sin unscaled."""
    cfg = get_config("deepseek-v2-lite")
    want = 192 ** -0.5 * (0.1 * 0.707 * math.log(40) + 1) ** 2
    assert mla_mod.softmax_scale(cfg.mla, cfg.rope_scaling) == \
        pytest.approx(want, rel=1e-12)
    assert want == pytest.approx(192 ** -0.5 * 1.590, rel=1e-3)
    c, s = rope_tables(jnp.asarray([0]), 64, 10000.0, cfg.rope_scaling)
    np.testing.assert_allclose(np.asarray(c), 1.0)


def test_yarn_changes_every_position():
    """YaRN is not an extension past the original context: the tables
    differ from plain rope below 4096 positions too."""
    pos = jnp.asarray([5, 100, 1000])
    plain = rope_tables(pos, 64, 10000.0)
    yarn = rope_tables(pos, 64, 10000.0, V2_LITE_YARN)
    assert float(jnp.max(jnp.abs(plain[0] - yarn[0]))) > 0.1


# --- MLA without a query low rank --------------------------------------

@pytest.mark.parametrize("arch", ["deepseek-v2-lite", "deepseek-v2-236b"])
def test_query_projection_path(arch):
    """q_lora_rank None projects queries directly (w_q, no w_dq / q_norm /
    w_uq); teacher-forced decode reproduces the forward either way."""
    cfg = get_config(arch).reduced(vocab_size=64)
    params = registry.init_params(cfg, seed=1)
    attn = params["layers"]["attn"]
    direct = get_config(arch).mla.q_lora_rank is None
    assert ("w_q" in attn) == direct
    assert ("w_dq" in attn) == (not direct)
    toks = jnp.asarray(np.random.RandomState(1).randint(0, 64, (1, 8)))
    full, _ = tfm.forward_lm(params, {"tokens": toks}, cfg, XLOC)
    cache = tfm.init_decode_cache(cfg, 1, 8)
    outs = []
    for t in range(8):
        lg, cache = tfm.decode_step(params, {"tokens": toks[:, t:t + 1]},
                                    cache, t, cfg, XLOC)
        outs.append(lg[:, 0])
    np.testing.assert_allclose(np.asarray(jnp.stack(outs, 1)),
                               np.asarray(full), atol=2e-2, rtol=2e-2)


# --- the held-experts layer -------------------------------------------

def _moe_params(cfg, seed=0):
    return moe_mod.init_moe(jax.random.key(seed), 32, cfg, jnp.float32)


def _share(params, lo, hi):
    """The weights one device holds: its experts' rows, the router whole,
    no shared experts (counted once, below)."""
    return {"router": params["router"],
            **{n: params[n][lo:hi] for n in ("w_gate", "w_up", "w_down")}}


@pytest.mark.parametrize("norm", [True, False])
def test_held_shares_add_up_to_the_uncut_layer(norm):
    """Four devices holding disjoint quarters of 8 experts: their routed
    parts, plus the shared expert once, equal the layer that holds them
    all; each device computes exactly the pairs routed to its range."""
    full = MoECfg(n_experts=8, top_k=3, n_shared=1, d_ff_expert=16,
                  norm_topk_prob=norm, routed_scaling_factor=1.5)
    params = _moe_params(full)
    x = jax.random.normal(jax.random.key(3), (2, 7, 32), jnp.float32)
    with jax.default_matmul_precision("highest"):
        y_full, aux, tokens = moe_mod.apply_moe(params, x, full)
        total = moe_mod.apply_mlp(params["shared"], x)
        _, top_i, _ = moe_mod.route(params["router"], x.reshape(-1, 32),
                                    full)
        for lo in range(0, 8, 2):
            cfg = dataclasses.replace(full, held=(lo, lo + 2))
            y, _, t = moe_mod.apply_moe(_share(params, lo, lo + 2), x, cfg)
            total = total + y
            want = [int(jnp.sum(top_i == e)) for e in (lo, lo + 1)]
            assert t.tolist() == want
    np.testing.assert_allclose(np.asarray(total), np.asarray(y_full),
                               atol=1e-5, rtol=1e-5)
    assert int(tokens.sum()) == 2 * 7 * 3
    assert jnp.isfinite(aux)


def test_routing_is_softmax_over_all_then_top_k():
    """No renormalisation with norm_topk_prob False: the weights are the
    softmax probabilities over all the router's experts, times the
    scaling factor."""
    cfg = MoECfg(n_experts=8, top_k=2, d_ff_expert=4, norm_topk_prob=False,
                 routed_scaling_factor=2.0)
    router = jax.random.normal(jax.random.key(0), (4, 8))
    x = jax.random.normal(jax.random.key(1), (5, 4))
    w, idx, probs = moe_mod.route(router, x, cfg)
    want = jax.nn.softmax(x @ router, axis=-1)
    np.testing.assert_allclose(np.asarray(probs), np.asarray(want),
                               rtol=1e-6)
    np.testing.assert_allclose(
        np.asarray(w), 2.0 * np.take_along_axis(np.asarray(want),
                                                np.asarray(idx), -1),
        rtol=1e-6)


@pytest.mark.parametrize("arch", ["deepseek-v2-lite", "deepseek-moe-16b"])
def test_single_pass_prefill_equals_the_scanned_decode(arch):
    """Dropless routing: the prompt in one pass gives the logits and the
    cache that the same prompt decoded token by token gives."""
    cfg = get_config(arch).reduced(vocab_size=64)
    params = registry.init_params(cfg, seed=2)
    prompt = jnp.asarray(np.random.RandomState(5).randint(0, 64, (1, 12)))
    assert gen.resolve_prefill_mode(cfg, XLOC) == "single_pass"
    c0 = tfm.init_decode_cache(cfg, 1, 16)
    lg_pass, c_pass = tfm.prefill(params, {"tokens": prompt}, c0, cfg, XLOC)
    lg_scan, c_scan = gen.prefill_by_decode(params, prompt, c0, cfg, XLOC)
    np.testing.assert_allclose(np.asarray(lg_pass), np.asarray(lg_scan),
                               atol=2e-2, rtol=2e-2)
    assert int(jnp.argmax(lg_pass)) == int(jnp.argmax(lg_scan))
    # bf16 caches: one pass attends in the expanded form, the steps in the
    # absorbed one (MLA), so a cached value may differ by a few bf16 ulps
    for a, b in zip(jax.tree_util.tree_leaves(c_pass),
                    jax.tree_util.tree_leaves(c_scan)):
        np.testing.assert_allclose(np.asarray(a, np.float32)[:, :, :12],
                                   np.asarray(b, np.float32)[:, :, :12],
                                   atol=2e-2, rtol=2e-2)


# --- the paged latent decode kernel ------------------------------------

@pytest.mark.parametrize("B, ps, block, lengths", [
    (3, 8, 16, [1, 17, 48]),          # one key, a block and one, whole table
    (4, 16, 32, [32, 33, 5, 96]),     # block edges, two pages a block
    (1, 16, 512, [70]),               # a suffix scan's single row
], ids=["ragged", "edges", "one-row"])
def test_latent_kernel_matches_reference(monkeypatch, B, ps, block, lengths):
    """Interpret mode, scattered page tables, ragged lengths, and NaN in
    every position past a row's length: the kernel's normalized output
    equals the gather reference's."""
    from repro.kernels.flash_decode import paged
    monkeypatch.setattr(paged, "MAX_BLOCK_TOKENS", block)
    rng = np.random.RandomState(B + ps)
    H, r, dr, dp = 4, 128, 16, 128
    MP = -(-max(lengths) // ps)
    P = B * MP + 3
    c = rng.randn(P, ps, r)
    pe = np.zeros((P, ps, dp))
    pe[..., :dr] = rng.randn(P, ps, dr)
    pt = rng.permutation(P)[:B * MP].reshape(B, MP)
    for b, n in enumerate(lengths):     # poison everything past the length
        for p in range(MP):
            lo = max(n - p * ps, 0)
            c[pt[b, p], lo:] = np.nan
            pe[pt[b, p], lo:] = np.nan
    args = (jnp.asarray(rng.randn(B, H, r), jnp.bfloat16),
            jnp.asarray(rng.randn(B, H, dr), jnp.bfloat16),
            jnp.asarray(c[None], jnp.bfloat16),
            jnp.asarray(pe[None], jnp.bfloat16), 0,
            jnp.asarray(pt, jnp.int32), jnp.asarray(lengths, jnp.int32))
    o, m, l = latent.flash_decode_latent_pallas(*args, scale=0.07,
                                                interpret=True)
    got = np.asarray(o / l[..., None])
    want = np.asarray(latent.flash_decode_latent_ref(*args, scale=0.07))
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=1e-5)


def test_latent_dispatch_routes_the_kernel_or_the_reference():
    """Both backends of ``decode_attention_latent_paged`` agree; compiled,
    a latent rank that is not a lane multiple falls back, counted."""
    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randn(2, 1, 4, 128), jnp.bfloat16)
    qp = jnp.asarray(rng.randn(2, 1, 4, 8), jnp.bfloat16)
    c = jnp.asarray(rng.randn(2, 6, 8, 128), jnp.bfloat16)
    pe = jnp.asarray(np.pad(rng.randn(2, 6, 8, 8),
                            ((0, 0), (0, 0), (0, 0), (0, 120))),
                     jnp.bfloat16)
    pt = jnp.asarray([[0, 2, 4], [1, 3, 5]], jnp.int32)
    n = jnp.asarray([20, 3], jnp.int32)
    with kdsp.force_backend("reference"):
        ref = kdsp.decode_attention_latent_paged(q, qp, c, pe, 1, pt, n,
                                                 scale=0.1)
    with kdsp.force_backend("pallas"):
        got = kdsp.decode_attention_latent_paged(q, qp, c, pe, 1, pt, n,
                                                 scale=0.1)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(ref, np.float32), atol=1e-2)
    assert kdsp.paged_pages_per_block({"kv": {"c_kv": c,
                                              "k_pe": pe}}, 3) == 3


# --- MoE models through the paged pool ----------------------------------

def _session(cfg, seed=0):
    params = registry.init_params(cfg, seed=seed)
    sess = InferenceSession(cfg, params, [ExecutionPlan.local()],
                            allow_modes=("local",))
    sess.profile(backend="simulated")
    return sess


@pytest.mark.parametrize("arch, backend", [
    ("deepseek-v2-lite", "reference"), ("deepseek-v2-lite", "pallas"),
    ("deepseek-moe-16b", "reference")])
def test_paged_moe_serving_matches_generate(arch, backend):
    """Misses, a full prefix hit and partial hits (COW tails) on the paged
    pool (latent pages under MLA, K/V pages otherwise), decoded in chunks,
    give the tokens of a per-request ``generate``."""
    from repro.serving import ServingRuntime
    cfg = get_config(arch).reduced(vocab_size=64)
    assert tfm.supports_page_pool(cfg)
    sess = _session(cfg)
    base = np.random.RandomState(4).randint(0, 64, 13).tolist()
    prompts = [base, base, base + [5, 6, 7], base[:9] + [1, 2], base[:5]]
    with kdsp.force_backend(backend):
        rt = ServingRuntime(sess, max_len=64, page_size=8, n_pages=40,
                            n_rows=3, chunk=4)
        reqs = [rt.submit(p, 7 + i, seed=i) for i, p in enumerate(prompts)]
        rt.run()
        served = {c.request_id: list(c.tokens) for c in rt.completions}
        for r in reqs:
            want = sess.generate(jnp.asarray([r.prompt]), n_new=r.n_new,
                                 seed=r.seed)
            assert served[r.id] == np.asarray(want)[0].tolist(), r.id
    stats = next(iter(rt.pools.values())).page_stats()
    assert stats["full_hits"] >= 1 and stats["partial_hits"] >= 1
    assert stats["cow_splits"] >= 1


def test_latent_pool_layout_and_cold_pages_refused():
    cfg = get_config("deepseek-v2-lite").reduced(vocab_size=64)
    pool = tfm.init_page_pool(cfg, 5, 8)
    assert set(pool) == {"first", "kv"}
    assert pool["kv"]["c_kv"].shape == (cfg.n_layers - 1, 5, 8, 32)
    assert pool["kv"]["k_pe"].shape == (cfg.n_layers - 1, 5, 8,
                                        mla_mod.PE_LANES)
    from repro.serving import ServingRuntime
    rt = ServingRuntime(_session(cfg), max_len=32, page_size=8, n_pages=8,
                        n_rows=2, cold_horizon=2)
    rt.submit([1, 2, 3], 2)
    with pytest.raises(NotImplementedError, match="latent"):
        rt.run()
