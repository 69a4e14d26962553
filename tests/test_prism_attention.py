"""PRISM scaling-aware attention: exactness, masking, paper-semantics."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_fallback import given, settings, st

from repro.core.partition import (partition_sequence,
                                  simulate_prism_attention,
                                  simulate_voltage_attention,
                                  unpartition_sequence)
from repro.core.prism_attention import (chunked_reference_attention,
                                        prism_attention, reference_attention)

RNG = np.random.RandomState(0)


def _qkv(B=2, N=32, H=4, Hk=2, dh=16, dtype=jnp.float32):
    q = jnp.asarray(RNG.randn(B, N, H, dh), dtype)
    k = jnp.asarray(RNG.randn(B, N, Hk, dh), dtype)
    v = jnp.asarray(RNG.randn(B, N, Hk, dh), dtype)
    return q, k, v


def test_voltage_equals_full_attention():
    """Voltage's AllGather reconstructs full K/V — math must be identical."""
    q, k, v = _qkv()
    for causal in (False, True):
        out = simulate_voltage_attention(q, k, v, P=4, causal=causal)
        ref = reference_attention(q, k, v, causal=causal)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5)


def test_prism_seg1_equals_full_bidirectional():
    """Segment size 1 → means are the tokens; scaling bias log(1)=0 →
    PRISM attention must equal full attention exactly (paper's limit)."""
    q, k, v = _qkv(N=32)
    out = simulate_prism_attention(q, k, v, P=4, L=8, causal=False)
    ref = reference_attention(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_prism_sim_key_mask_excludes_padding():
    """With a key mask the simulation never reads padded keys (the shard_map
    path's semantics): changing the pads moves no real output, a full mask
    equals no mask, and segment size 1 is still exact full attention."""
    q, k, v = _qkv(N=32)
    mask = jnp.broadcast_to(jnp.arange(32) < 27, (2, 32))
    out = simulate_prism_attention(q, k, v, P=4, L=8, kv_mask=mask)
    junk = jnp.where(mask[..., None, None], k, 1e3)
    alt = simulate_prism_attention(q, junk, jnp.where(
        mask[..., None, None], v, -1e3), P=4, L=8, kv_mask=mask)
    np.testing.assert_allclose(np.asarray(out[:, :27]),
                               np.asarray(alt[:, :27]), atol=2e-5)
    ref = reference_attention(q, k, v, kv_mask=mask)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)
    full = simulate_prism_attention(q, k, v, P=4, L=2,
                                    kv_mask=jnp.ones((2, 32), bool))
    np.testing.assert_allclose(
        np.asarray(full),
        np.asarray(simulate_prism_attention(q, k, v, P=4, L=2)), atol=2e-5)


def test_prism_causal_first_partition_is_local_only():
    """Partition 0 under causality sees no remote means — equals local-only
    causal attention on its slice."""
    q, k, v = _qkv(N=32)
    P = 4
    out = simulate_prism_attention(q, k, v, P=P, L=2, causal=True)
    qp = partition_sequence(q, P)
    kp = partition_sequence(k, P)
    vp = partition_sequence(v, P)
    local0 = reference_attention(qp[0], kp[0], vp[0], causal=True)
    np.testing.assert_allclose(np.asarray(out[:, :8]), np.asarray(local0),
                               atol=2e-5)


def test_scaling_aware_bias_equals_duplicate_keys():
    """THE paper property: one mean key with +log(s) bias carries the mass
    of s identical keys — verify exactly with duplicated keys."""
    B, Nq, H, dh, s = 1, 4, 2, 8, 5
    q = jnp.asarray(RNG.randn(B, Nq, H, dh), jnp.float32)
    k1 = jnp.asarray(RNG.randn(B, 1, H, dh), jnp.float32)
    v1 = jnp.asarray(RNG.randn(B, 1, H, dh), jnp.float32)
    k_loc = jnp.asarray(RNG.randn(B, Nq, H, dh), jnp.float32)
    v_loc = jnp.asarray(RNG.randn(B, Nq, H, dh), jnp.float32)
    # (a) local keys + s duplicates of (k1, v1)
    k_dup = jnp.concatenate([k_loc] + [k1] * s, axis=1)
    v_dup = jnp.concatenate([v_loc] + [v1] * s, axis=1)
    ref = reference_attention(q, k_dup, v_dup)
    # (b) local keys + ONE mean key with seg_size=s bias (means of partition
    # 1; query partition 0, bidirectional → remote visible)
    km = jnp.stack([k1 * jnp.nan, k1], axis=1)  # own partition masked anyway
    vm = jnp.stack([v1 * jnp.nan, v1], axis=1)
    km = jnp.where(jnp.isnan(km), 0.0, km)
    vm = jnp.where(jnp.isnan(vm), 0.0, vm)
    out = prism_attention(q, k_loc, v_loc, km, vm, part_idx=0, seg_size=s,
                          causal=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_mean_counts_mask_empty_segments():
    q, k, v = _qkv(N=8, H=2, Hk=2)
    km = jnp.asarray(RNG.randn(2, 2, 2, 2, 16), jnp.float32)
    vm = jnp.asarray(RNG.randn(2, 2, 2, 2, 16), jnp.float32)
    counts = jnp.asarray([[[4.0, 0.0], [4.0, 4.0]]] * 2)   # one empty segment
    out = prism_attention(q, k, v, km, vm, part_idx=0, seg_size=4,
                          causal=False, mean_counts=counts)
    assert not bool(jnp.any(jnp.isnan(out)))
    # zeroing the masked mean's value must not change anything
    vm2 = vm.at[:, 0, 1].set(1e3)
    out2 = prism_attention(q, k, v, km, vm2, part_idx=0, seg_size=4,
                           causal=False, mean_counts=counts)
    np.testing.assert_allclose(np.asarray(out), np.asarray(out2), atol=1e-6)


def test_partition_roundtrip():
    x = jnp.asarray(RNG.randn(3, 24, 5), jnp.float32)
    p = partition_sequence(x, 4)
    assert p.shape == (4, 3, 6, 5)
    np.testing.assert_array_equal(np.asarray(unpartition_sequence(p)),
                                  np.asarray(x))


def test_chunked_equals_reference():
    q, k, v = _qkv(B=1, N=64, H=2, Hk=2)
    for causal in (False, True):
        for window in (None, 16):
            ref = reference_attention(q, k, v, causal=causal, window=window)
            out = chunked_reference_attention(q, k, v, chunk=16,
                                              causal=causal, window=window)
            np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                       atol=2e-5)


def test_chunked_gradient_matches():
    q, k, v = _qkv(B=1, N=32, H=2, Hk=2)

    def loss_ref(q):
        return jnp.sum(reference_attention(q, k, v, causal=True) ** 2)

    def loss_chk(q):
        return jnp.sum(chunked_reference_attention(q, k, v, chunk=8,
                                                   causal=True) ** 2)
    g1 = jax.grad(loss_ref)(q)
    g2 = jax.grad(loss_chk)(q)
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g2), atol=1e-3,
                               rtol=1e-3)


@given(st.integers(2, 4), st.integers(1, 4), st.booleans())
@settings(max_examples=20, deadline=None)
def test_prism_rows_sum_to_one(P, L, causal):
    """Softmax over [local ‖ means] is a proper distribution: outputs are
    convex combinations → bounded by the max |v|."""
    rng = np.random.RandomState(P * 10 + L)
    N = P * L * 2
    q = jnp.asarray(rng.randn(1, N, 2, 8), jnp.float32)
    k = jnp.asarray(rng.randn(1, N, 2, 8), jnp.float32)
    v = jnp.asarray(rng.randn(1, N, 2, 8), jnp.float32)
    out = simulate_prism_attention(q, k, v, P=P, L=L, causal=causal)
    assert float(jnp.max(jnp.abs(out))) <= float(jnp.max(jnp.abs(v))) + 1e-4
