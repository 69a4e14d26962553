"""The Pallas kernels compile for a TPU v5e at the widths the chip smoke run
uses, without a chip: the TPU compiler is installed, and it compiles for a
described topology.  Interpret mode (every other kernel test) accepts block
shapes the chip's compiler refuses; these tests do not.  Two more compile
whole programs: the ViT forward under ``prism_sim``, for the kernel in it,
and the prefill, for what only the TPU compiler does to it.

Widths: internlm2-1.8b (H=16, Hk=8, dh=128, caches up to 4096 positions,
16-position pages), DeepSeek-V2-Lite's latent pages (H=16, r=512, rope 64)
and ViT-B/16 (197 tokens padded, d=768, 12 heads).  Two compile paged
decode programs, to see what the compiler made of the stacked page pool.
"""
import dataclasses
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.flash_decode.latent import flash_decode_latent_pallas
from repro.kernels.flash_decode.ops import flash_decode_op
from repro.kernels.flash_decode.paged import flash_decode_paged_op
from repro.kernels.prism_attention.ops import prism_attention_op
from repro.kernels.segment_means.ops import segment_means_op


@pytest.fixture(scope="module")
def one_chip():
    """One chip of a described v5e:2x2 host.  The persistent compilation
    cache stays off meanwhile: entries compiled for a described chip cannot
    be read back without one."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _compile(fn, *shapes):
    """Compile ``fn`` for the described chip; returns the lowered text."""
    lowered = jax.jit(fn).lower(*shapes)
    lowered.compile()               # raises what the chip's compiler raises
    return lowered.as_text()


def _shape(sharding, shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("S", [2048, 4096])
def test_flash_decode_compiles(one_chip, S):
    B, H, Hk, dh = 8, 16, 8, 128
    text = _compile(
        lambda q, k, v, n: flash_decode_op(q, k, v, n, interpret=False),
        _shape(one_chip, (B, 1, H, dh)), _shape(one_chip, (B, S, Hk, dh)),
        _shape(one_chip, (B, S, Hk, dh)), _shape(one_chip, (B,), jnp.int32))
    assert "tpu_custom_call" in text


# B = 8: a decode chunk's rows; B = 1: a suffix scan
@pytest.mark.parametrize("page_size, B", [
    pytest.param(ps, B, id=f"{ps}" + ("" if B == 8 else f"-B{B}"))
    for ps in (16, 32) for B in (8, 1)])
def test_paged_flash_decode_compiles(one_chip, page_size, B):
    H, Hk, dh, pages_per_row = 16, 8, 128, 128
    n_pages = B * pages_per_row + 1
    pool = _shape(one_chip, (2, n_pages, page_size, Hk * dh))
    text = _compile(
        lambda q, k, v, layer, pt, n: flash_decode_paged_op(
            q, k, v, layer, pt, n, interpret=False),
        _shape(one_chip, (B, 1, H, dh)), pool, pool,
        _shape(one_chip, (), jnp.int32),
        _shape(one_chip, (B, pages_per_row), jnp.int32),
        _shape(one_chip, (B,), jnp.int32))
    assert "tpu_custom_call" in text


# DeepSeek-V2-Lite latent pages: c_kv 512, k_pe 64 padded to 128 lanes;
# B = 32: a decode chunk's rows over 192-page tables; B = 1: a suffix scan
@pytest.mark.parametrize("B", [32, 1])
def test_paged_latent_decode_compiles(one_chip, B):
    H, r, dr, dp, ps, MP = 16, 512, 64, 128, 16, 192
    n_pages = 4096 + 1
    text = _compile(
        lambda q, qp, c, pe, layer, pt, n: flash_decode_latent_pallas(
            q, qp, c, pe, layer, pt, n, scale=0.1, interpret=False),
        _shape(one_chip, (B, H, r)), _shape(one_chip, (B, H, dr)),
        _shape(one_chip, (2, n_pages, ps, r)),
        _shape(one_chip, (2, n_pages, ps, dp)),
        _shape(one_chip, (), jnp.int32),
        _shape(one_chip, (B, MP), jnp.int32), _shape(one_chip, (B,), jnp.int32))
    assert "tpu_custom_call" in text


def _paged_program_text(chip, monkeypatch, cfg, n_pages, program):
    """Compiled text of a paged program over ``cfg``'s stacked pool of
    ``n_pages`` pages of 16 (+ the trash page), its kernels compiled:
    ``chunk``, a decode chunk of 2 steps over 8 rows of 128-page tables
    (32 rows of 192 pages under MLA, DeepSeek-V2-Lite's cell), or
    ``suffix``, a 4-token suffix scan of one row."""
    from repro.api import ExecutionPlan, generation
    from repro.kernels import dispatch as kdsp
    from repro.models import registry
    from repro.models import transformer as tfm
    monkeypatch.setattr(kdsp, "_interpret", lambda: False)
    rows, MP = (32, 192) if cfg.mla is not None else (8, 128)
    xcfg = ExecutionPlan.local().to_exchange_config()
    shaped = lambda t: jax.tree_util.tree_map(
        lambda a: _shape(chip, a.shape, a.dtype), t)
    params = shaped(registry.abstract_params(cfg))
    pool = shaped(jax.eval_shape(
        lambda: tfm.init_page_pool(cfg, n_pages + 1, 16)))
    i32 = lambda *s: _shape(chip, s, jnp.int32)
    key = jax.eval_shape(lambda: jax.random.key(0))
    if program == "chunk":
        fn = generation.build_paged_decode_chunk_fn(cfg, xcfg, n_steps=2)
        keys = jax.eval_shape(lambda: jax.random.split(jax.random.key(0),
                                                       rows))
        args = (params, pool, i32(rows, MP), i32(rows), i32(rows),
                i32(rows), shaped(keys),
                _shape(chip, (rows,), jnp.float32))
    else:
        fn = generation.build_paged_suffix_fn(cfg, xcfg, n_suffix=4)
        args = (params, pool, i32(1, MP), i32(1, 4), i32(1), shaped(key),
                _shape(chip, (), jnp.float32))
    with kdsp.force_backend("pallas"):
        return fn.jitted.lower(*args).compile().as_text()


@pytest.mark.parametrize("program", ["chunk", "suffix"])
def test_paged_decode_reads_the_stacked_pool_in_place(one_chip, monkeypatch,
                                                      program):
    """internlm2-1.8b's widths, two layers and a pool of 1,536 pages: the
    paged programs write and read every layer's K/V pages where they lie
    in the stacked pool.  No op makes one layer's pages, in the pool's
    layout [P, 16, 1024], the model's [P, 16, 8, 128] or either with a
    leading 1: no per-layer slice, relayout or write-back."""
    from repro.configs import get_config
    cfg = dataclasses.replace(get_config("internlm2-1.8b"), n_layers=2)
    text = _paged_program_text(one_chip, monkeypatch, cfg, 1536, program)
    assert "tpu_custom_call" in text
    assert "bf16[2,1537,16,1024]" in text
    one_layer = re.compile(r"bf16\[(1,)?1537,16,(1024|8,128)\]")
    assert not one_layer.search(text), one_layer.search(text).group(0)


def test_latent_pages_keep_the_held_experts_placement(one_chip, monkeypatch):
    """DeepSeek-V2-Lite's decode chunk at its cell's sizes (27 layers, 16
    of 64 experts held, 4,096 latent pages): each MoE layer stages its
    held experts' three weight stacks for the grouped matmul, and the
    compiler gives all three its alternate memory space (``S(1)``).
    Latent pages therefore keep their per-layer slice: read in place,
    the w_gate and w_up stacks lose that placement (PERF.md)."""
    from repro.configs import get_config
    cfg = get_config("deepseek-v2-lite")
    cfg = dataclasses.replace(cfg, moe=dataclasses.replace(cfg.moe,
                                                           held=(0, 16)))
    text = _paged_program_text(one_chip, monkeypatch, cfg, 4096, "chunk")
    staged = re.findall(r"= (bf16\[16,(?:2048,1408|1408,2048)\]\{[^}]*\}) "
                        r"fusion\(", text)
    assert len(staged) == 3 and all("S(1)" in s for s in staged), staged
    assert "bf16[4097,16,512]" in text          # one layer's latent pages


@pytest.mark.parametrize("shape, L", [
    ((8, 200, 12, 64), 4),      # ViT-B/16, 197 tokens padded; seg = 50
    ((8, 60, 12, 64), 20),      # one of four ViT partitions; seg = 3
    ((8, 512, 8, 128), 8),      # internlm2-1.8b K/V
])
def test_segment_means_compiles(one_chip, shape, L):
    text = _compile(lambda x: segment_means_op(x, L, interpret=False),
                    _shape(one_chip, shape))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("Nq, H, Hk, dh, P, L, causal", [
    (512, 16, 8, 128, 4, 8, True),      # internlm2-1.8b partition
    (60, 12, 12, 64, 4, 20, False),     # ViT-B/16 partition (240 / 4)
    (200, 12, 12, 64, 1, 20, False),    # ViT-B/16, 197 tokens padded
])
def test_prism_attention_compiles(one_chip, Nq, H, Hk, dh, P, L, causal):
    B = 8
    kv = _shape(one_chip, (B, Nq, Hk, dh))
    means = _shape(one_chip, (B, P, L, Hk, dh))
    text = _compile(
        lambda q, k, v, km, vm: prism_attention_op(
            q, k, v, km, vm, P - 1, Nq // L, causal=causal,
            interpret=False),
        _shape(one_chip, (B, Nq, H, dh)), kv, kv, means, means)
    assert "tpu_custom_call" in text


def _longest_resident_partition(dh, M):
    from repro.kernels.prism_attention.ops import fits_vmem
    return max(n for n in range(128, 1 << 16, 128)
               if fits_vmem(n, n, M, dh, 2))


@pytest.mark.parametrize("longer", [False, True])
def test_prism_attention_runs_the_kernel_while_vmem_holds_it(
        one_chip, monkeypatch, longer):
    """The kernel holds a partition's whole K/V in VMEM.  Through the
    dispatch layer, the longest internlm2-1.8b partition it admits
    compiles; one 128-token step longer runs the reference and is counted
    (``prism_attention/vmem``)."""
    from repro.kernels import dispatch as kdsp
    monkeypatch.setattr(kdsp, "_interpret", lambda: False)
    B, H, Hk, dh, P, L = 1, 16, 8, 128, 2, 8
    N = _longest_resident_partition(dh, P * L) + 128 * longer
    kv = _shape(one_chip, (B, N, Hk, dh))
    means = _shape(one_chip, (B, P, L, Hk, dh))
    before = kdsp.fallback_counts().get("prism_attention/vmem", 0)
    with kdsp.force_backend("pallas"):
        text = _compile(
            lambda q, k, v, km, vm: kdsp.prism_attention(
                q, k, v, km, vm, P - 1, N // L, causal=True),
            _shape(one_chip, (B, N, H, dh)), kv, kv, means, means)
    after = kdsp.fallback_counts().get("prism_attention/vmem", 0)
    assert ("tpu_custom_call" in text) != longer
    assert after == before + longer


def test_vit_prism_sim_runs_the_segment_means_kernel(one_chip, monkeypatch):
    """The paper's one-chip pipeline (ViT-B/16 under ``prism_sim``) compiles
    with the segment-means kernel in it; its attention takes padded keys,
    so it runs the reference and says so in the fallback counter."""
    from repro.api import ExecutionPlan
    from repro.configs import get_config
    from repro.kernels import dispatch as kdsp
    from repro.models import registry

    monkeypatch.setattr(kdsp, "_interpret", lambda: False)
    cfg = get_config("vit-base-16")
    params = jax.tree_util.tree_map(
        lambda a: _shape(one_chip, a.shape, a.dtype),
        registry.abstract_params(cfg))
    xcfg = ExecutionPlan.prism_sim(L=20, cr=4.95).to_exchange_config()
    fwd = registry.forward_fn(cfg)
    before = kdsp.fallback_counts().get("prism_attention/kv_mask", 0)
    with kdsp.force_backend("pallas"):
        text = _compile(lambda p, b: fwd(p, b, xcfg)[0], params,
                        {"images": _shape(one_chip, (8, 224, 224, 3),
                                          jnp.float32)})
    assert "tpu_custom_call" in text
    assert (kdsp.fallback_counts()["prism_attention/kv_mask"]
            == before + xcfg.seq_shards)


def test_prefill_program_keeps_the_cache_zeros(one_chip):
    """The decode cache a prefill program makes for itself must reach memory
    as zeros.  Left to itself the TPU compiler sinks the zero fill into the
    layer scan and allocates the cache uninitialized (an ``AllocateBuffer``),
    so the positions past the prompt hold garbage, NaN included."""
    import dataclasses

    from repro.api import ExecutionPlan, generation
    from repro.configs import get_config
    from repro.models import registry

    cfg = dataclasses.replace(get_config("internlm2-1.8b"), n_layers=2)
    params = jax.tree_util.tree_map(
        lambda a: _shape(one_chip, a.shape, a.dtype),
        registry.abstract_params(cfg))
    key = jax.eval_shape(lambda: jax.random.key(0))
    fn = generation.build_prefill_fn(
        cfg, ExecutionPlan.local().to_exchange_config(), total_len=544)
    text = fn.jitted.lower(
        params, _shape(one_chip, (1, 256), jnp.int32), {},
        _shape(one_chip, key.shape, key.dtype),
        _shape(one_chip, (), jnp.float32)).compile().as_text()
    cache = f"bf16[2,1,544,{cfg.n_kv_heads},{cfg.hd}]"
    assert cache in text
    assert not [line for line in text.splitlines()
                if "AllocateBuffer" in line and cache in line]
