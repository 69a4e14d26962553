"""Smoke run of the main path on a TPU, in one process.

    python chip_smoke.py              # one chip: phases 1-4
    python chip_smoke.py --chips 4    # four chips: ViT attention split across
                                      # a ("seq",) mesh, against one chip

One chip:
  1. the device is a TPU and the Pallas kernels run compiled;
  2. the paper's pipeline on ViT-B/16 at published widths, batch 8: measured
     profiling sweep, policy decision, local and PRISM-simulated runs;
  3. internlm2-1.8b at published widths served through ``ServingRuntime``,
     once over the dense slot pool and once over the paged pool with the
     prefix cache on; prefill and one decode step are checked against the
     model's full forward pass;
  4. the decode-chunk executables of phase 3 contain the Pallas kernels.

Four chips: ViT-B/16 under ``ExecutionPlan.prism(L=20, seq_shards=4)`` and
``ExecutionPlan.voltage(seq_shards=4)`` across the mesh, compared with the
one-chip ``prism_sim`` and ``local`` results on the same images.

Weights are random, drawn from a fixed seed.  Times printed are host wall
clock around ``block_until_ready``, compilation included: not a benchmark.
Any failed check raises, so the exit code is non-zero; the last line of a
passing run is ``{"ok": true, "device": {...}}``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.utils.compile_cache import configure_compile_cache  # noqa: E402

SEED = 0
VIT_L, VIT_CR, VIT_BATCH = 20, 4.95, 8
# phase 3: prompts A (BASE tokens), A+B (BASE+EXTRA), A again, and an
# unrelated LONG one; N_NEW greedy tokens each, decoded CHUNK steps per call
BASE, EXTRA, LONG, N_NEW, CHUNK, PAGE_SIZE = 256, 128, 512, 32, 8, 16

# bf16 keeps 8 significant bits (relative step 2^-8 ≈ 0.4%).  Two correct
# paths through a 24-layer bf16 model round the activations at different
# points (XLA attention over the prompt vs the Pallas decode kernel; one
# chip vs four), so their logits differ by a few such steps of the logits'
# scale.  5% of the largest |logit| bounds that with margin.  Phase 3 runs
# two controls through the same measure, a decode step at a cache position
# off by one and one over another prompt's cache, and fails unless both
# come out above this limit.
LOGIT_RTOL = 5e-2


class SmokeFailure(AssertionError):
    pass


def check(ok: bool, msg: str) -> None:
    if not ok:
        raise SmokeFailure(msg)


def wall(label: str, t0: float) -> None:
    print(f"    {label}: {time.perf_counter() - t0:.1f} s "
          "(host wall, not a benchmark)", flush=True)


def rel_err(out, ref) -> float:
    out, ref = np.asarray(out, np.float32), np.asarray(ref, np.float32)
    return float(np.max(np.abs(out - ref)) / max(np.max(np.abs(ref)), 1e-30))


# ---------------------------------------------------------------------------
# phase 1: the device
# ---------------------------------------------------------------------------

def check_device(n_chips: int) -> dict:
    from repro.kernels import dispatch as kdsp
    devs = jax.devices()
    d0 = devs[0]
    if d0.platform != "tpu":
        raise SystemExit(f"chip_smoke: no TPU (JAX found {d0.platform})")
    print(f"[1] device: {d0.platform} {d0.device_kind!r} x{len(devs)}",
          flush=True)
    info = kdsp.backend_info()
    print(f"    kernels: {info}")
    check(info["resolved"] == "pallas" and not info["interpret"],
          f"kernels would not run compiled ({kdsp.ENV_VAR}="
          f"{info['env']!r}, override {info['override']!r}): {info}")
    check(len(devs) >= n_chips,
          f"--chips {n_chips} needs {n_chips} devices, found {len(devs)}")
    return {"platform": d0.platform, "kind": d0.device_kind,
            "count": len(devs)}


# ---------------------------------------------------------------------------
# phase 2: the paper's pipeline on ViT-B/16
# ---------------------------------------------------------------------------

def vit_images(batch: int):
    from repro.data.pipeline import SyntheticImageDataset
    imgs, _ = SyntheticImageDataset(batch_size=batch).sample(
        np.random.RandomState(SEED))
    return {"images": jnp.asarray(imgs)}


def vit_pipeline() -> None:
    from repro.api import ExecutionPlan, InferenceSession
    from repro.profiling import SweepSpec
    t0 = time.perf_counter()
    prism = ExecutionPlan.prism_sim(L=VIT_L, cr=VIT_CR)
    session = InferenceSession.from_config(
        "vit-base-16", plans=[ExecutionPlan.local(), prism],
        reduced=False, seed=SEED)
    cfg = session.cfg
    print(f"[2] {cfg.name}: d_model {cfg.d_model}, {cfg.n_heads} heads, "
          f"{cfg.n_layers} layers, batch {VIT_BATCH}", flush=True)
    pm = session.profile(SweepSpec(batches=(1, VIT_BATCH)),
                         backend="measured", iters=2, warmup=1)
    d = session.decide(VIT_BATCH)
    print(f"    measured sweep: {len(pm)} entries; decide(B={VIT_BATCH}) → "
          f"{d.mode}" + (f" CR={d.cr:g}" if d.cr else ""))
    batch = vit_images(VIT_BATCH)
    out = {}
    for key in ("local", prism.key):
        out[key] = np.asarray(jax.block_until_ready(session.run(key, batch)))
        check(out[key].shape == (VIT_BATCH, cfg.vocab_size),
              f"{key} logits shape {out[key].shape}")
        check(bool(np.all(np.isfinite(out[key]))), f"{key} logits not finite")
    agree = float(np.mean(out["local"].argmax(-1)
                          == out[prism.key].argmax(-1)))
    print(f"    local vs {prism.key}: prediction agreement {agree:.0%}")
    wall("phase 2", t0)


# ---------------------------------------------------------------------------
# phase 3: internlm2-1.8b through ServingRuntime
# ---------------------------------------------------------------------------

def lm_prompts(vocab: int):
    """A; A+B (a partial prefix hit in the paged pool); A again (a full
    hit); and an unrelated long prompt (a miss)."""
    rng = np.random.RandomState(SEED)
    a = rng.randint(0, vocab, BASE)
    ab = np.concatenate([a, rng.randint(0, vocab, EXTRA)])
    return [a, ab, a.copy(), rng.randint(0, vocab, LONG)]


def serve(session, prompts, max_len: int, **paged):
    from repro.serving import ServingRuntime
    rt = ServingRuntime(session, n_slots=len(prompts), chunk=CHUNK,
                        max_len=max_len, **paged)
    reqs = [rt.submit(p, N_NEW) for p in prompts]
    done = {c.request_id: c.tokens for c in rt.run()}
    toks = [np.asarray(done[r.id]) for r in reqs]
    for i, t in enumerate(toks):
        check(t.shape == (N_NEW,), f"request {i}: {t.shape[0]} tokens")
    return toks, rt


def lm_serving() -> dict:
    from repro.api import ExecutionPlan, InferenceSession
    from repro.models import registry
    from repro.models import transformer as tfm
    t0 = time.perf_counter()
    session = InferenceSession.from_config(
        "internlm2-1.8b", reduced=False, seed=SEED,
        allow_modes=("local",))
    session.profile(backend="simulated")   # the scheduler's policy table
    cfg, params = session.cfg, session.params
    xcfg = ExecutionPlan.local().to_exchange_config()
    print(f"[3] {cfg.name}: d_model {cfg.d_model}, {cfg.n_heads}/"
          f"{cfg.n_kv_heads} heads x {cfg.hd}, {cfg.n_layers} layers, "
          f"vocab {cfg.vocab_size}, {cfg.dtype}", flush=True)
    wall("init", t0)
    prompts = lm_prompts(cfg.vocab_size)
    max_len = max(len(p) for p in prompts) + N_NEW

    t0 = time.perf_counter()
    dense, rt_dense = serve(session, prompts, max_len)
    wall(f"dense pool, {len(prompts)} requests x {N_NEW} tokens", t0)
    t0 = time.perf_counter()
    paged, rt_paged = serve(session, prompts, max_len, page_size=PAGE_SIZE,
                            n_rows=len(prompts), prefix_cache=True)
    wall(f"paged pool (page size {PAGE_SIZE}, prefix cache on)", t0)
    st = rt_paged.stats_snapshot()
    print(f"    prefix cache: {st['full_hits']} full / {st['partial_hits']} "
          f"partial hits, {st['prefix_misses']} misses")
    check(st["full_hits"] >= 1 and st["partial_hits"] >= 1,
          f"the prompts did not exercise the prefix cache: {st}")

    # prefill, then one decode step through the cache, against the model's
    # full forward pass (causal: the zero padding after the sequence does
    # not reach earlier positions, so one padded length serves every check)
    t0 = time.perf_counter()
    fwd = jax.jit(lambda p, t: registry.forward_fn(cfg)(
        p, {"tokens": t}, xcfg)[0])

    def forward_at(seq, pos):
        buf = np.zeros((1, max_len), np.int32)
        buf[0, :len(seq)] = seq
        return np.asarray(fwd(params, jnp.asarray(buf))[0, pos], np.float32)

    def prime(prompt):
        return session.prime_slot(jnp.asarray(prompt[None], jnp.int32),
                                  total_len=max_len, with_logits=True)

    prompt = prompts[0]
    T0 = len(prompt)
    tok0, cache, _, pre = prime(prompt)
    # past the prompt the cache holds the zeros it was made with (the
    # compiler has been seen to leave them uninitialized)
    check(not any(bool(jnp.any(c[:, :, T0:] != 0))
                  for c in jax.tree_util.tree_leaves(cache)),
          "the primed cache is not zero past the prompt")
    step = jax.jit(lambda p, t, c, i: tfm.decode_step(
        p, {"tokens": t}, c, i, cfg, xcfg)[0][0, 0])
    seq = np.append(prompt, int(np.asarray(tok0)[0, 0]))
    ref_pre, ref_dec = forward_at(seq, T0 - 1), forward_at(seq, T0)
    err_pre = rel_err(np.asarray(pre)[0, 0], ref_pre)
    err_dec = rel_err(step(params, tok0, cache, jnp.int32(T0)), ref_dec)
    print(f"    logits vs full forward (max |Δ| / max |logit|): prefill "
          f"{err_pre:.2e}, decode step {err_dec:.2e} (limit {LOGIT_RTOL:g})")
    check(err_pre <= LOGIT_RTOL and err_dec <= LOGIT_RTOL,
          "decode-path logits disagree with the full forward pass")
    # controls: the same decode step, made wrong on purpose, must fail the
    # same measure, or the check above could not see such a fault
    ctl_pos = rel_err(step(params, tok0, cache, jnp.int32(T0 - 1)), ref_dec)
    ctl_cache = rel_err(step(params, tok0, prime(prompts[3])[1],
                             jnp.int32(T0)), ref_dec)
    print(f"    controls: cache position off by one {ctl_pos:.2e}, another "
          f"prompt's cache {ctl_cache:.2e} (each must exceed the limit)")
    check(min(ctl_pos, ctl_cache) > LOGIT_RTOL,
          "the logits check cannot tell a wrong decode step from a right one")

    # dense vs paged tokens.  The pools reduce attention in different tile
    # orders, so a token may differ only where the model itself cannot
    # separate the two candidates: their full-forward logits must lie
    # within the bf16 bound above, LOGIT_RTOL of the largest |logit| there.
    exact = 0
    for i, (a, b) in enumerate(zip(dense, paged)):
        if np.array_equal(a, b):
            exact += 1
            continue
        j = int(np.argmax(a != b))
        lg = forward_at(np.concatenate([prompts[i], a[:j]]),
                        len(prompts[i]) + j - 1)
        gap = abs(float(lg[a[j]] - lg[b[j]]))
        tie = LOGIT_RTOL * float(np.max(np.abs(lg)))
        print(f"    request {i}: dense/paged diverge at token {j} "
              f"({a[j]} vs {b[j]}), logit gap {gap:.3g}, bound {tie:.3g}")
        check(gap <= tie, f"request {i}: dense and paged tokens differ "
              "at a position the model separates clearly")
    print(f"    dense vs paged: {exact}/{len(prompts)} requests "
          "token-identical")
    wall("logit checks", t0)
    return {"cfg": cfg, "xcfg": xcfg, "params": params,
            "max_len": max_len, "dense": next(iter(rt_dense.pools.values())),
            "paged": next(iter(rt_paged.pools.values()))}


# ---------------------------------------------------------------------------
# phase 4: the decode executables hold the kernels
# ---------------------------------------------------------------------------

def abstract(tree):
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(np.shape(x), x.dtype,
                                       sharding=getattr(x, "sharding", None)),
        tree)


def decode_chunk_texts(run: dict) -> dict:
    """Compiled text of the dense and paged decode-chunk programs phase 3
    ran (same builders, same shapes: the compilation cache returns them)."""
    from repro.api import generation as gen
    cfg, xcfg, params = run["cfg"], run["xcfg"], run["params"]
    d, p = run["dense"], run["paged"]
    dense = gen.build_decode_chunk_fn(cfg, xcfg, n_steps=CHUNK,
                                      max_len=run["max_len"]).jitted
    paged = gen.build_paged_decode_chunk_fn(cfg, xcfg, n_steps=CHUNK).jitted
    caps = np.zeros((p.n_rows,), np.int32)
    return {
        "dense": dense.lower(*abstract((params, d.cache, d.tok, d.lengths,
                                        d.keys, d.temps))).compile().as_text(),
        "paged": paged.lower(*abstract((params, p.pool, p.page_table, caps,
                                        p.tok, p.lengths, p.keys, p.temps))
                             ).compile().as_text()}


def decode_executables_have_kernels(run: dict) -> None:
    t0 = time.perf_counter()
    for name, text in decode_chunk_texts(run).items():
        n = text.count("tpu_custom_call")
        print(f"[4] {name} decode chunk: {n} tpu_custom_call site(s)")
        check(n > 0, f"the {name} decode chunk runs no Pallas kernel")
    wall("phase 4", t0)


# ---------------------------------------------------------------------------
# --chips 4: the paper's split attention across a mesh
# ---------------------------------------------------------------------------

def split_attention(n_chips: int) -> None:
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.api import ExecutionPlan, InferenceSession
    from repro.kernels import dispatch as kdsp
    from repro.launch.mesh import make_auto_mesh
    from repro.models import registry
    t0 = time.perf_counter()
    one = InferenceSession.from_config(
        "vit-base-16", reduced=False, seed=SEED,
        plans=[ExecutionPlan.local(),
               ExecutionPlan.prism_sim(L=VIT_L, seq_shards=n_chips)])
    batch = vit_images(VIT_BATCH)
    # the references run no Pallas kernel, so a faulty compiled kernel
    # cannot sit on both sides of the comparison
    with kdsp.force_backend("reference"):
        ref = {k: np.asarray(one.run(k, batch)) for k in ("local", "prism")}
    print(f"[2x{n_chips}] {one.cfg.name} one-chip references (reference "
          f"kernels): local, prism_sim(L={VIT_L}, P={n_chips})", flush=True)

    mesh = make_auto_mesh((n_chips,), ("seq",))
    rep = NamedSharding(mesh, P())
    split = InferenceSession(
        one.cfg, jax.device_put(one.params, rep),
        plans=[ExecutionPlan.prism(L=VIT_L, seq_shards=n_chips),
               ExecutionPlan.voltage(seq_shards=n_chips)])
    images = jax.device_put(batch["images"], rep)
    devs = set(mesh.devices.flat)
    check(images.sharding.device_set == devs,
          f"inputs live on {images.sharding.device_set}, not the mesh")
    fwd = registry.forward_fn(one.cfg)
    with jax.sharding.set_mesh(mesh):
        for key, base in (("prism", "prism"), ("voltage", "local")):
            xcfg = split.plans[key].to_exchange_config()
            text = jax.jit(lambda p, b: fwd(p, b, xcfg)[0]).lower(
                abstract(split.params), {"images": images}).as_text()
            check("all_gather" in text, f"{key}: no all-gather in the program")
            out = jax.block_until_ready(split.run(key, {"images": images}))
            check(out.sharding.device_set == devs,
                  f"{key} ran on {out.sharding.device_set}")
            out = np.asarray(out)
            check(bool(np.all(np.isfinite(out))), f"{key}: logits not finite")
            err = rel_err(out, ref[base])
            agree = float(np.mean(out.argmax(-1) == ref[base].argmax(-1)))
            print(f"    {key} over {n_chips} chips vs one-chip {base}: "
                  f"max |Δ| / max |logit| {err:.2e} (limit {LOGIT_RTOL:g}), "
                  f"prediction agreement {agree:.0%}")
            check(err <= LOGIT_RTOL, f"{key} disagrees with {base}")
    wall(f"{n_chips}-chip phase", t0)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)
    configure_compile_cache()
    device = check_device(args.chips)
    from repro.kernels import dispatch as kdsp
    if args.chips == 1:
        vit_pipeline()
        run = lm_serving()
        decode_executables_have_kernels(run)
    else:
        split_attention(args.chips)
    print(f"kernel fallbacks to the reference: "
          f"{kdsp.fallback_counts() or 'none'}")
    print(json.dumps({"ok": True, "device": device}))


if __name__ == "__main__":
    main()
