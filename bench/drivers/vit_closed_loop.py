"""One closed-loop client classifying image batches through the paper's
adaptive policy: ``InferenceSession.dispatch`` decides local or split
execution per batch and runs it across the host's chips.

Set-up: weights from the seed, made replicated over a ``("seq",)`` mesh
of the cell's chips; the session with the configuration's plans; the
policy from ``session.profile()`` with its default arguments; a pool of
float32 images from the seed in host memory; one dispatch at every batch
size of the traffic.  The window: batches of seeded sizes, each copied
from host memory and dispatched as soon as the previous one returned.
"""
from __future__ import annotations

import json
import shutil
import tempfile

import numpy as np

from bench import counts, harness, traffic
from bench.models import vit
from bench.trace_reduce import Profile

clock = __import__("time").perf_counter


def program_config(config):
    import dataclasses
    from repro.configs import get_config
    from repro.models import vit as vit_mod
    m = config["model"]
    cfg = dataclasses.replace(
        get_config(config["program_arch"]), n_layers=m["num_hidden_layers"],
        d_model=m["hidden_size"], n_heads=m["num_attention_heads"],
        n_kv_heads=m["num_attention_heads"], d_ff=m["intermediate_size"],
        vocab_size=m["num_labels"], dtype=m["torch_dtype"])
    fixed = {"family": (cfg.family, "vit"), "norm": (cfg.norm_type,
                                                      "layernorm"),
             "act": (cfg.act, "gelu"), "qkv_bias": (cfg.qkv_bias, False),
             "image": (vit_mod.IMAGE, m["image_size"]),
             "patch": (vit_mod.PATCH, m["patch_size"])}
    wrong = {k: v for k, v in fixed.items() if v[0] != v[1]}
    if wrong:
        raise SystemExit(f"{config['name']}: the program differs from the "
                         f"reference's semantics: {wrong}")
    return cfg


def plans_for(config, n_tokens: int):
    """The configuration's plans, with PRISM's physical segment count
    from its compression rate."""
    from repro.api import ExecutionPlan
    from repro.core.segment_means import cr_to_L
    out = []
    for p in config["deployment"]["plans"]:
        if p["mode"] == "local":
            out.append(ExecutionPlan.local())
        elif p["mode"] == "prism":
            out.append(ExecutionPlan.prism(
                L=cr_to_L(n_tokens, p["seq_shards"], p["cr"]), cr=p["cr"],
                seq_axis=p["seq_axis"], seq_shards=p["seq_shards"]))
        elif p["mode"] == "voltage":
            out.append(ExecutionPlan.voltage(seq_axis=p["seq_axis"],
                                             seq_shards=p["seq_shards"]))
        else:
            raise SystemExit(f"unknown plan mode {p['mode']!r}")
    return out


def reference_mode(plan):
    """(partitions, means) of the reference for the plan that ran."""
    if plan.mode == "prism":
        return plan.seq_shards, plan.L
    return 1, 0                      # local, and voltage (exact attention)


def run(cell, args, t_start, devices) -> harness.RunRecord:
    import jax
    from jax.sharding import NamedSharding, PartitionSpec
    from repro.api import InferenceSession
    from repro.launch.mesh import make_auto_mesh
    w = cell.workload
    p = w["traffic"]
    m = cell.config["model"]
    mesh_cfg = cell.config["deployment"]["mesh"]
    mesh = make_auto_mesh(mesh_cfg["shape"], mesh_cfg["axes"])
    weights = vit.make_weights(m, harness.key_int(args.seed, 0),
                               sharding=NamedSharding(mesh, PartitionSpec()))
    cfg = program_config(cell.config)
    plans = plans_for(cell.config, counts.vit_tokens(m))
    session = InferenceSession(cfg, vit.program_params(weights), plans=plans)
    session.profile()
    for b in sorted(set(p["batch_sizes"])):
        key, substituted = session._exec_key_for(session.decide(b))
        if substituted:
            raise SystemExit(f"policy picks {session.decide(b).exec_key!r} "
                             f"at batch {b}, which no plan registers")
    rng = harness.rng(args.seed, 5)
    pool = rng.standard_normal((p["image_pool"], m["image_size"],
                                m["image_size"], m["num_channels"]),
                               dtype=np.float32)

    def images(start, b):
        return np.take(pool, np.arange(start, start + b) % len(pool), axis=0)

    with jax.sharding.set_mesh(mesh):
        for b in sorted(set(p["batch_sizes"])):
            session.dispatch({"images": images(0, b)})
        compiles = harness.CompileCounter()
        sizes = traffic.batch_sizes(p, args.seed, harness.rng)
        prof = Profile(tempfile.mkdtemp(prefix="bench-trace-"), clock) \
            if args.trace else None
        n_hist = len(session.history)
        t_open = clock()
        t_close = t_open + args.seconds
        t_trace = t_close - min(w["trace_s"], args.seconds)
        done = []             # (size, first image, start, end, output)
        compiles.start()
        while True:
            now = clock()
            if now >= t_close:
                break
            if prof is not None and prof.t_open is None and now >= t_trace:
                prof.start()
            b, start = next(sizes), int(rng.integers(0, len(pool)))
            out = session.dispatch({"images": images(start, b)})
            done.append((b, start, now, clock(), out))
        compiles.stop()
        if prof is not None:
            prof.stop()
    t_end = clock()
    history = session.history[n_hist:n_hist + len(done)]
    print(f"client: {len(done)} batches, {sum(d[0] for d in done)} images; "
          f"plans {json.dumps(_plan_counts(history))}; compilations in "
          f"the window: {compiles.count} {sorted(set(compiles.names))}",
          flush=True)
    device = harness.device_info(devices)
    trace = None
    if prof is not None:
        trace = prof.reduce()
        shutil.rmtree(prof.log_dir, ignore_errors=True)
    records = [{"batch": b, "start": t0, "end": t1, "plan": h.exec_key}
               for (b, _, t0, t1, _), h in zip(done, history)]
    check = _check(session, weights, m, done, history, w["check"],
                   args.seed, images, max(p["batch_sizes"]))
    return harness.RunRecord(
        cell=cell, setup_s=t_open - t_start, window_s=args.seconds,
        attempted=len(done), failed=0, checks=[check], device=device,
        trace=trace, recheck=lambda precision: _check(
            session, weights, m, done, history, w["check"], args.seed,
            images, max(p["batch_sizes"]), precision),
        data={"batches": records, "t_open": t_open, "t_close": t_close,
              "t_end": t_end, "model": m, "chips": len(devices),
              "plans": {k: reference_mode(v) for k, v
                        in session.plans.items()},
              "trace_window": (prof.t_open, prof.t_close) if prof else None,
              "compiles": compiles.count})


def _plan_counts(history):
    out = {}
    for h in history:
        out[h.exec_key] = out.get(h.exec_key, 0) + h.batch
    return out


def _check(session, weights, m, done, history, check, seed, images,
           ref_batch, precision="float32"):
    """Widest relative logit error, max |out - ref| / max |ref|, over a
    sample of the window's batches drawn from the seed (the largest among
    them), each against the reference of the plan that ran it.  Under
    ``precision="fp8"`` (the control) the fp8 reference takes the
    program's place."""
    if not done:
        return harness.Check("logit_rel_err", float("inf"), check["limit"])
    order = harness.rng(seed, 6).permutation(len(done))
    largest = max(range(len(done)), key=lambda i: (done[i][0], -i))
    pick = [largest] + [int(i) for i in order if i != largest]
    pick = pick[:check["sample"]]
    plans = {k: reference_mode(v) for k, v in session.plans.items()}
    worst = 0.0
    for i in pick:
        b, start, _, _, out = done[i]
        parts, means = plans[history[i].exec_key]
        x = images(start, b)
        ref = vit.reference_logits(weights, m, x, parts, means, ref_batch)
        got = (np.asarray(out, np.float32) if precision == "float32" else
               vit.reference_logits(weights, m, x, parts, means, ref_batch,
                                    precision))
        worst = max(worst, float(np.max(np.abs(got - ref))
                                 / np.max(np.abs(ref))))
    harness.log(f"check: {len(pick)} batches, "
                f"{sum(done[i][0] for i in pick)} images, plans "
                f"{sorted({history[i].exec_key for i in pick})}")
    return harness.Check("logit_rel_err", worst, check["limit"])
