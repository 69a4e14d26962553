"""E2E distributed check on 8 host devices: PRISM train step + sharded
decode on a reduced llama over a (4 data × 2 model) mesh. Invoked as a
subprocess by tests/test_distributed.py so the 8-device XLA flag never
leaks into the main pytest process."""
import os
os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=8 "
                           "--xla_disable_hlo_passes=all-reduce-promotion")

import jax
import jax.numpy as jnp
import numpy as np

from repro.api import ExecutionPlan
from repro.configs import get_config
from repro.core.exchange import ExchangeMode
from repro.models import registry, transformer as tfm
from repro.sharding.specs import (batch_shardings, cache_shardings, make_plan,
                                  opt_state_shardings, param_shardings)
from repro.train.optimizer import adamw_init
from repro.train.train_step import build_train_step
from repro.launch.mesh import make_auto_mesh

mesh = make_auto_mesh((4, 2), ("data", "model"))
cfg = get_config("llama3.2-1b").reduced()
rng = np.random.RandomState(0)
B, N = 8, 32

with jax.sharding.set_mesh(mesh):
    for mode in (ExchangeMode.PRISM, ExchangeMode.VOLTAGE):
        plan = make_plan(mesh, cfg, mode, L=4, train=True)
        xcfg = plan.xcfg
        params = registry.init_params(cfg, seed=0)
        pshard = param_shardings(plan, cfg, params)
        params = jax.device_put(params, pshard)
        aopt = jax.eval_shape(adamw_init, params)
        opt = jax.device_put(adamw_init(params),
                             opt_state_shardings(plan, cfg, aopt))
        batch = {"tokens": jnp.asarray(rng.randint(0, cfg.vocab_size, (B, N))),
                 "labels": jnp.asarray(rng.randint(0, cfg.vocab_size, (B, N)))}
        bshard = batch_shardings(plan, cfg, jax.eval_shape(lambda: batch),
                                 "train")
        batch = jax.device_put(batch, bshard)
        step = jax.jit(build_train_step(cfg, xcfg),
                       in_shardings=(pshard, None, None),
                       donate_argnums=(0,))
        params2, opt2, m = step(params, opt, batch)
        loss = float(m["loss"])
        assert np.isfinite(loss), (mode, loss)
        print(f"train {mode.value}: loss {loss:.3f} OK")

    # distributed PRISM forward == single-host PRISM_SIM oracle
    plan = make_plan(mesh, cfg, ExchangeMode.PRISM, L=4)
    params = registry.init_params(cfg, seed=0)
    tokens = jnp.asarray(rng.randint(0, cfg.vocab_size, (4, 32)))
    lg_dist, _ = jax.jit(lambda p, t: registry.forward_fn(cfg)(
        p, {"tokens": t}, plan.xcfg))(params, tokens)
    xsim = ExecutionPlan.prism_sim(L=4, seq_axis="model",
                               seq_shards=2).to_exchange_config()
    lg_sim, _ = registry.forward_fn(cfg)(params, {"tokens": tokens}, xsim)
    np.testing.assert_allclose(np.asarray(lg_dist), np.asarray(lg_sim),
                               atol=0.15, rtol=0.05)
    print("distributed PRISM forward == single-host oracle OK")

    # sharded decode vs local decode
    plan = make_plan(mesh, cfg, ExchangeMode.PRISM, L=4)
    cache = tfm.init_decode_cache(cfg, 4, 32)
    cshard = cache_shardings(plan, cfg, jax.eval_shape(lambda: cache))
    cache = jax.device_put(cache, cshard)
    dec = jax.jit(lambda p, b, c, i: tfm.decode_step(p, b, c, i, cfg,
                                                     plan.xcfg),
                  donate_argnums=(2,))
    tok = tokens[:, :1]
    lg_d, cache = dec(params, {"tokens": tok}, cache, 0)
    cache_l = tfm.init_decode_cache(cfg, 4, 32)
    lg_l, _ = tfm.decode_step(params, {"tokens": tok}, cache_l, 0, cfg,
                              ExecutionPlan.local().to_exchange_config())
    np.testing.assert_allclose(np.asarray(lg_d), np.asarray(lg_l), atol=0.1,
                               rtol=0.05)
    print("sharded decode == local decode OK")

print("E2E DISTRIBUTED SANITY PASSED")
