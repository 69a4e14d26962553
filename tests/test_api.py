"""The unified `repro.api` surface: ExecutionPlan conversions, the strategy
registry, InferenceSession routing vs the raw policy, perf-map hardening,
and the legacy deprecation shims."""
import dataclasses
import warnings

import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import (AdaptivePolicy, ExchangeConfig, ExchangeMode,
                       ExecutionPlan, InferenceSession, PerfKey, PerfMap,
                       get_strategy, list_strategies, profile_simulated,
                       register_strategy)
from repro.api.strategies import ExchangeStrategy
from repro.core.perfmap import SCHEMA_VERSION, PerfEntry


@pytest.fixture(scope="module")
def perfmap():
    return profile_simulated()


@pytest.fixture(scope="module")
def session(perfmap):
    sess = InferenceSession.from_config(
        "llama3.2-1b", reduced={"vocab_size": 64},
        plans=[ExecutionPlan.local(), ExecutionPlan.prism_sim(L=4, cr=9.9)],
        perfmap=perfmap)
    return sess


# --- ExecutionPlan ---------------------------------------------------------

def test_plan_keys():
    assert ExecutionPlan.local().key == "local"
    assert ExecutionPlan.prism(L=10, cr=9.9).key == "prism@9.9"
    # prism_sim shares prism's profiling identity
    assert ExecutionPlan.prism_sim(L=4, cr=4.95).key == "prism@4.95"
    assert ExecutionPlan.voltage().key == "voltage"


def test_plan_exchange_config_roundtrip():
    plan = ExecutionPlan.prism(L=10, cr=9.9, seq_axis="seq", seq_shards=2,
                               batch_axes=("data",))
    xcfg = plan.to_exchange_config()
    assert xcfg == ExchangeConfig(ExchangeMode.PRISM, "seq", 2, L=10,
                                  batch_axes=("data",), strategy="prism")
    back = ExecutionPlan.from_exchange_config(xcfg, cr=9.9)
    assert back == plan
    # CR recoverable from the sequence length: CR = N/(L·P) = 197/(10·2)
    lifted = ExecutionPlan.from_exchange_config(xcfg, n_tokens=197)
    assert lifted.cr == pytest.approx(9.85)


def test_plan_local_exchange_config_is_degenerate():
    xcfg = ExecutionPlan.local().to_exchange_config()
    assert xcfg.mode == ExchangeMode.LOCAL
    assert xcfg.seq_axis is None and xcfg.seq_shards == 1


def test_plan_perf_key_roundtrip():
    plan = ExecutionPlan.prism(L=10, cr=9.9)
    pk = plan.to_perf_key(8, 400.0)
    assert pk == PerfKey("prism", 8, 9.9, 400.0)
    back = ExecutionPlan.from_perf_key(pk, n_tokens=197, seq_shards=2)
    assert back.mode == "prism" and back.cr == 9.9 and back.L == 10
    sim = ExecutionPlan.from_perf_key(pk, n_tokens=197, simulated=True)
    assert sim.mode == "prism_sim" and sim.key == plan.key
    # local plans profile at bw=0 regardless of the observed bandwidth
    assert ExecutionPlan.local().to_perf_key(4, 700.0) == \
        PerfKey("local", 4, 0.0, 0.0)


def test_plan_parse_legacy_keys():
    p = ExecutionPlan.parse("prism@9.9", L=4)
    assert p.mode == "prism" and p.cr == 9.9 and p.L == 4
    assert ExecutionPlan.parse("local") == ExecutionPlan.local()
    with pytest.raises(ValueError):
        ExecutionPlan.parse("prism@fast")


def test_plan_validation_errors():
    with pytest.raises(KeyError):
        ExecutionPlan(mode="warp")
    with pytest.raises(ValueError):                 # PRISM without L or CR
        ExecutionPlan(mode="prism", seq_axis="seq", seq_shards=2)
    with pytest.raises(ValueError):                 # shards without an axis
        ExecutionPlan(mode="voltage", seq_axis=None, seq_shards=2)


def test_plan_resolve_L():
    plan = ExecutionPlan(mode="prism", cr=9.9, seq_axis="seq", seq_shards=2)
    assert plan.resolve_L(197).L == 10
    assert plan.resolve_L(197).resolve_L(400).L == 10   # idempotent


def test_exchange_config_with_mode_preserves_all_fields():
    xcfg = ExchangeConfig(ExchangeMode.PRISM, "seq", 4, L=8,
                          batch_axes=("data", "pod"))
    out = xcfg.with_mode(ExchangeMode.VOLTAGE)
    assert out == dataclasses.replace(xcfg, mode=ExchangeMode.VOLTAGE)


# --- strategy registry -----------------------------------------------------

def test_registry_contents():
    assert set(list_strategies()) >= {"local", "voltage", "prism",
                                      "prism_sim"}
    assert get_strategy("prism").distributed
    assert not get_strategy("local").distributed
    assert get_strategy("prism_sim").perf_mode == "prism"
    assert not get_strategy("voltage").selectable


def test_registry_unknown_lookup():
    with pytest.raises(KeyError, match="unknown exchange strategy"):
        get_strategy("warp")


def test_registry_rejects_duplicates_and_anonymous():
    with pytest.raises(ValueError, match="already registered"):
        @register_strategy
        class Dup(ExchangeStrategy):       # noqa: F811 — intentional clash
            name = "local"
    with pytest.raises(ValueError, match="non-empty `name`"):
        @register_strategy
        class Anon(ExchangeStrategy):
            name = ""


def test_new_strategy_plugs_into_plans():
    """A custom strategy reusing a built-in ExchangeMode must actually be
    dispatched by exchange_attention (via ExchangeConfig.strategy), not
    silently resolve back to the built-in."""
    from repro.core.exchange import exchange_attention

    @register_strategy
    class EchoStrategy(ExchangeStrategy):
        name = "echo-test"
        exchange_mode = ExchangeMode.PRISM     # reuses a built-in mode
        distributed = True

        def _prefill(self, q, k, v, cfg, **kw):
            return q + 1.0                      # sentinel, no collectives
    try:
        plan = ExecutionPlan(mode="echo-test", seq_axis="seq", seq_shards=2)
        assert plan.key == "echo-test"
        xcfg = plan.to_exchange_config()
        assert xcfg.mode == ExchangeMode.PRISM and xcfg.strategy == "echo-test"
        q = jnp.zeros((1, 8, 2, 4), jnp.float32)
        out = exchange_attention(q, q, q, xcfg)
        assert float(out.sum()) == q.size       # EchoStrategy ran, not PRISM
    finally:
        from repro.api import strategies as S
        S._REGISTRY.pop("echo-test")


# --- perf-map hardening ----------------------------------------------------

def test_perfkey_rejects_pipe_mode():
    with pytest.raises(ValueError):
        PerfKey("pri|sm", 8, 9.9, 400.0)


def test_perfkey_decode_tolerates_float_batch():
    assert PerfKey.decode("prism|8.0|9.9|400").batch == 8
    with pytest.raises(ValueError):
        PerfKey.decode("prism|8.5|9.9|400")
    with pytest.raises(ValueError):
        PerfKey.decode("prism|8|9.9")          # missing field


def test_perfmap_schema_version_roundtrip(tmp_path, perfmap):
    path = str(tmp_path / "pm.json")
    perfmap.save(path)
    import json
    data = json.load(open(path))
    assert data["schema_version"] == SCHEMA_VERSION
    assert len(PerfMap.load(path)) == len(perfmap)


def test_perfmap_schema_version_mismatch(tmp_path, perfmap):
    path = str(tmp_path / "pm.json")
    perfmap.save(path)
    import json
    data = json.load(open(path))
    data["schema_version"] = SCHEMA_VERSION + 1
    json.dump(data, open(path, "w"))
    with pytest.raises(ValueError, match="schema version"):
        PerfMap.load(path)


def test_perfmap_loads_legacy_flat_format(tmp_path):
    """Pre-versioning maps (flat key→entry dict) still load."""
    import json
    entry = PerfEntry(1.0, 1.0, 0.1, 0.5, 0.2, 0.3)
    path = str(tmp_path / "legacy.json")
    json.dump({PerfKey("local", 1, 0.0, 0.0).encode(): entry.to_dict()},
              open(path, "w"))
    pm = PerfMap.load(path)
    assert pm.get(PerfKey("local", 1, 0.0, 0.0)).total_ms == 1.0


# --- InferenceSession ------------------------------------------------------

def test_session_dispatch_matches_policy(session, perfmap):
    """Routing under swept (batch, bandwidth) pairs == AdaptivePolicy.decide."""
    pol = AdaptivePolicy(perfmap)
    rng = np.random.RandomState(0)
    V = session.cfg.vocab_size
    for batch in (1, 4, 8, 32):
        for bw in (200.0, 400.0, 900.0):
            session._bw = bw                       # pin the EWMA state
            toks = jnp.asarray(rng.randint(0, V, (batch, 32)))
            out = session.dispatch({"tokens": toks})
            assert out.shape == (batch, 32, V)
            rec = session.history[-1]
            expect = pol.decide(batch, bw)
            assert rec.decision.mode == expect.mode
            assert rec.decision.cr == expect.cr
            assert rec.batch == batch
            assert not rec.substituted             # both plans registered
            want = ("local" if expect.mode == "local"
                    else f"{expect.mode}@{expect.cr:g}")
            assert rec.exec_key == want


def test_session_dispatch_substitution_recorded(perfmap):
    """No local executable registered → same-mode/any fallback, recorded."""
    sess = InferenceSession.from_config(
        "llama3.2-1b", reduced={"vocab_size": 64},
        plans=[ExecutionPlan.prism_sim(L=4, cr=3.3)], perfmap=perfmap)
    toks = jnp.ones((1, 32), jnp.int32)
    sess._bw = 400.0
    sess.dispatch({"tokens": toks})                # B=1 decides "local"
    rec = sess.history[-1]
    assert rec.decision.mode == "local"
    assert rec.substituted and rec.exec_key == "prism@3.3"


def test_session_explain_reproduces_paper_artifacts(session):
    exp = session.explain(8, 400.0)
    pol = session.policy
    assert exp.batch_crossover == pol.batch_crossover(400.0) == 8
    assert exp.bandwidth_crossover == pol.bandwidth_crossover(8)
    assert exp.decision.mode == pol.decide(8, 400.0).mode
    assert exp.plan_key in session.plans
    assert any(k.mode == "local" for k, _ in exp.candidates)
    assert "crossover" in exp.summary()


def test_session_requires_perfmap_for_policy():
    sess = InferenceSession.from_config("llama3.2-1b",
                                        reduced={"vocab_size": 64})
    with pytest.raises(RuntimeError, match="performance map"):
        sess.decide(8)


def test_session_generate_and_run(session):
    prompt = jnp.ones((2, 4), jnp.int32)
    out = session.generate(prompt, n_new=3)
    assert out.shape == (2, 3)
    lg = session.run("local", {"tokens": jnp.ones((1, 32), jnp.int32)})
    assert lg.shape == (1, 32, session.cfg.vocab_size)
    with pytest.raises(KeyError):
        session.run("voltage", {"tokens": jnp.ones((1, 32), jnp.int32)})


def test_session_generate_distinct_plans_not_conflated(session):
    """Two plans sharing a key (prism_sim L=4 vs L=8, both cr=0) must get
    distinct decode executables — and sim plans must decode at all
    (exact path; sim has no sharded-cache analogue)."""
    prompt = jnp.ones((1, 4), jnp.int32)
    n0 = len(session._decode_execs)
    o1 = session.generate(prompt, n_new=2, plan=ExecutionPlan.prism_sim(L=4))
    o2 = session.generate(prompt, n_new=2, plan=ExecutionPlan.prism_sim(L=8))
    assert o1.shape == o2.shape == (1, 2)
    assert len(session._decode_execs) == n0 + 2


def test_session_duplicate_plan_rejected(session):
    with pytest.raises(ValueError, match="already registered"):
        session.add_plan(ExecutionPlan.local())


def test_session_rejects_unresolved_L(session):
    """A cr-only plan (no physical L) cannot be jitted — clear error up
    front instead of a ZeroDivisionError at trace time."""
    with pytest.raises(ValueError, match="resolve_L"):
        session.add_plan(ExecutionPlan.parse("prism@3.3"))
    # resolving L makes the same plan registrable
    key = session.add_plan(ExecutionPlan.parse("prism@3.3").resolve_L(197))
    assert key == "prism@3.3"


def test_session_bandwidth_ewma():
    sess = InferenceSession.from_config(
        "llama3.2-1b", reduced={"vocab_size": 64},
        bandwidth_alpha=0.5, initial_bandwidth_mbps=400.0)
    sess.observe_bandwidth(200.0)
    assert sess.bandwidth == pytest.approx(300.0)


# --- legacy shims are gone -------------------------------------------------

def test_legacy_shims_removed():
    """The docs promised removal in this release: the serving package no
    longer exports the deprecated dispatcher/engine surfaces."""
    import repro.serving as serving
    assert not hasattr(serving, "AdaptiveDispatcher")
    assert not hasattr(serving, "ServeEngine")
    assert "AdaptiveDispatcher" not in serving.__all__
    assert "ServeEngine" not in serving.__all__
    with pytest.raises(ImportError):
        from repro.serving import AdaptiveDispatcher  # noqa: F401
    with pytest.raises(ImportError):
        from repro.serving.dispatcher import AdaptiveDispatcher  # noqa: F401,F811


def test_plan_executables_take_params_as_an_argument(session):
    """The per-plan executables read ``session.params`` at call time: the
    parameters are an argument of the compiled program, not constants
    baked into it (which would copy the whole model into every program)."""
    import jax
    batch = {"tokens": jnp.ones((1, 8), jnp.int32)}
    before = np.asarray(session.run("local", batch))
    params = session.params
    try:
        session.params = jax.tree_util.tree_map(lambda p: p * 2, params)
        after = np.asarray(session.run("local", batch))
    finally:
        session.params = params
    assert not np.allclose(before, after)
    np.testing.assert_array_equal(np.asarray(session.run("local", batch)),
                                  before)


def test_compile_cache_env_wins_else_fixed_checkout_dir(monkeypatch):
    import jax
    from repro.utils import compile_cache as cc
    was = jax.config.jax_compilation_cache_dir
    try:
        jax.config.update("jax_compilation_cache_dir", None)
        monkeypatch.setenv(cc.ENV_VAR, "/elsewhere/cache")
        assert cc.configure_compile_cache() == "/elsewhere/cache"
        assert jax.config.jax_compilation_cache_dir is None  # JAX's own
        monkeypatch.delenv(cc.ENV_VAR)
        path = cc.configure_compile_cache()
        assert path == str(cc.CHECKOUT_CACHE)
        assert jax.config.jax_compilation_cache_dir == path
        root = cc.CHECKOUT_CACHE.parent
        assert (root / "src" / "repro").is_dir()
        assert ".jax_cache/" in (root / ".gitignore").read_text().split()
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
