"""What the two serving loops share: the model on the chip behind
``ServingRuntime``, a client that stamps deliveries, warm-up, the drain,
and the check of served tokens against the reference.

The program is driven only through its public surface: ``submit``,
``step`` and the ``on_progress`` hook, which fires after each decode
chunk, when a streaming client receives tokens.
"""
from __future__ import annotations

import dataclasses
import gc
import time
from typing import Any, Dict, Iterable, List, Optional

import numpy as np

from bench import harness
from bench.models import lm_dense
from bench.traffic import LMRequest

clock = time.perf_counter


def program_config(config: Dict[str, Any]):
    """The program's ``ModelConfig`` at the sizes the file states."""
    import dataclasses as dc
    from repro.configs import get_config
    m = config["model"]
    cfg = get_config(config["program_arch"])
    cfg = dc.replace(cfg, n_layers=m["num_hidden_layers"],
                     d_model=m["hidden_size"],
                     n_heads=m["num_attention_heads"],
                     n_kv_heads=m["num_key_value_heads"],
                     head_dim=m["head_dim"], d_ff=m["intermediate_size"],
                     vocab_size=m["vocab_size"], rope_theta=m["rope_theta"])
    expect = {"family": "dense", "norm_type": "rmsnorm", "act": "silu",
              "tie_embeddings": m["tie_word_embeddings"], "qkv_bias": False,
              "dtype": m["torch_dtype"], "window": None,
              "attn_softcap": None, "final_softcap": None,
              "embed_scale": False, "post_norms": False}
    wrong = {k: getattr(cfg, k) for k, v in expect.items()
             if getattr(cfg, k) != v}
    if wrong:
        raise SystemExit(f"{config['name']}: the program's config differs "
                         f"from the reference's semantics: {wrong}")
    return cfg


def same_tree(params, cfg) -> None:
    """The arrays handed to the program fit its parameter tree exactly."""
    import jax
    from repro.models import registry
    want = registry.abstract_params(cfg)
    if jax.tree_util.tree_structure(want) != \
            jax.tree_util.tree_structure(params):
        raise SystemExit("benchmark weights do not match the program's "
                         "parameter tree")
    for a, b in zip(jax.tree_util.tree_leaves(want),
                    jax.tree_util.tree_leaves(params)):
        if a.shape != b.shape or a.dtype != b.dtype:
            raise SystemExit(f"parameter {a.shape}/{a.dtype} handed "
                             f"{b.shape}/{b.dtype}")


@dataclasses.dataclass
class Rec:
    """One request as the client sees it (host clock, seconds)."""
    index: int
    due: float
    prompt: np.ndarray
    n_new: int
    in_window: bool
    tag: str
    submit: Optional[float] = None
    first: Optional[float] = None      # first delivery
    last: Optional[float] = None       # all n_new tokens delivered
    delivered: int = 0
    refused: bool = False
    tokens: Optional[np.ndarray] = None
    rid: int = -1

    @property
    def done(self) -> bool:
        return self.last is not None and self.tokens is not None


class Server:
    """The model on the chip behind ``ServingRuntime``, with a client."""

    def __init__(self, config: Dict[str, Any], seed: int, trace: bool):
        import jax
        from repro.api import ExecutionPlan, InferenceSession
        from repro.obs import Tracer
        from repro.serving import ServingRuntime
        self.m = config["model"]
        dep = config["deployment"]
        self.weights = lm_dense.make_weights(self.m, harness.key_int(seed, 0))
        jax.block_until_ready(self.weights)
        cfg = program_config(config)
        params = lm_dense.program_params(self.weights)
        same_tree(params, cfg)
        session = InferenceSession(cfg, params,
                                   plans=[ExecutionPlan.local()],
                                   allow_modes=("local",))
        session.profile(backend="simulated")   # the scheduler's table
        self.tracer = Tracer("bench", clock=clock) if trace else None
        self.rt = ServingRuntime(
            session, max_len=dep["max_len"], page_size=dep["page_size"],
            n_pages=dep["n_pages"], n_rows=dep["n_rows"],
            prefix_cache=dep["prefix_cache"], clock=clock,
            tracer=self.tracer)
        self.rt.on_progress = self._progress
        self.recs: Dict[int, Rec] = {}
        self.deliveries: List[tuple] = []     # (time, tokens delivered)
        self.chunks: List[tuple] = []         # per decode chunk, see below
        self._chunk_seen = 0
        # per step: (start, until the chunk's first delivery, the rest,
        # requests admitted); the first part holds admission and the chunk
        self.steps: List[tuple] = []
        self._step_first: Optional[float] = None

    # -- client ------------------------------------------------------------

    def submit(self, r: LMRequest, due: float) -> Rec:
        from repro.serving.queue import QueueFull
        rec = Rec(r.index, due, r.prompt, r.n_new, r.in_window, r.tag)
        try:
            req = self.rt.submit(r.prompt, r.n_new, seed=r.index)
        except QueueFull:
            rec.refused = True
            return rec
        rec.submit, rec.rid = clock(), req.id
        self.recs[req.id] = rec
        return rec

    def _progress(self, rid: int, tokens: List[int]) -> None:
        now = clock()
        if self.rt.stats["chunks"] != self._chunk_seen:
            self._chunk_seen = self.rt.stats["chunks"]
            self._sample_chunk(now)
        if self._step_first is None:
            self._step_first = now
        rec = self.recs.get(rid)
        n = 1 + len(tokens)
        if rec is None or n <= rec.delivered:
            return
        if rec.first is None:
            rec.first = now
        self.deliveries.append((now, n - rec.delivered))
        rec.delivered = n
        if n >= rec.n_new:
            rec.last = now

    def _sample_chunk(self, now: float) -> None:
        """At a chunk boundary (the first delivery after a chunk): each
        busy row's length before the chunk, and the pages holding valid
        tokens of busy rows against the pages held."""
        pool = next(iter(self.rt.pools.values()))
        ps, steps = pool.page_size, self.rt.chunk
        lens, valid = [], set()
        for row, act in enumerate(pool.slots):
            if act is not None:
                n = pool.row_len[row]
                lens.append(n - steps)
                valid.update(pool.row_pages[row][:-(-n // ps)])
        held = pool.n_pages - len(pool.alloc.free)
        self.chunks.append((now, lens, len(valid), held))

    @property
    def busy(self) -> bool:
        return bool(self.rt.queue) or not self.rt.idle

    def step(self) -> None:
        t0, admitted = clock(), self.rt.stats["admitted"]
        self._step_first = None
        for c in self.rt.step():
            rec = self.recs.get(c.request_id)
            if rec is not None:
                rec.tokens = np.asarray(c.tokens)
        t1 = clock()
        first = t1 if self._step_first is None else self._step_first
        self.steps.append((t0, first - t0, t1 - first,
                           self.rt.stats["admitted"] - admitted))

    def slow_steps(self, since: float, until: float, n: int = 3) -> str:
        """The median step in ``[since, until)`` and the ``n`` longest:
        seconds to the first delivery (admission and the chunk), seconds
        after it, requests admitted."""
        steps = [s for s in self.steps if since <= s[0] < until]
        if not steps:
            return "steps: none"
        med = float(np.median([a + b for _, a, b, _ in steps]))
        top = sorted(steps, key=lambda s: -(s[1] + s[2]))[:n]
        between = max((b[0] - (a[0] + a[1] + a[2])
                       for a, b in zip(steps, steps[1:])), default=0.0)
        return (f"steps: {len(steps)}, median {1e3 * med:.1f} ms, longest "
                f"gap between steps {1e3 * between:.1f} ms; longest "
                + ", ".join(f"{1e3 * (a + b):.1f} ms at {t - since:.1f} s "
                            f"({1e3 * a:.1f} to delivery + {1e3 * b:.1f}, "
                            f"{k} admitted)" for t, a, b, k in top))

    def serve(self, reqs: Iterable[LMRequest]) -> List[Rec]:
        """Submit all, step until every one has finished (set-up only)."""
        recs = [self.submit(r, clock()) for r in reqs]
        while any(not r.done for r in recs if not r.refused):
            self.step()
        return recs

    # -- after the window ----------------------------------------------------

    def release_program(self) -> None:
        """Drop the program's state (pool, prefix cache, session), keeping
        the benchmark's weights for the reference."""
        self.rt = None
        gc.collect()


def warm_requests(shapes: Dict[str, List[int]], prefix: Optional[np.ndarray],
                  vocab: int, n_new: int, rng: np.random.Generator
                  ) -> List[LMRequest]:
    """One request per length the traffic can compile for: misses at each
    whole-prompt length, partial hits at each suffix length after
    ``prefix`` (already in the prefix cache)."""
    out = [LMRequest(-1, 0.0, rng.integers(0, vocab, n, dtype=np.int32),
                     n_new, False, "warm") for n in shapes["prompt"]]
    if prefix is not None:
        out += [LMRequest(-1, 0.0, np.concatenate(
            [prefix, rng.integers(0, vocab, n, dtype=np.int32)]),
            n_new, False, "warm") for n in shapes["suffix"]]
    return out


def check_served(server: Server, recs: List[Rec], check: Dict[str, Any],
                 seed: int, shape: tuple, precision: str = "float32"
                 ) -> harness.Check:
    """The widest gap by which a served token's reference logit lies below
    the reference's best, over a sample of the window's finished requests
    drawn from the seed, the longest among them.  Under ``precision="fp8"``
    (the control) the token read at each position is the one the fp8
    reference puts first.  ``shape``: (the traffic's longest prompt plus
    its most new tokens, its most new tokens), so one reference program
    serves every request."""
    done = [r for r in recs if r.in_window and r.done]
    if not done:
        return harness.Check("widest_logit_gap", float("inf"),
                             check["limit"])
    top = max(done, key=lambda r: (len(r.prompt) + r.n_new, -r.index))
    rest = [r for r in done if r is not top]
    pick = harness.rng(seed, 9).permutation(len(rest))
    sample = [top] + [rest[i] for i in pick[:check["sample"] - 1]]
    widest = 0.0
    for r in sample:
        g = lm_dense.served_gaps(server.weights, server.m, r.prompt,
                                 r.tokens, *shape, precision)
        widest = max(widest, float(np.max(g)))
    harness.log(f"check: {len(sample)} requests, "
                f"{sum(r.n_new for r in sample)} served tokens")
    return harness.Check("widest_logit_gap", widest, check["limit"])
