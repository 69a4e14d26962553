"""Seeded traffic: same seed, same schedule; same work for every seed; and
every length drawn is one that set-up warms."""
import itertools

import numpy as np

from bench import harness, traffic

BIG_SEED = 2**31 + 987_654


def shared_prefix():
    return harness.load_json(harness.BENCH / "workloads" /
                             "internlm2-1.8b.shared-prefix.json")["traffic"]


def schedule(p, seed, n=200, seconds=51.0, vocab=92544):
    prompts, stream = traffic.open_loop_schedule(p, seconds, vocab, seed,
                                                 harness.rng)
    return prompts, list(itertools.islice(stream, n))


def test_same_seed_same_schedule():
    p = shared_prefix()
    pa, a = schedule(p, BIG_SEED)
    pb, b = schedule(p, BIG_SEED)
    assert all(np.array_equal(x, y) for x, y in zip(pa, pb))
    for x, y in zip(a, b):
        assert (x.due, x.n_new, x.in_window, x.tag) == \
            (y.due, y.n_new, y.in_window, y.tag)
        assert np.array_equal(x.prompt, y.prompt)
    _, c = schedule(p, BIG_SEED + 1)
    assert any(not np.array_equal(x.prompt, y.prompt) for x, y in zip(a, c))


def test_every_seed_gets_the_same_work_in_the_window():
    p = shared_prefix()
    seconds = 51.0
    windows = []
    for seed in (1, BIG_SEED, 2**40 + 3):
        _, reqs = schedule(p, seed, seconds=seconds)
        w = [r for r in reqs if r.in_window]
        assert len(w) == round(p["rate"] * seconds)
        assert all(0.0 <= r.due < seconds for r in w)
        windows.append((sorted(len(r.prompt) for r in w),
                        sorted(r.n_new for r in w),
                        sorted(r.tag for r in w)))
    assert windows[0] == windows[1] == windows[2]


def test_ramp_precedes_the_window():
    p = shared_prefix()
    _, reqs = schedule(p, BIG_SEED)
    ramp = [r for r in reqs if r.due < 0]
    assert ramp and all(not r.in_window and r.due >= -p["ramp_s"]
                        for r in ramp)
    dues = [r.due for r in reqs]
    assert dues == sorted(dues)


def test_drawn_lengths_are_warmed():
    p = shared_prefix()
    shapes = traffic.open_loop_shapes(p)
    prompts, reqs = schedule(p, BIG_SEED, n=400)
    sys_len = p["system_prompt_len"]
    for r in reqs:
        assert len(r.prompt) in shapes["prompt"]            # a miss
        assert len(r.prompt) - sys_len in shapes["suffix"]  # a partial hit
        assert r.n_new in p["n_new"]
        assert any(np.array_equal(r.prompt[:sys_len], s) for s in prompts)


def test_backlog_is_seeded_apportioned_and_warmed():
    p = harness.load_json(harness.BENCH / "workloads" /
                          "internlm2-1.8b.decode-backlog.json")["traffic"]

    def take(seed):
        return list(itertools.islice(
            traffic.backlog_stream(p, 92544, seed, harness.rng),
            2 * p["block"]))
    a, b = take(BIG_SEED), take(BIG_SEED)
    assert [(len(x.prompt), x.n_new) for x in a] == \
        [(len(y.prompt), y.n_new) for y in b]
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    warmed = set(traffic.backlog_shapes(p)["prompt"])
    assert {len(x.prompt) for x in a} <= warmed
    blk = a[:p["block"]]
    assert sorted(len(x.prompt) for x in blk) == sorted(
        v for v, c in zip(p["prompt_lens"],
                          traffic.apportion(p["prompt_weights"], p["block"]))
        for _ in range(c))
    assert {x.n_new for x in a} <= set(p["n_new"])


def test_batch_sizes_come_in_apportioned_blocks():
    p = harness.load_json(harness.BENCH / "workloads" /
                          "vit-base-16.adaptive-4chip.json")["traffic"]
    a = list(itertools.islice(traffic.batch_sizes(p, BIG_SEED, harness.rng),
                              2 * p["block"]))
    b = list(itertools.islice(traffic.batch_sizes(p, BIG_SEED, harness.rng),
                              2 * p["block"]))
    assert a == b
    per = p["block"] // len(p["batch_sizes"])
    for blk in (a[:p["block"]], a[p["block"]:]):
        assert sorted(blk) == sorted(p["batch_sizes"] * per)


def test_apportion_and_gaps():
    assert traffic.apportion([0.4, 0.3, 0.2, 0.1], 7) == [3, 2, 1, 1]
    g = traffic.exp_gaps(40, 20.0, harness.rng(3))
    assert abs(g.sum() - 20.0) < 1e-9 and (g > 0).all()


def test_a_schedule_seed_fixes_the_order_and_not_the_tokens():
    """With ``schedule_seed`` every run serves the same requests at the same
    times, and only the token contents follow the run's seed."""
    p = dict(shared_prefix(), schedule_seed=5)
    _, a = schedule(p, BIG_SEED)
    _, b = schedule(p, 7)
    assert [(r.due, r.n_new, r.tag, len(r.prompt)) for r in a] == \
        [(r.due, r.n_new, r.tag, len(r.prompt)) for r in b]
    assert all(not np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    _, c = schedule(dict(p, schedule_seed=6), BIG_SEED)
    assert [r.tag for r in a] != [r.tag for r in c]
