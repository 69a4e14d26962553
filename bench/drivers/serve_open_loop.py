"""Open-loop serving: requests arrive on a seeded Poisson schedule, whether
or not the server keeps up, and each is timed from when it was due.

Set-up: weights from the seed, the runtime over the paged pool, the
system prompts registered in the prefix cache, one request at every length
the traffic can compile for, then a ramp of the same traffic before the
window opens.  The window lasts ``--seconds``; the drain after it keeps
the arrivals going until every request due in the window has finished, or
``drain_s`` has passed (those unfinished count as failed).
"""
from __future__ import annotations

import shutil
import tempfile
import time

import numpy as np

from bench import harness, traffic
from bench.lm_serving import Server, check_served, clock, warm_requests
from bench.trace_reduce import Profile


def run(cell, args, t_start, devices) -> harness.RunRecord:
    w = cell.workload
    p = w["traffic"]
    vocab = cell.config["model"]["vocab_size"]
    srv = Server(cell.config, args.seed, trace=bool(args.trace))
    prompts, stream = traffic.open_loop_schedule(
        p, args.seconds, vocab, args.seed, harness.rng)
    srv.serve(traffic.LMRequest(-1, 0.0, sp, 1, False, "system")
              for sp in prompts)
    srv.serve(warm_requests(traffic.open_loop_shapes(p), prompts[0], vocab,
                            srv.rt.chunk + 1, harness.rng(args.seed, 4)))
    compiles = harness.CompileCounter()
    gcw = harness.GcWatch()

    prof = Profile(tempfile.mkdtemp(prefix="bench-trace-"), clock) \
        if args.trace else None
    t_open = clock() + p["ramp_s"]
    t_close = t_open + args.seconds
    t_trace = t_close - min(w["trace_s"], args.seconds)
    drain_end = t_close + w["drain_s"]
    recs, nxt = [], next(stream)
    stats = {}
    opened = closed = False
    while True:
        now = clock()
        while nxt.due + t_open <= now:
            recs.append(srv.submit(nxt, nxt.due + t_open))
            nxt = next(stream)
        if not opened and now >= t_open:
            opened = True
            compiles.start()
            gcw.start()
            stats["open"] = srv.rt.stats_snapshot()
        if prof is not None and prof.t_open is None and now >= t_trace:
            prof.start()
        if not closed and now >= t_close:
            closed = True
            compiles.stop()
            gcw.stop()
            if prof is not None:
                prof.stop()
            stats["close"] = srv.rt.stats_snapshot()
        if closed and (all(r.done or r.refused for r in recs if r.in_window)
                       or now >= drain_end):
            break
        if srv.busy:
            srv.step()
        else:
            time.sleep(min(max(nxt.due + t_open - clock(), 0.0), 0.005))
    t_end = clock()

    window = [r for r in recs if r.in_window]
    late = [r.submit - r.due for r in window if r.submit is not None]
    print(f"generator: {len(window)} requests due in the window, lateness "
          f"p50 {1e3 * float(np.median(late)):.3f} ms, max "
          f"{1e3 * max(late):.3f} ms; compilations in the window: "
          f"{compiles.count} {sorted(set(compiles.names))}", flush=True)
    ttft = [(r.first or t_end) - r.due for r in window]
    tpot = [(r.last - r.first) / (r.n_new - 1) for r in window if r.done]
    print(f"window: ttft p50 {1e3 * np.median(ttft):.1f} ms, mean "
          f"{1e3 * np.mean(ttft):.1f} ms; tpot p50 "
          f"{1e3 * np.median(tpot):.1f} ms, mean {1e3 * np.mean(tpot):.1f} "
          f"ms; {len(tpot)} of {len(window)} finished", flush=True)
    print(srv.slow_steps(t_open, t_close), gcw.summary(), flush=True)
    device = harness.device_info(devices)
    spans = srv.tracer.spans if srv.tracer is not None else []
    trace = None
    if prof is not None:
        trace = prof.reduce([(s.name, s.start, s.end) for s in spans
                             if s.name in ("prefill", "admit", "decode_chunk",
                                           "cow_split")])
        shutil.rmtree(prof.log_dir, ignore_errors=True)
    srv.release_program()
    shape = (max(traffic.open_loop_shapes(p)["prompt"]) + max(p["n_new"]),
             max(p["n_new"]))
    check = check_served(srv, recs, w["check"], args.seed, shape)
    failed = sum(1 for r in window if not r.done)
    return harness.RunRecord(
        cell=cell, setup_s=t_open - t_start, window_s=args.seconds,
        attempted=len(window), failed=failed, checks=[check], device=device,
        trace=trace, recheck=lambda precision: check_served(
            srv, recs, w["check"], args.seed, shape, precision),
        data={"recs": recs, "t_open": t_open, "t_close": t_close,
              "t_end": t_end, "stats": stats, "spans": spans,
              "trace_window": (prof.t_open, prof.t_close) if prof else None,
              "model": cell.config["model"], "compiles": compiles.count})
