"""Offline batch generation: a backlog of unshared prompts that always
holds more requests than the pool has rows, so every row decodes all the
time and admission is a miss prefill whenever a row frees.

Set-up: weights from the seed, the runtime over the paged pool, one miss
at every prompt length, then the backlog until ``turnover`` requests have
finished (the rows have started to turn over).  The window lasts
``--seconds``; a request counts in it when its last token is delivered
inside it.
"""
from __future__ import annotations

import shutil
import tempfile

from bench import harness, traffic
from bench.lm_serving import Server, check_served, clock, warm_requests
from bench.trace_reduce import Profile


def run(cell, args, t_start, devices) -> harness.RunRecord:
    w = cell.workload
    p = w["traffic"]
    vocab = cell.config["model"]["vocab_size"]
    rows = cell.config["deployment"]["n_rows"]
    srv = Server(cell.config, args.seed, trace=bool(args.trace))
    srv.serve(warm_requests(traffic.backlog_shapes(p), None, vocab,
                            srv.rt.chunk + 1, harness.rng(args.seed, 4)))
    stream = traffic.backlog_stream(p, vocab, args.seed, harness.rng)
    compiles = harness.CompileCounter()
    prof = Profile(tempfile.mkdtemp(prefix="bench-trace-"), clock) \
        if args.trace else None
    recs = []

    def top_up():
        while len(srv.rt.queue) < p["queued"] * rows:
            recs.append(srv.submit(next(stream), clock()))

    def finished():
        return sum(1 for r in recs if r.done)

    top_up()
    while finished() < p["turnover"]:
        srv.step()
        top_up()
    t_open = clock()
    t_close = t_open + args.seconds
    t_trace = t_close - min(w["trace_s"], args.seconds)
    compiles.start()
    gcw = harness.GcWatch()
    gcw.start()
    n_chunks = len(srv.chunks)
    while clock() < t_close:
        if prof is not None and prof.t_open is None and clock() >= t_trace:
            prof.start()
        srv.step()
        top_up()
    if prof is not None:
        prof.stop()
    compiles.stop()
    gcw.stop()
    t_end = clock()
    for r in recs:
        r.in_window = r.done and t_open <= r.last < t_close
    window = [r for r in recs if r.in_window]
    print(f"backlog: {len(recs)} submitted, {len(window)} finished in the "
          f"window, {len(srv.chunks) - n_chunks} chunks; compilations in "
          f"the window: {compiles.count} {sorted(set(compiles.names))}",
          flush=True)
    print(srv.slow_steps(t_open, t_close), gcw.summary(), flush=True)
    device = harness.device_info(devices)
    spans = srv.tracer.spans if srv.tracer is not None else []
    trace = None
    if prof is not None:
        trace = prof.reduce([(s.name, s.start, s.end) for s in spans
                             if s.name in ("prefill", "admit",
                                           "decode_chunk")])
        shutil.rmtree(prof.log_dir, ignore_errors=True)
    steps = srv.rt.chunk
    srv.release_program()
    shape = (max(p["prompt_lens"]) + max(p["n_new"]), max(p["n_new"]))
    check = check_served(srv, recs, w["check"], args.seed, shape)
    return harness.RunRecord(
        cell=cell, setup_s=t_open - t_start, window_s=args.seconds,
        attempted=len(window), failed=0, checks=[check], device=device,
        trace=trace, recheck=lambda precision: check_served(
            srv, recs, w["check"], args.seed, shape, precision),
        data={"recs": recs, "t_open": t_open, "t_close": t_close,
              "t_end": t_end, "spans": spans,
              "deliveries": srv.deliveries, "chunks": srv.chunks,
              "chunk_steps": steps,
              "trace_window": (prof.t_open, prof.t_close) if prof else None,
              "model": cell.config["model"], "compiles": compiles.count})
