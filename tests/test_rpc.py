"""RPC tier: framed wire protocol (codec payloads, typed faults), the
in-process WorkerServer protocol contract, and RpcWorker subprocess workers
under the fleet router — placement, kill-mid-decode failover, readmission,
and wire-sabotage retry, all token-exact and exactly-once."""
import socket
import struct
import threading
import time
import zlib

import numpy as np
import pytest

from repro.rpc import (FRAME_OVERHEAD, PROTOCOL_VERSION, FrameError,
                       RpcWorker, WireClosed, WireTimeout, pack_tensor,
                       recv_message, send_message, unpack_tensor)
from repro.rpc.wire import (MAGIC, _FRAME, CompletionMsg, Heartbeat, Hello,
                            HelloAck, Message, SubmitRequest, TokenChunk)
from repro.transport.codecs import CodecSpec, get_codec, list_codecs
from repro.serving.queue import Request


def _rand(shape, seed=0, dtype=np.float32):
    return np.random.RandomState(seed).randn(*shape).astype(dtype)


@pytest.fixture(scope="module", autouse=True)
def _pristine_codec_registry():
    """The in-process WorkerServer rig answers ``Calibrate`` by running
    ``calibrate_codec_bws`` *in this process*, which shadows the modeled
    ``decode_bw`` constants on the shared codec registry instances —
    restore them so later test modules sweep against the documented
    constants (subprocess workers calibrate in their own process and
    never touch this one)."""
    saved = {n: dict(get_codec(n).__dict__) for n in list_codecs()}
    yield
    for n, state in saved.items():
        codec = get_codec(n)
        codec.__dict__.clear()
        codec.__dict__.update(state)


# ---------------------------------------------------------------------------
# tensor packing through the codec registry
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name,spec", [
    ("identity", CodecSpec()),
    ("int8", CodecSpec()),
    ("int8", CodecSpec(param=8)),
    ("int4", CodecSpec()),
    ("topk", CodecSpec(param=4)),
    ("segment_means", CodecSpec(L=4)),
])
@pytest.mark.parametrize("shape", [(2, 8, 32), (1, 4, 4, 16)])
def test_pack_tensor_wire_bytes_and_bit_exact(name, spec, shape):
    """The packed blob is exactly ``wire_bytes`` long and unpacking is
    bit-exact with a local decode of the same encoded payload."""
    x = _rand(shape, seed=hash(name) % 100)
    codec = get_codec(name)
    meta, blob = pack_tensor(x, name, spec)
    assert len(blob) == codec.wire_bytes(x.shape, x.dtype, spec)
    local = np.asarray(codec.decode(codec.encode(x, spec), spec,
                                    shape=x.shape, dtype=x.dtype))
    np.testing.assert_array_equal(unpack_tensor(meta, blob), local)


def test_pack_tensor_int_identity_roundtrip():
    x = np.arange(-5, 11, dtype=np.int32).reshape(4, 4)
    meta, blob = pack_tensor(x, "identity")
    np.testing.assert_array_equal(unpack_tensor(meta, blob), x)


def test_unpack_truncated_payload_is_frame_error():
    meta, blob = pack_tensor(_rand((2, 8, 32)), "int8")
    with pytest.raises(FrameError):
        unpack_tensor(meta, blob[:-1])
    with pytest.raises(FrameError):
        unpack_tensor(meta, blob + b"\x00")


# ---------------------------------------------------------------------------
# framing across a real socket
# ---------------------------------------------------------------------------

@pytest.fixture()
def pair():
    a, b = socket.socketpair()
    a.settimeout(5.0)
    b.settimeout(5.0)
    yield a, b
    a.close()
    b.close()


@pytest.mark.parametrize("name", sorted(list_codecs()))
def test_framed_codec_roundtrip_over_socket(pair, name):
    """Every registered codec: framed encode → send → recv → decode is
    bit-exact, and bytes-on-wire equals FRAME_OVERHEAD + header +
    ``codec.wire_bytes`` — the exact quantity the policy table sweeps."""
    a, b = pair
    spec = CodecSpec(L=4, param=4)
    x = _rand((2, 8, 32), seed=7)
    msg = SubmitRequest(request_id=9, n_new=3, seed=1, codec=name,
                        codec_l=spec.L, codec_param=spec.param, prompt=x)
    sent = send_message(a, msg)
    got, read = recv_message(b)
    assert sent == read
    # the frame's payload length IS the codec's wire accounting — parse it
    # out of the bytes that actually crossed the socket
    codec = get_codec(name)
    head = msg.encode_frame()[:FRAME_OVERHEAD]
    _, _, _, hlen, plen, _ = _FRAME.unpack(head)
    assert plen == codec.wire_bytes(x.shape, x.dtype, spec)
    assert sent == FRAME_OVERHEAD + hlen + plen
    local = np.asarray(codec.decode(codec.encode(x, spec), spec,
                                    shape=x.shape, dtype=x.dtype))
    np.testing.assert_array_equal(np.asarray(got.prompt), local)
    assert (got.request_id, got.n_new, got.codec) == (9, 3, name)


def test_scalar_only_message_roundtrip(pair):
    a, b = pair
    send_message(a, Heartbeat(seq=3, t=1.5, pong=True,
                              stats={"served": 2, "tok": 5}))
    got, _ = recv_message(b)
    assert isinstance(got, Heartbeat) and got.pong
    assert got.stats == {"served": 2, "tok": 5}


def test_truncated_frame_is_typed_wire_closed(pair):
    a, b = pair
    frame = Heartbeat(seq=1).encode_frame()
    a.sendall(frame[: len(frame) // 2])
    a.close()
    with pytest.raises(WireClosed, match="mid-frame"):
        recv_message(b)


def test_clean_close_at_boundary_is_wire_closed(pair):
    a, b = pair
    a.close()
    with pytest.raises(WireClosed, match="closed the connection"):
        recv_message(b)


def test_recv_timeout_is_wire_timeout(pair):
    _, b = pair
    with pytest.raises(WireTimeout):
        recv_message(b, timeout=0.05)


def test_corrupt_crc_is_frame_error(pair):
    a, b = pair
    frame = bytearray(Heartbeat(seq=1).encode_frame())
    frame[-1] ^= 0xFF                      # flip a payload/header byte
    a.sendall(bytes(frame))
    with pytest.raises(FrameError, match="CRC"):
        recv_message(b)


def test_bad_magic_is_frame_error(pair):
    a, b = pair
    frame = b"XX" + Heartbeat(seq=1).encode_frame()[2:]
    a.sendall(frame)
    with pytest.raises(FrameError, match="magic"):
        recv_message(b)


def test_newer_protocol_version_rejected(pair):
    """Versioning rule: accept <= PROTOCOL_VERSION, reject newer frames."""
    a, b = pair
    frame = bytearray(Heartbeat(seq=1).encode_frame())
    struct.pack_into(">H", frame, 2, PROTOCOL_VERSION + 1)
    a.sendall(bytes(frame))
    with pytest.raises(FrameError, match="protocol"):
        recv_message(b)


def test_implausible_lengths_rejected(pair):
    a, b = pair
    head = _FRAME.pack(MAGIC, PROTOCOL_VERSION, Heartbeat.KIND,
                       1 << 30, 0, 0)
    a.sendall(head)
    with pytest.raises(FrameError, match="implausible"):
        recv_message(b)


def test_unknown_header_fields_ignored():
    """Forward compatibility: a newer peer may add header fields; this
    build must decode the frame and drop what it doesn't know."""
    import json
    header = json.dumps({"f": {"seq": 4, "from_the_future": True},
                         "t": []}).encode()
    got = Message.decode_frame(Heartbeat.KIND, header, b"")
    assert isinstance(got, Heartbeat) and got.seq == 4
    with pytest.raises(FrameError, match="unknown message kind"):
        Message.decode_frame(250, header, b"")


def test_all_typed_errors_are_retryable_transport_errors():
    from repro.transport.links import TransportError
    for cls in (WireTimeout, WireClosed, FrameError):
        e = cls("boom", worker="w")
        assert isinstance(e, TransportError) and e.retryable
        assert e.stage.startswith("rpc-")


# ---------------------------------------------------------------------------
# WorkerServer protocol contract (in-process, over a socketpair)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def server_rig():
    """One WorkerServer on a thread + a raw client socket, plus a local
    reference session with identical parameters (the token-exact oracle)."""
    from repro.rpc.worker import WorkerServer, build_session
    session, hardware, link = build_session("llama3.2-1b", vocab=64, seed=0)
    session.profile(backend="simulated", hardware=hardware, link=link)
    server = WorkerServer(session, name="inproc", arch="llama3.2-1b",
                          n_slots=2, chunk=3, max_len=24,
                          hardware=hardware, link=link)
    client, conn = socket.socketpair()
    client.settimeout(30.0)
    t = threading.Thread(target=server.serve_conn, args=(conn,), daemon=True)
    t.start()
    yield client, server, session
    server._shutdown = True
    client.close()
    conn.close()
    t.join(timeout=5.0)


def _ask(client, msg, want, deadline_s=60.0):
    """Send and pump until a `want` arrives; returns (reply, others)."""
    send_message(client, msg)
    others = []
    end = time.monotonic() + deadline_s
    while time.monotonic() < end:
        got, _ = recv_message(client, timeout=deadline_s)
        if isinstance(got, want):
            return got, others
        others.append(got)
    raise AssertionError(f"no {want.__name__} within {deadline_s}s")


def test_server_hello_describes_runtime(server_rig):
    client, server, _ = server_rig
    ack, _ = _ask(client, Hello(name="t"), HelloAck)
    assert (ack.n_slots, ack.chunk, ack.max_len) == (2, 3, 24)
    assert ack.arch == "llama3.2-1b"


def test_server_serves_token_exact_and_streams(server_rig):
    client, server, session = server_rig
    prompt = np.arange(1, 6, dtype=np.int32)
    sub = SubmitRequest(request_id=42, n_new=6, seed=11, prompt=prompt)
    done, others = _ask(client, sub, CompletionMsg)
    assert done.request_id == 42
    want = np.asarray(session.generate(prompt[None], 6, seed=11)[0])
    np.testing.assert_array_equal(np.asarray(done.tokens), want)
    # decode progress streamed as TokenChunk frames covering tokens 1..n-1
    chunks = [m for m in others if isinstance(m, TokenChunk)]
    assert chunks and chunks[0].start == 1
    streamed = np.concatenate([np.asarray(c.tokens) for c in chunks])
    np.testing.assert_array_equal(streamed, want[1:1 + len(streamed)])


def test_server_dedups_duplicate_submit(server_rig):
    """Exactly-once: re-submitting a finished id re-sends the cached
    completion (same tokens) instead of decoding twice."""
    client, server, _ = server_rig
    before = server.stats["submits"]
    sub = SubmitRequest(request_id=42, n_new=6, seed=11,
                        prompt=np.arange(1, 6, dtype=np.int32))
    done, _ = _ask(client, sub, CompletionMsg)
    assert done.request_id == 42
    assert server.stats["submits"] == before       # not admitted again
    assert server.stats["dup_submits"] >= 1


def test_server_heartbeat_pong_carries_stats(server_rig):
    client, _, _ = server_rig
    pong, _ = _ask(client, Heartbeat(seq=77, t=1.0), Heartbeat)
    assert pong.pong and pong.seq == 77
    assert pong.stats["completed"] >= 1 and "pid" in pong.stats
    assert pong.stats["submits"] >= 1


def test_server_calibrate_is_measured(server_rig):
    from repro.rpc.wire import Calibrate, CalibrateResult
    client, server, _ = server_rig
    res, _ = _ask(client, Calibrate(shape=(2, 16, 64), iters=1, warmup=0),
                  CalibrateResult, deadline_s=300.0)
    assert res.measured
    want = {n for n in list_codecs()
            if type(get_codec(n)).decode_bw > 0
            and not get_codec(n).summarizing}
    assert set(res.bws) == want and want
    assert all(v > 0 for v in res.bws.values())
    assert server.stats["calibrations"] >= 1


def test_server_drops_conn_on_garbage(server_rig):
    """Stream desync is unrecoverable: the server must close rather than
    guess at framing (the client reconnects and re-submits)."""
    client, server, _ = server_rig
    errs = server.stats["frame_errors"]
    client.sendall(b"ZZ" + bytes(FRAME_OVERHEAD))
    deadline = time.monotonic() + 10.0
    while time.monotonic() < deadline and server.stats["frame_errors"] == errs:
        time.sleep(0.02)
    assert server.stats["frame_errors"] == errs + 1


# ---------------------------------------------------------------------------
# RpcWorker subprocess fleet: placement, failover, readmission
# (ordered tests sharing one spawned fleet — subprocesses are expensive)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def rpc_fleet():
    from repro.fleet import DeviceRegistry, FleetRouter
    from repro.rpc.worker import build_session
    from repro.runtime.fault import RetryPolicy
    reg = DeviceRegistry(heartbeat_timeout_s=30.0)
    # liveness timer is NOT what these tests exercise (kill discovery is
    # via failed reconnects) — keep it far above any CPU-starved JIT
    # compile so loaded machines can't false-positive both workers dead
    kw = dict(vocab=64, seed=0, n_slots=2, chunk=3, max_len=24,
              heartbeat_timeout_s=300.0,
              retry=RetryPolicy(max_retries=3, backoff_base_s=0.02))
    w1 = RpcWorker("w1", **kw)
    w2 = RpcWorker("w2", **kw)
    reg.add(w1)
    reg.add(w2)
    router = FleetRouter(reg, retry=RetryPolicy(max_retries=3))
    ref, _, _ = build_session("llama3.2-1b", vocab=64, seed=0)
    yield dict(reg=reg, router=router, w1=w1, w2=w2, ref=ref)
    w1.close()
    w2.close()


def _oracle(ref, req):
    return np.asarray(ref.generate(np.asarray(req.prompt)[None],
                                   req.n_new, seed=req.seed)[0])


def test_rpc_fleet_calibration_is_measured(rpc_fleet):
    """DeviceRegistry.add routes calibration through the worker process —
    provenance says measured, and the numbers exist for every lossy codec."""
    want = {n for n in list_codecs()
            if type(get_codec(n)).decode_bw > 0
            and not get_codec(n).summarizing}
    for w in (rpc_fleet["w1"], rpc_fleet["w2"]):
        assert w.codec_bws_measured
        assert set(w.codec_bws) == want and want
        assert w.policy is not None          # profiled over the wire


def test_rpc_fleet_placement_token_exact(rpc_fleet):
    router, ref = rpc_fleet["router"], rpc_fleet["ref"]
    reqs = [Request(prompt=np.arange(1, 5 + i, dtype=np.int32), n_new=6,
                    seed=100 + i) for i in range(4)]
    for r in reqs:
        router.route(r)
    done = router.run()
    assert sorted(c.request_id for c in done) == sorted(r.id for r in reqs)
    by_id = {c.request_id: c for c in done}
    for r in reqs:
        np.testing.assert_array_equal(np.asarray(by_id[r.id].tokens),
                                      _oracle(ref, r))
    assert router.stats["lost"] == 0


class _OneShotChaos:
    """Minimal ChaosController stand-in: arm one dispatch fault."""

    def __init__(self, kind):
        from repro.chaos.schedule import ChaosEvent
        self._armed = [ChaosEvent(t=0.0, kind=kind, target="?")]

    def dispatch_fault(self, worker, now):
        return self._armed.pop(0) if self._armed else None


def test_rpc_truncated_frame_retried_not_dropped(rpc_fleet):
    """Wire sabotage (half a frame + hard close) surfaces as a typed
    TransportError, backs off, reconnects, re-submits — never loses the
    request."""
    router, w2, ref = rpc_fleet["router"], rpc_fleet["w2"], rpc_fleet["ref"]
    errs0 = w2.stats["transport_errors"]
    reconn0 = w2.stats["reconnects"]
    req = Request(prompt=np.arange(1, 7, dtype=np.int32), n_new=5, seed=400)
    w2.chaos = _OneShotChaos("error")        # armed: next step sabotages
    router.route(req, pin="w2")
    done = router.run()
    w2.chaos = None
    assert [c.request_id for c in done] == [req.id]
    np.testing.assert_array_equal(np.asarray(done[0].tokens),
                                  _oracle(ref, req))
    assert w2.stats["transport_errors"] == errs0 + 1
    assert w2.stats["reconnects"] == reconn0 + 1   # capped-backoff retry
    assert w2.healthy and w2.stats["retries"] >= 1
    assert router.stats["lost"] == 0


def test_rpc_kill_mid_decode_fails_over_token_exact(rpc_fleet):
    """The tentpole scenario against a real process: SIGKILL w1 with work
    in flight → its breaker opens on genuine reconnect failures → the
    router drains the wire mirror and re-routes EDF to w2 — exactly once,
    token-exact."""
    reg, router = rpc_fleet["reg"], rpc_fleet["router"]
    w1, ref = rpc_fleet["w1"], rpc_fleet["ref"]
    reqs = [Request(prompt=np.arange(1, 6, dtype=np.int32), n_new=8,
                    seed=200 + i) for i in range(3)]
    for r in reqs:
        router.route(r, pin="w1")
    router.step()                            # at least one lands in-flight
    w1.kill_process()                        # real SIGKILL, state is gone
    done = router.run()
    assert sorted(c.request_id for c in done) == sorted(r.id for r in reqs)
    assert all(c.worker == "w2" for c in done)
    by_id = {c.request_id: c for c in done}
    for r in reqs:
        np.testing.assert_array_equal(np.asarray(by_id[r.id].tokens),
                                      _oracle(ref, r))
    assert router.breaker("w1").opened_total >= 1
    assert not w1.healthy and not reg.is_alive("w1")
    assert router.stats["lost"] == 0 and router.stats["rerouted"] >= len(reqs)


def test_rpc_readmit_respawns_process(rpc_fleet):
    """Re-admission after a real process death: fresh subprocess, fresh
    socket, re-measured calibration, placeable and token-exact again."""
    reg, router = rpc_fleet["reg"], rpc_fleet["router"]
    w1, ref = rpc_fleet["w1"], rpc_fleet["ref"]
    old_pid = w1.proc.pid
    router.readmit("w1")
    assert w1.healthy and reg.is_alive("w1")
    assert w1.proc.pid != old_pid and w1.proc.poll() is None
    assert w1.codec_bws_measured
    req = Request(prompt=np.arange(1, 4, dtype=np.int32), n_new=5, seed=300)
    router.route(req, pin="w1")
    done = router.run()
    assert [c.request_id for c in done] == [req.id]
    assert done[0].worker == "w1"
    np.testing.assert_array_equal(np.asarray(done[0].tokens),
                                  _oracle(ref, req))


def test_workers_refuse_to_share_an_accelerator(monkeypatch):
    """On an accelerator host every spawned worker would open the one chip:
    spawning fails at once, before any process starts, unless the workers
    are pinned to the CPU."""
    import jax
    from repro.rpc import client
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(client.subprocess, "Popen", None)   # never reached
    with pytest.raises(RuntimeError, match="contend for one chip"):
        client.require_cpu_children({"JAX_PLATFORMS": ""})
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    with pytest.raises(RuntimeError, match="contend for one chip"):
        RpcWorker("w-tpu", spawn=True)
    client.require_cpu_children({"JAX_PLATFORMS": "cpu"})
