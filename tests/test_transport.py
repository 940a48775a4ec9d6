"""Wire-transport tests: codec round-trips and rejection, fail-fast reply
contracts, loopback gateway semantics, and the in-proc <-> socket parity
the disaggregated deployment rests on.

The parity test is the load-bearing one: a socket-transport rollout with
the same (num_actors, envs_per_actor, seed) must be BIT-identical to the
in-process backend, because the transport replaces only the request/reply
plumbing — batching, recurrent slots, env seeding all stay server-side.
"""

import io
import queue
import threading
import time

import numpy as np
import pytest

from repro.core.actor import Actor
from repro.core.inference import InferenceServer, ReplyError
from repro.envs.catch import CatchEnv
from repro.launch.actor_host import ActorHostPool
from repro.transport import codec
from repro.transport.socket import InferenceGateway, SyncSocketTransport


def det_policy(obs, ids):
    """Deterministic and slot-order independent, so batching/arrival order
    (which legitimately differs across transports) cannot change actions."""
    flat = np.abs(obs.reshape(obs.shape[0], -1))
    return (flat.sum(axis=1) * 997.0).astype(np.int64) % CatchEnv.num_actions


# ------------------------------------------------------------------ codec

@pytest.mark.parametrize("dtype,shape", [
    (np.uint8, (4, 84, 84)),        # Atari-style frame lanes
    (np.float32, (8, 50)),          # vectorized obs
    (np.float64, (3,)),
    (np.int32, ()),                 # scalar action
    (np.bool_, (2, 5)),
    (np.float32, (0,)),             # zero-length lane batch
    (np.uint8, (0, 84, 84)),
])
def test_codec_request_roundtrip_preserves_dtype_shape_bytes(dtype, shape):
    rng = np.random.default_rng(0)
    arr = (rng.random(shape) * 100).astype(dtype)
    wire = codec.encode_request(actor_id=7, request_id=123, obs=arr)
    stream = io.BytesIO(wire)
    frame = codec.read_frame(stream.read)
    assert frame.kind == codec.KIND_REQUEST
    assert frame.actor_id == 7 and frame.request_id == 123
    assert frame.array.dtype == arr.dtype
    assert frame.array.shape == arr.shape
    assert np.array_equal(frame.array, arr)


def test_codec_reply_error_traj_roundtrip():
    actions = np.arange(6, dtype=np.int64)
    frame = codec.decode_frame(codec.encode_reply(9, actions)[4:])
    assert frame.kind == codec.KIND_REPLY and frame.request_id == 9
    assert np.array_equal(frame.array, actions)

    err = codec.decode_frame(codec.encode_error(0, "server died: boom")[4:])
    assert err.kind == codec.KIND_ERROR and err.request_id == 0
    assert err.message == "server died: boom"

    traj = {"obs": np.random.rand(8, 50).astype(np.float32),
            "actions": np.arange(8, dtype=np.int32),
            "rewards": np.zeros(8, np.float32),
            "dones": np.zeros(8, np.float32)}
    out = codec.decode_frame(codec.encode_trajectory(3, traj)[4:])
    assert out.kind == codec.KIND_TRAJ and out.actor_id == 3
    assert sorted(out.arrays) == sorted(traj)
    for k in traj:
        assert out.arrays[k].dtype == traj[k].dtype
        assert np.array_equal(out.arrays[k], traj[k])


def test_codec_refuses_the_retired_single_observation_flag():
    # bit 0x01 once marked a single-observation request; no encoder sets
    # it now, so a frame carrying it is refused like any unknown bit
    wire = bytearray(codec.encode_request(1, 2, np.zeros((1, 4), np.float32)))
    wire[4 + 4] |= 0x01           # length prefix, magic, ver, kind: flags
    with pytest.raises(codec.CodecError, match="unknown flag bits 0x01"):
        codec.decode_frame(bytes(wire[4:]))


def test_codec_rejects_truncated_frames():
    wire = codec.encode_request(1, 1, np.random.rand(4, 10).astype(np.float32))
    # truncation at every interesting boundary: inside the length prefix,
    # inside the header, inside the ndarray prologue, inside the data
    for cut in (2, 6, 24, len(wire) - 3):
        stream = io.BytesIO(wire[:cut])
        with pytest.raises(codec.TruncatedFrame):
            codec.read_frame(stream.read)
    # clean EOF at a frame boundary is not an error
    assert codec.read_frame(io.BytesIO(b"").read) is None


def test_codec_rejects_oversized_frames_before_allocating():
    wire = codec.encode_request(1, 1, np.zeros((4, 10), np.float32))
    with pytest.raises(codec.FrameTooLarge):
        codec.read_frame(io.BytesIO(wire).read, max_frame=16)


def test_codec_rle_roundtrip_uint8():
    """RLE request payloads decode to the identical array, and only uint8
    frames that actually shrink carry the flag."""
    arr = np.zeros((4, 84, 84), np.uint8)
    arr[:, 40:44] = 255                      # Atari-ish sparse frame
    wire = codec.encode_request(7, 9, arr, compress=True)
    raw = codec.encode_request(7, 9, arr)
    assert len(wire) < len(raw) // 10        # long runs compress hard
    frame = codec.decode_frame(wire[4:])
    assert frame.flags & codec.FLAG_RLE
    assert frame.array.dtype == np.uint8
    assert frame.array.shape == arr.shape
    assert np.array_equal(frame.array, arr)
    # incompressible payload: compress=True must fall back to raw framing
    rng = np.random.default_rng(0)
    noisy = rng.integers(0, 256, (3, 64), dtype=np.uint8)
    wire_n = codec.encode_request(1, 2, noisy, compress=True)
    frame_n = codec.decode_frame(wire_n[4:])
    assert not frame_n.flags & codec.FLAG_RLE
    assert np.array_equal(frame_n.array, noisy)
    # non-uint8 payloads never compress
    f32 = np.zeros((4, 50), np.float32)
    assert not codec.decode_frame(
        codec.encode_request(1, 3, f32, compress=True)[4:]).flags \
        & codec.FLAG_RLE


def test_codec_rle_property_roundtrip():
    hypothesis = pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    @settings(deadline=None, max_examples=30)
    @given(st.integers(0, 2 ** 31 - 1), st.integers(0, 4),
           st.integers(1, 600))
    def roundtrip(seed, runs, n):
        rng = np.random.default_rng(seed)
        # mix of long runs and noise, incl. runs > 255 (pair splitting)
        arr = rng.integers(0, 2 if runs else 256, n, dtype=np.uint8)
        out = codec.rle_decode_u8(codec.rle_encode_u8(arr), arr.size)
        assert np.array_equal(out, arr)

    roundtrip()


def test_codec_rejects_unknown_flags_and_bad_rle():
    wire = codec.encode_request(1, 1, np.zeros((2, 4), np.float32))
    body = bytearray(wire[4:])
    body[4] |= 0x80                          # unknown flag bit
    with pytest.raises(codec.CodecError, match="unknown flag"):
        codec.decode_frame(bytes(body))
    # array-encoding flags are only valid on array frames
    err = bytearray(codec.encode_error(0, "boom")[4:])
    err[4] |= codec.FLAG_RLE
    with pytest.raises(codec.CodecError, match="invalid on frame kind"):
        codec.decode_frame(bytes(err))
    # RLE run total must match the declared shape exactly
    with pytest.raises(codec.CodecError, match="RLE"):
        codec.rle_decode_u8(bytes([5, 1]), expected=4)
    with pytest.raises(codec.CodecError, match="zero-length"):
        codec.rle_decode_u8(bytes([0, 1]), expected=0)
    with pytest.raises(codec.CodecError, match="odd"):
        codec.rle_decode_u8(bytes([5]), expected=5)


def test_rle_expansion_capped_at_readers_max_frame():
    """The RLE expansion bound follows the configured max_frame, both
    tightened and (by default) at DEFAULT_MAX_FRAME — a tiny hostile
    frame cannot out-expand the limit the raw path enforces."""
    arr = np.zeros(4096, np.uint8)
    wire = codec.encode_request(1, 1, arr, compress=True)
    assert codec.decode_frame(wire[4:]).array.size == 4096
    with pytest.raises(codec.CodecError, match="RLE expansion"):
        codec.decode_frame(wire[4:], max_frame=1024)
    with pytest.raises(codec.CodecError, match="RLE expansion"):
        codec.read_frame(io.BytesIO(wire).read, max_frame=1024)


def test_gateway_contains_zero_dim_request_to_its_connection():
    """A wire REQUEST with a 0-d obs (decodable, but not lane-batched)
    must sever only the offending connection — never `_fatal` the server
    out from under every other peer."""
    srv = InferenceServer(det_policy, max_batch=4, deadline_ms=2.0)
    gw = InferenceGateway(srv)
    srv.start()
    addr = gw.start()
    import socket as _s
    evil = _s.create_connection(addr)
    good = SyncSocketTransport.connect(addr)
    try:
        evil.sendall(codec.encode_request(0, 1, np.int32(7)))  # 0-d
        obs = np.random.rand(2, 50).astype(np.float32)
        deadline = time.perf_counter() + 5.0
        while gw.error is None and time.perf_counter() < deadline:
            time.sleep(0.01)
        assert gw.error is not None and "ndim" in gw.error
        # the server and other connections are untouched
        assert srv.error is None
        got = good.submit_batch(1, obs).get(timeout=5.0)
        assert np.array_equal(got, det_policy(obs, None))
    finally:
        evil.close()
        good.close()
        gw.stop()
        srv.stop()


def test_codec_hello_roundtrip():
    frame = codec.decode_frame(
        codec.encode_hello(codec.SUPPORTED_CODECS)[4:])
    assert frame.kind == codec.KIND_HELLO
    assert frame.codecs == codec.SUPPORTED_CODECS


def test_codec_rejects_garbage():
    with pytest.raises(codec.CodecError):
        codec.decode_frame(b"\x00" * 40)          # bad magic
    wire = codec.encode_reply(1, np.zeros(3, np.float32))
    with pytest.raises(codec.CodecError):
        codec.decode_frame(wire[4:] + b"xx")      # trailing bytes
    # internal length lies about the payload size
    tampered = bytearray(wire[4:])
    tampered[-13] ^= 0xFF                          # flip a byte of u64 nbytes
    with pytest.raises(codec.CodecError):
        codec.decode_frame(bytes(tampered))
    with pytest.raises(codec.CodecError):          # no pickle on the wire
        codec.encode_reply(1, np.array([object()], dtype=object))


def test_codec_property_roundtrip():
    hypothesis = pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    @settings(deadline=None, max_examples=30)
    @given(st.sampled_from(["u1", "i4", "i8", "f4", "f8"]),
           st.lists(st.integers(0, 5), min_size=0, max_size=3),
           st.integers(0, 2 ** 31 - 1))
    def roundtrip(dtype, shape, seed):
        rng = np.random.default_rng(seed)
        arr = (rng.random(shape) * 50).astype(dtype)
        frame = codec.decode_frame(
            codec.encode_request(seed % 1000, seed, arr)[4:])
        assert frame.array.dtype == arr.dtype
        assert frame.array.shape == arr.shape
        assert np.array_equal(frame.array, arr)

    roundtrip()


# ------------------------------------------- in-process seam + fail-fast

def test_inproc_transport_is_the_server_behavior():
    # in-process actors hold the server itself: its submit_batch is the
    # whole in-process transport, fail-fast once stopped
    srv = InferenceServer(det_policy, max_batch=4, deadline_ms=2.0)
    srv.start()
    obs = np.random.rand(4, 50).astype(np.float32)
    try:
        got = srv.submit_batch(0, obs).get(timeout=5.0)
        assert np.array_equal(got, det_policy(obs, None))
        assert srv.error is None
    finally:
        srv.stop()
    assert isinstance(srv.submit_batch(0, obs).get(timeout=1.0), ReplyError)


def test_server_stop_drains_pending_with_poison():
    started = threading.Event()

    def slow_policy(obs, ids):
        started.set()
        time.sleep(0.2)
        return np.zeros((obs.shape[0],), np.int32)

    srv = InferenceServer(slow_policy, max_batch=1, deadline_ms=1.0)
    srv.start()
    srv.submit_batch(0, np.zeros((1, 4), np.float32))
    started.wait(timeout=5.0)
    # second request is queued behind the in-flight batch when stop() lands
    reply = srv.submit_batch(1, np.zeros((1, 4), np.float32))
    srv.stop()
    out = reply.get(timeout=2.0)
    assert isinstance(out, ReplyError), out


def test_actor_surfaces_server_death_instead_of_deadlocking():
    calls = []

    def dying_policy(obs, ids):
        calls.append(1)
        if len(calls) > 3:
            raise RuntimeError("policy exploded")
        return np.zeros((obs.shape[0],), np.int32)

    srv = InferenceServer(dying_policy, max_batch=2, deadline_ms=1.0)
    actor = Actor(0, CatchEnv, srv, lambda t: None, unroll=4, num_envs=2)
    srv.start()
    actor.start()
    # without fail-fast the actor thread would hang forever here
    actor._thread.join(timeout=10.0)
    assert not actor._thread.is_alive(), "actor deadlocked on a dead server"
    assert actor.error is not None and "policy exploded" in actor.error
    assert "policy exploded" in srv.error
    srv.stop()


def test_derived_stats_normalize_the_raw_sums():
    srv = InferenceServer(det_policy, max_batch=4, deadline_ms=1.0)
    srv.start()
    try:
        for _ in range(5):
            out = srv.submit_batch(0, np.random.rand(2, 50).astype(
                np.float32)).get(timeout=5.0)
            assert out.shape == (2,)
    finally:
        srv.stop()
    d = srv.derived_stats()
    s = srv.stats
    assert d["mean_batch_occupancy"] == pytest.approx(
        s["batch_occupancy"] / s["batches"])
    assert d["mean_queue_wait_ms"] == pytest.approx(
        1e3 * s["queue_wait_s"] / s["requests"])
    assert d["mean_lanes_per_rpc"] == pytest.approx(
        s["requests"] / s["rpcs"])
    assert 0 < d["mean_batch_occupancy"] <= 1.0


# ------------------------------------------------------- socket loopback

def test_socket_loopback_roundtrip_and_recurrent_slots():
    seen_slots = {}

    def slot_recording_policy(obs, ids):
        for row, slot in enumerate(np.asarray(ids)):
            seen_slots.setdefault(int(slot), 0)
            seen_slots[int(slot)] += 1
        return det_policy(obs, ids)

    srv = InferenceServer(slot_recording_policy, max_batch=8, deadline_ms=2.0)
    gw = InferenceGateway(srv)
    srv.start()
    addr = gw.start()
    tr = SyncSocketTransport.connect(addr)
    try:
        obs = np.random.rand(4, 50).astype(np.float32)
        for _ in range(3):
            got = tr.submit_batch(11, obs).get(timeout=5.0)
            assert np.array_equal(got, det_policy(obs, None))
        # a one-lane request is one slot and one action
        one = tr.submit_batch(12, np.zeros((1, 50), np.float32)).get(
            timeout=5.0)
        assert one.shape == (1,)
        # 4 lanes of actor 11 + 1 lane of actor 12 = 5 distinct slots, and
        # lane slots are stable across repeated requests
        assert srv.num_slots == 5
        assert sorted(seen_slots) == [0, 1, 2, 3, 4]
        assert all(c == 3 for s, c in seen_slots.items() if s < 4)
    finally:
        tr.close()
        gw.stop()
        srv.stop()


def test_sync_socket_transport_roundtrip_and_timeout():
    srv = InferenceServer(det_policy, max_batch=2, deadline_ms=1.0)
    gw = InferenceGateway(srv)
    srv.start()
    addr = gw.start()
    tr = SyncSocketTransport.connect(addr)
    try:
        obs = np.random.rand(2, 50).astype(np.float32)
        reply = tr.submit_batch(0, obs)
        assert np.array_equal(reply.get(timeout=5.0), det_policy(obs, None))
        # a too-short timeout raises queue.Empty (the actor-loop contract)
        # and a retry on the SAME reply object still succeeds
        reply2 = tr.submit_batch(0, obs)
        try:
            got = reply2.get(timeout=1e-5)
        except queue.Empty:
            got = reply2.get(timeout=5.0)
        assert np.array_equal(got, det_policy(obs, None))
    finally:
        tr.close()
        gw.stop()
        srv.stop()


def test_transport_poisons_pending_on_gateway_loss():
    block = threading.Event()

    def blocking_policy(obs, ids):
        block.wait(timeout=10.0)
        return np.zeros((obs.shape[0],), np.int32)

    srv = InferenceServer(blocking_policy, max_batch=1, deadline_ms=1.0)
    gw = InferenceGateway(srv)
    srv.start()
    addr = gw.start()
    tr = SyncSocketTransport.connect(addr)
    try:
        reply = tr.submit_batch(0, np.zeros((1, 4), np.float32))
        time.sleep(0.1)
        gw.stop()                     # connection drops mid-request
        out = reply.get(timeout=5.0)
        assert isinstance(out, ReplyError), out
        assert tr.error is not None
        # subsequent submits fail fast, no new hang
        out2 = tr.submit_batch(0, np.zeros((1, 4), np.float32)).get(
            timeout=1.0)
        assert isinstance(out2, ReplyError)
    finally:
        block.set()
        tr.close()
        srv.stop()


def test_wire_compression_is_negotiated_per_connection():
    """A `compress=True` client HELLOs, the gateway grants RLE, and uint8
    obs then cross the wire compressed — while a plain client on the SAME
    gateway keeps sending raw frames (negotiation is per connection)."""

    def u8_policy(obs, ids):
        return obs.reshape(obs.shape[0], -1).astype(np.int64).sum(axis=1) % 3

    srv = InferenceServer(u8_policy, max_batch=8, deadline_ms=2.0)
    gw = InferenceGateway(srv)
    srv.start()
    addr = gw.start()
    obs = np.zeros((2, 84, 84), np.uint8)
    obs[:, 10:12] = 3
    tr_c = SyncSocketTransport.connect(addr, compress=True)
    tr_p = SyncSocketTransport.connect(addr)
    try:
        for _ in range(4):
            got = tr_c.submit_batch(0, obs).get(timeout=5.0)
            assert np.array_equal(got, u8_policy(obs, None))
        assert tr_c._rle, "gateway did not grant the offered codec"
        for _ in range(2):
            got = tr_p.submit_batch(1, obs).get(timeout=5.0)
            assert np.array_equal(got, u8_policy(obs, None))
        assert not tr_p._rle
        assert gw.stats["hello_frames"] == 1
        # first request may race the HELLO grant (sent raw); the rest ride
        # compressed. The plain connection contributes zero RLE frames.
        assert gw.stats["rle_request_frames"] >= 3
        assert gw.stats["request_frames"] == 6
    finally:
        tr_c.close()
        tr_p.close()
        gw.stop()
        srv.stop()


def test_wire_replies_leave_via_writer_thread_not_server_loop():
    """Async-reply contract: a connection whose peer never reads cannot
    stall the server's batch loop — replies to it queue (or sever that
    one connection), while OTHER connections keep round-tripping at full
    rate. The stalled peer is a raw socket that sends requests and never
    recvs, so nothing drains its side of the wire."""
    import socket as _s

    from repro.transport import codec as _codec

    def policy(obs, ids):
        return np.zeros((obs.shape[0],), np.int64)

    srv = InferenceServer(policy, max_batch=1, deadline_ms=0.5)
    gw = InferenceGateway(srv)
    srv.start()
    addr = gw.start()
    stalled = _s.create_connection(addr)
    live = SyncSocketTransport.connect(addr)
    try:
        obs = np.zeros((1, 64), np.float32)
        for rid in range(1, 65):
            stalled.sendall(_codec.encode_request(0, rid, obs))
        # the live connection must keep round-tripping promptly while the
        # stalled connection's replies sit in its writer's queue
        t0 = time.perf_counter()
        for _ in range(20):
            out = live.submit_batch(1, obs).get(timeout=5.0)
            assert out.shape == (1,)
        assert time.perf_counter() - t0 < 5.0
        assert srv.error is None and gw.error is None
    finally:
        stalled.close()
        live.close()
        gw.stop()
        srv.stop()


def test_conn_writer_backpressure_fails_connection_not_server():
    """A writer whose bounded queue overflows severs THAT connection
    (fail-fast: the peer sees the drop and poisons its pending replies)
    instead of blocking the thread that called `send`."""
    import socket as _s

    from repro.transport.socket import _ConnWriter

    a, b = _s.socketpair()
    w = _ConnWriter(a, maxsize=4)
    try:
        # overflow the bounded queue while nobody drains the peer: the
        # writer must fail (not block) once the queue and buffers jam
        payload = b"x" * (1 << 20)
        deadline = time.perf_counter() + 10.0
        while not w.failed and time.perf_counter() < deadline:
            w.send(payload)
        assert w.failed, "writer blocked instead of failing the connection"
        # and `send` after failure is a no-op, not an error
        w.send(payload)
    finally:
        w.stop()
        a.close()
        b.close()


# ------------------------------------------- parity + end-to-end system

def _run_inproc_rollout(n_traj):
    srv = InferenceServer(det_policy, max_batch=3, deadline_ms=2.0)
    trajs = []
    actor = Actor(0, CatchEnv, srv, lambda t: trajs.append(t),
                  unroll=4, num_envs=3)
    srv.start()
    actor.start()
    deadline = time.perf_counter() + 30.0
    while len(trajs) < n_traj and time.perf_counter() < deadline:
        time.sleep(0.01)
    actor.stop()
    srv.stop()
    actor.join()
    assert len(trajs) >= n_traj, "in-proc rollout produced too few unrolls"
    return trajs[:n_traj]


def _run_socket_rollout(n_traj):
    srv = InferenceServer(det_policy, max_batch=3, deadline_ms=2.0)
    trajs = []
    gw = InferenceGateway(srv, sink=lambda t: trajs.append(t))
    srv.start()
    addr = gw.start()
    pool = ActorHostPool(CatchEnv, num_actors=1, envs_per_actor=3, unroll=4)
    stats = pool.run(addr, seconds=2.0)
    gw.stop()
    srv.stop()
    assert stats[0]["error"] is None, stats[0]["error"]
    assert len(trajs) >= n_traj, \
        f"socket rollout produced {len(trajs)} < {n_traj} unrolls"
    return trajs[:n_traj]


def test_loopback_parity_socket_rollouts_bit_identical_to_inproc():
    """THE transport contract: same seeds, same policy -> the per-lane
    unroll stream that crosses the wire equals the in-proc one, bitwise."""
    n = 6
    a_trajs = _run_inproc_rollout(n)
    b_trajs = _run_socket_rollout(n)
    for i, (ta, tb) in enumerate(zip(a_trajs, b_trajs)):
        assert sorted(ta) == sorted(tb)
        for k in ta:
            va, vb = np.asarray(ta[k]), np.asarray(tb[k])
            assert va.dtype == vb.dtype, (i, k)
            assert np.array_equal(va, vb), f"unroll {i} key {k} diverged"


def test_seed_system_socket_transport_end_to_end():
    """`SeedSystem(transport='socket')` on loopback: frames flow, replay is
    fed over the wire, derived+raw inference stats are reported, and
    throughput is within sanity range of the in-proc backend (the strict
    0.5x acceptance sweep lives in fig4 --smoke; here we gate against
    catastrophic regression on noisy CI boxes)."""
    from repro.core.system import SeedSystem

    def run_once(transport):
        kwargs = dict(env_factory=CatchEnv, policy_step=det_policy,
                      num_actors=2, unroll=8, envs_per_actor=4,
                      deadline_ms=1.0, transport=transport)
        if transport == "socket":
            kwargs["num_actor_hosts"] = 1
        sys_ = SeedSystem(**kwargs)
        sys_.warmup()
        stats = sys_.run(seconds=0.8, with_learner=False)
        return sys_, stats

    best_rel = 0.0
    for attempt in range(3):
        sys_in, stats_in = run_once("inproc")
        sys_so, stats_so = run_once("socket")
        assert stats_so["inference_error"] is None
        assert stats_so["host_errors"] == []
        assert stats_so["env_frames"] > 50, stats_so
        assert stats_so["gateway_traj_frames"] > 0
        assert len(sys_so.replay) > 0, "trajectories did not reach replay"
        # raw counters AND derived means are both reported
        for key in ("batch_occupancy_sum", "queue_wait_s_sum",
                    "mean_batch_occupancy", "mean_queue_wait_ms",
                    "mean_lanes_per_rpc", "inference_rpcs"):
            assert key in stats_so, key
        best_rel = max(best_rel, stats_so["env_frames_per_s"]
                       / stats_in["env_frames_per_s"])
        if best_rel >= 0.5:
            break
    assert best_rel >= 0.25, \
        f"socket transport {best_rel:.2f}x in-proc: wire path regressed"


def test_codec_reply_version_header_field():
    """Wire v2: the behavior-param version travels in the REPLY header's
    dedicated param_version field — the v1 hack that smuggled it through
    the unused actor_id slot is gone, and actor_id stays 0 on replies."""
    wire = codec.encode_reply(9, np.arange(4, dtype=np.int32), version=17)
    frame = codec.read_frame(io.BytesIO(wire).read)
    assert frame.kind == codec.KIND_REPLY
    assert frame.request_id == 9
    assert frame.param_version == 17
    assert frame.actor_id == 0
    assert np.array_equal(frame.array, np.arange(4, dtype=np.int32))
    # default stays 0 = unversioned
    legacy = codec.read_frame(io.BytesIO(
        codec.encode_reply(9, np.arange(4, dtype=np.int32))).read)
    assert legacy.param_version == 0
    # every non-REPLY frame carries 0 in the reserved field
    req = codec.decode_frame(
        codec.encode_request(3, 4, np.zeros(2, np.float32))[4:])
    assert req.param_version == 0


def test_codec_rejects_mismatched_wire_version():
    """A peer speaking a different frame version byte is rejected with a
    clear CodecError — capability interop WITHIN a version is HELLO's
    job; across versions both ends must upgrade."""
    wire = bytearray(codec.encode_reply(1, np.arange(3, dtype=np.int64)))
    assert wire[6] == codec.VERSION          # len(4) + magic(2), then ver
    wire[6] = codec.VERSION - 1
    with pytest.raises(codec.CodecError, match="wire version"):
        codec.decode_frame(bytes(wire[4:]))
    wire[6] = codec.VERSION + 1
    with pytest.raises(codec.CodecError, match="wire version"):
        codec.decode_frame(bytes(wire[4:]))


def test_onpolicy_negotiation_version_flow_and_traj_stripping():
    """Per-connection CODEC_ONPOLICY: a granted client sees the learner's
    param version on every reply and its TRAJ metadata reaches the sink;
    an un-negotiated client on the SAME gateway strips the on-policy keys
    before they cross the wire (old-gateway interop, exercised from the
    client side)."""
    version = {"v": 3}
    srv = InferenceServer(det_policy, max_batch=8, deadline_ms=2.0)
    sunk = []
    gw = InferenceGateway(srv, sink=sunk.append,
                          version_source=lambda: version["v"],
                          onpolicy=True)
    srv.start()
    addr = gw.start()
    traj = {"obs": np.zeros((4, 5), np.float32),
            "actions": np.zeros((4,), np.int32),
            "rewards": np.ones((4,), np.float32),
            "dones": np.zeros((4,), np.float32),
            "behavior_logprobs": np.full((4,), -0.7, np.float32),
            "param_version": np.int64(3)}
    tr_on = SyncSocketTransport.connect(addr, onpolicy=True)
    tr_off = SyncSocketTransport.connect(addr)
    try:
        assert tr_on.wait_hello(5.0) and tr_on.onpolicy_granted
        obs = np.zeros((2, 50), np.float32)
        tr_on.submit_batch(0, obs).get(timeout=5.0)
        assert tr_on.param_version == 3
        version["v"] = 8                       # learner "published"
        tr_on.submit_batch(0, obs).get(timeout=5.0)
        assert tr_on.param_version == 8        # monotone, reply-borne
        tr_on.send_trajectory(traj)
        tr_off.send_trajectory(traj)           # not granted: must strip
        deadline = time.perf_counter() + 5.0
        while len(sunk) < 2 and time.perf_counter() < deadline:
            time.sleep(0.01)
        assert len(sunk) == 2, "trajectories did not reach the sink"
        by_keys = sorted((sorted(t) for t in sunk), key=len)
        assert by_keys[0] == ["actions", "dones", "obs", "rewards"]
        assert by_keys[1] == ["actions", "behavior_logprobs", "dones",
                              "obs", "param_version", "rewards"]
        full = next(t for t in sunk if "param_version" in t)
        assert int(np.asarray(full["param_version"]).reshape(())) == 3
        np.testing.assert_array_equal(full["behavior_logprobs"],
                                      traj["behavior_logprobs"])
    finally:
        tr_on.close()
        tr_off.close()
        gw.stop()
        srv.stop()


def test_replay_gateway_refuses_onpolicy_grant():
    """A gateway fronting a replay-based system (the default) must NOT
    grant CODEC_ONPOLICY even to a client that offers it — otherwise
    on-policy TRAJ metadata would flow into a replay sink that never
    asked for it (schema drift inside PrioritizedReplay)."""
    srv = InferenceServer(det_policy, max_batch=4, deadline_ms=2.0)
    sunk = []
    gw = InferenceGateway(srv, sink=sunk.append)       # onpolicy=False
    srv.start()
    addr = gw.start()
    tr = SyncSocketTransport.connect(addr, onpolicy=True)
    try:
        assert tr.wait_hello(5.0)
        assert not tr.onpolicy_granted
        tr.send_trajectory({"obs": np.zeros((2, 4), np.float32),
                            "actions": np.zeros((2,), np.int32),
                            "rewards": np.zeros((2,), np.float32),
                            "dones": np.zeros((2,), np.float32),
                            "behavior_logprobs": np.zeros((2,), np.float32),
                            "param_version": np.int64(5)})
        deadline = time.perf_counter() + 5.0
        while not sunk and time.perf_counter() < deadline:
            time.sleep(0.01)
        assert sunk and sorted(sunk[0]) == \
            ["actions", "dones", "obs", "rewards"]
    finally:
        tr.close()
        gw.stop()
        srv.stop()
