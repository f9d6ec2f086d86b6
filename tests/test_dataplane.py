"""Distributed data-plane tests: delta wire protocol, zero-copy
tensor framing, protocol negotiation, multi-tick jobs, and the
bytes-per-job micro-bench (ISSUE 4; docs/distributed.md).

The equivalence tests drive the master/worker workflow contract
DIRECTLY (no sockets) on a fixed round-robin schedule: real threaded
workers interleave nondeterministically, and the bit-identical
acceptance gate needs the exact same update order in both runs.  The
wire layer gets its own socketpair/loopback coverage below.
"""

import socket
import threading
import time

import numpy
import pytest

import veles_tpu.prng as prng
from veles_tpu import resilience
from veles_tpu.client import Client
from veles_tpu.config import root
from veles_tpu.launcher import Launcher
from veles_tpu.network_common import (
    Channel, WireCodec, decode_bf16, encode_bf16, encode_message,
    parse_codec_spec, recv_message, send_message)
from veles_tpu.resilience import ProtocolError
from veles_tpu.server import Server, negotiate_protocol

#: The negotiated protocol the in-process drivers use for the delta
#: dialect (what a real handshake with default config produces).
DELTA_PROTO = {"tensor": True, "delta": True, "codec": "none",
               "dtype": "fp32", "ticks": 1}


@pytest.fixture(autouse=True)
def _clean_stats():
    resilience.reset()
    yield
    resilience.reset()


# -- tensor framing --------------------------------------------------------

def _framed_roundtrip(obj, proto):
    a, b = socket.socketpair()
    try:
        ca, cb = Channel(a, secret="s"), Channel(b, secret="s")
        ca.set_proto(proto)
        cb.set_proto(proto)
        t = threading.Thread(target=ca.send, args=(obj,))
        t.start()
        got = cb.recv()
        t.join()
        return got
    finally:
        a.close()
        b.close()


@pytest.mark.parametrize("codec", ["none", "gzip"])
def test_tensor_framing_roundtrip(codec):
    """ndarrays leave the pickle and survive bit-exactly through the
    framed format, nested anywhere in the message tree, under both
    payload codecs."""
    obj = {
        "cmd": "job",
        "data": {
            "fc0": {"F": {"weights":
                          numpy.arange(3000, dtype=numpy.float32)
                          .reshape(30, 100),
                          "bias": numpy.ones(100, numpy.float32)},
                    "v": 3},
            "loader": {"indices":
                       numpy.arange(64, dtype=numpy.int32)},
            "nested": [numpy.zeros((4, 4), numpy.float64),
                       ("tiny", numpy.arange(3)),  # stays in pickle
                       {"u16": numpy.arange(500,
                                            dtype=numpy.uint16)}],
        },
    }
    got = _framed_roundtrip(
        obj, {"tensor": True, "codec": codec,
              "codec_threshold": 1024})
    assert got["cmd"] == "job"
    fc0 = got["data"]["fc0"]
    assert fc0["v"] == 3
    assert fc0["F"]["weights"].dtype == numpy.float32
    numpy.testing.assert_array_equal(
        fc0["F"]["weights"], obj["data"]["fc0"]["F"]["weights"])
    numpy.testing.assert_array_equal(
        got["data"]["loader"]["indices"],
        obj["data"]["loader"]["indices"])
    nested = got["data"]["nested"]
    assert nested[0].dtype == numpy.float64
    assert isinstance(nested[1], tuple) and nested[1][0] == "tiny"
    numpy.testing.assert_array_equal(nested[2]["u16"],
                                     obj["data"]["nested"][2]["u16"])
    # Wire accounting rode along.
    assert resilience.stats.get("net.bytes_sent") > 0
    assert resilience.stats.get("net.bytes_recv") > 0


def test_tensor_framing_arrays_writable():
    """Received framed arrays must be writable (downstream code
    mutates applied minibatch/mask buffers in place)."""
    arr = numpy.arange(2000, dtype=numpy.float32)
    got = _framed_roundtrip({"a": arr},
                            {"tensor": True, "codec": "none"})
    got["a"][0] = 42.0
    assert got["a"][0] == 42.0
    # The gzip path hands back a decompressed copy — also writable.
    got = _framed_roundtrip({"a": arr},
                            {"tensor": True, "codec": "gzip",
                             "codec_threshold": 16})
    got["a"][1] = 7.0
    assert got["a"][1] == 7.0


def test_tensor_frame_respects_message_cap():
    """A tensor frame whose decompressed payload exceeds the
    receiver's cap reads as a dead peer, exactly like the legacy
    gunzip bomb guard."""
    a, b = socket.socketpair()
    try:
        flags, parts = encode_message(
            {"a": numpy.zeros(1 << 16, numpy.uint8)},
            codec=WireCodec("gzip", 1, 16), tensor=True)
        from veles_tpu.network_common import send_parts
        t = threading.Thread(target=send_parts,
                             args=(a, flags, parts))
        t.start()
        got = recv_message(b, max_message=1024)
        t.join()
        assert got is None
    finally:
        a.close()
        b.close()


def test_sender_bounds_raw_not_compressed_size(monkeypatch):
    """The sender cap must bound the RAW serialized size: a frame
    that only fits the wire compressed would blow the receiver's
    decompression budget and read as a dead peer (silent reconnect
    loop) instead of failing loudly at the sender."""
    import veles_tpu.network_common as nc
    monkeypatch.setattr(nc, "MAX_MESSAGE_SIZE", 16 * 1024)
    big = numpy.zeros(1 << 15, numpy.uint8)  # 32 KiB raw, gzips tiny
    with pytest.raises(ValueError):
        encode_message({"a": big}, codec=WireCodec("gzip", 1, 16),
                       tensor=True)
    with pytest.raises(ValueError):
        encode_message({"a": big.tobytes()},
                       codec=WireCodec("gzip", 1, 16))


def test_legacy_frames_interoperate_with_new_recv():
    """A plain pickled frame (old peer) parses fine through the new
    receive path — and vice versa the legacy sender path is still the
    default when no protocol was negotiated."""
    a, b = socket.socketpair()
    try:
        send_message(a, {"cmd": "x",
                         "arr": numpy.arange(5000.0)})
        got = recv_message(b)
        assert got["cmd"] == "x"
        numpy.testing.assert_array_equal(got["arr"],
                                         numpy.arange(5000.0))
    finally:
        a.close()
        b.close()


# -- codec configuration (satellite: configurable gzip) --------------------

def test_parse_codec_spec():
    assert parse_codec_spec("gzip") == ("gzip", None, None)
    assert parse_codec_spec("gzip:6") == ("gzip", 6, None)
    assert parse_codec_spec("gzip:6:4096") == ("gzip", 6, 4096)
    assert parse_codec_spec("none") == ("none", None, None)
    with pytest.raises(ValueError):
        parse_codec_spec("snappy")


def test_codec_threshold_and_level():
    """Frames below the configured threshold ship uncompressed; the
    level is honored (higher level → no bigger output)."""
    payload = bytes(numpy.arange(8192, dtype=numpy.uint8)
                    .repeat(4))  # compressible
    small = WireCodec("gzip", 1, threshold=1 << 20)
    assert small.pack(payload) == (False, payload)
    low = WireCodec("gzip", 1, threshold=16)
    high = WireCodec("gzip", 9, threshold=16)
    c1, p1 = low.pack(payload)
    c9, p9 = high.pack(payload)
    assert c1 and c9
    assert len(p9) <= len(p1) < len(payload)
    none = WireCodec("none")
    assert none.pack(payload) == (False, payload)


def test_bf16_roundtrip():
    """--net-dtype bf16: exact for bf16-representable values, RNE
    rounding otherwise, NaN-preserving (the round-trip contract)."""
    exact = numpy.array([0.0, 1.0, -2.5, 0.15625, 2.0 ** 38],
                        numpy.float32)
    assert decode_bf16(encode_bf16(exact)).tolist() == exact.tolist()
    rng = numpy.random.RandomState(7)
    vals = rng.randn(4096).astype(numpy.float32) * 1e-3
    back = decode_bf16(encode_bf16(vals), vals.shape)
    assert back.shape == vals.shape
    # bf16 has 8 mantissa bits → relative error < 2^-8.
    err = numpy.abs(back - vals) / numpy.maximum(numpy.abs(vals),
                                                 1e-30)
    assert float(err.max()) < 2.0 ** -8
    weird = numpy.array([numpy.nan, numpy.inf, -numpy.inf],
                        numpy.float32)
    back = decode_bf16(encode_bf16(weird))
    assert numpy.isnan(back[0]) and numpy.isposinf(back[1]) \
        and numpy.isneginf(back[2])


# -- deterministic master/worker driver ------------------------------------

def _mnist_pair(seed, **kwargs):
    from veles_tpu.znicz.samples.mnist import MnistWorkflow
    kwargs.setdefault("max_epochs", 3)
    kwargs.setdefault("learning_rate", 0.1)
    kwargs.setdefault("gradient_moment", 0.5)
    prng.reset()
    prng.get(0).seed(seed)
    launcher = Launcher()
    wf = MnistWorkflow(launcher, **kwargs)
    launcher.initialize()
    return wf


def _drive(master, workers, proto, max_cycles=2000):
    """Fixed round-robin schedule: serve every worker, then apply
    every reply, until the master's decision completes.  Pipelined
    enough to exercise staleness, deterministic enough to compare
    runs bit-for-bit."""
    for sid, wf in workers.items():
        master.note_slave_protocol(sid, proto)
        wf.note_net_proto(proto)
    for _ in range(max_cycles):
        if master.should_stop_serving():
            return
        jobs = {}
        for sid in workers:
            if master.should_stop_serving():
                break
            job = master.generate_data_for_slave(sid)
            if job is not None:
                jobs[sid] = job
        if not jobs:
            return
        for sid, job in jobs.items():
            replies = []
            workers[sid].do_job(job, None, replies.append)
            master.apply_data_from_slave(replies[0], sid)
    raise AssertionError("driver did not converge in %d cycles"
                         % max_cycles)


def _final_trainables(master):
    out = {}
    for unit in master.units:
        trainables = getattr(unit, "trainables", None)
        if not trainables:
            continue
        for attr, vec in trainables.items():
            vec.map_read()
            out["%s/%s" % (unit.name, attr)] = numpy.array(vec.mem)
    return out


def test_delta_protocol_bit_identical_to_legacy():
    """THE acceptance gate: N epochs of master+2-worker training with
    the delta protocol produce bit-identical final trainables to the
    legacy full-weights path (fp32, codec=none, same schedule)."""
    results = {}
    for name, proto in (("legacy", {}), ("delta", DELTA_PROTO)):
        master = _mnist_pair(1234)
        workers = {"w1": _mnist_pair(1234), "w2": _mnist_pair(1234)}
        _drive(master, workers, proto)
        assert master.decision.epoch_number == 3
        results[name] = _final_trainables(master)
    legacy, delta = results["legacy"], results["delta"]
    assert set(legacy) == set(delta) and legacy
    for key in legacy:
        assert legacy[key].dtype == delta[key].dtype
        assert numpy.array_equal(legacy[key], delta[key]), \
            "trainable %s diverged between legacy and delta" % key


def test_delta_mode_collapses_shipped_fifo():
    """Delta mode keeps O(1) master bookkeeping per WORKER (one
    synced base), never a FIFO of full copies per in-flight job."""
    master = _mnist_pair(5, max_epochs=5)
    master.note_slave_protocol("w1", DELTA_PROTO)
    for _ in range(4):  # 4 jobs in flight, nothing applied
        master.generate_data_for_slave("w1")
    for unit in master.units:
        shipped = getattr(unit, "_shipped_", None)
        if shipped is None:
            continue
        assert not shipped, \
            "%s kept a legacy shipped FIFO in delta mode" % unit.name
        synced = getattr(unit, "_synced_", {})
        if getattr(unit, "trainables", None):
            assert set(synced) == {"w1"}
            version, arrays = synced["w1"]
            assert isinstance(arrays, dict)
    # Legacy mode for comparison: the FIFO grows per in-flight job.
    master2 = _mnist_pair(5, max_epochs=5)
    for _ in range(4):
        master2.generate_data_for_slave("w1")
    fifo_lens = [len(getattr(u, "_shipped_", {}).get("w1", []))
                 for u in master2.units
                 if getattr(u, "trainables", None)]
    assert fifo_lens and all(n == 4 for n in fifo_lens)


def test_delta_piece_shapes():
    """First job ships full weights; later jobs ship deltas; an
    unchanged interval collapses to None markers."""
    master = _mnist_pair(9, max_epochs=5)
    worker = _mnist_pair(9, max_epochs=5)
    master.note_slave_protocol("w1", DELTA_PROTO)
    worker.note_net_proto(DELTA_PROTO)
    job1 = master.generate_data_for_slave("w1")
    piece = job1["fc0"]
    assert "F" in piece and "weights" in piece["F"]
    # No updates landed: the next job's delta is all unchanged.
    job2 = master.generate_data_for_slave("w1")
    piece2 = job2["fc0"]
    assert "D" in piece2
    assert all(v is None for v in piece2["D"].values())
    # Run the jobs on the worker; its update is a delta.
    replies = []
    worker.do_job(job1, None, replies.append)
    up = replies[0]["fc0"]
    assert "U" in up and "weights" in up["U"]
    master.apply_data_from_slave(replies[0], "w1")
    replies = []
    worker.do_job(job2, None, replies.append)
    master.apply_data_from_slave(replies[0], "w1")
    # Walk to a TRAINING job (the first classes are validation, whose
    # ticks don't change weights) and apply it: the next delta must
    # then carry real bits.
    for _ in range(20):
        job = master.generate_data_for_slave("w1")
        replies = []
        worker.do_job(job, None, replies.append)
        master.apply_data_from_slave(replies[0], "w1")
        if job["__job__"]["minibatch_class"] == 2:  # TRAIN
            break
    else:
        raise AssertionError("never reached a training job")
    job_n = master.generate_data_for_slave("w1")
    piece_n = job_n["fc0"]
    assert "D" in piece_n
    assert any(v is not None for v in piece_n["D"].values())


def test_delta_version_mismatch_raises_protocol_error():
    """A delta against the wrong base version must fail loudly (the
    client turns this into a clean reconnect+rebase), never corrupt
    weights silently."""
    master = _mnist_pair(11)
    worker = _mnist_pair(11)
    master.note_slave_protocol("w1", DELTA_PROTO)
    worker.note_net_proto(DELTA_PROTO)
    job1 = master.generate_data_for_slave("w1")
    worker.apply_data_from_master(job1)
    job2 = master.generate_data_for_slave("w1")
    piece = job2["fc0"]
    assert "D" in piece
    piece["bv"] = 999  # stale base
    with pytest.raises(ProtocolError):
        worker.apply_data_from_master(job2)
    # A delta with NO prior full sync is equally fatal.
    fresh = _mnist_pair(11)
    fresh.note_net_proto(DELTA_PROTO)
    with pytest.raises(ProtocolError):
        fresh.apply_data_from_master(job2)


def test_bf16_delta_session_trains():
    """--net-dtype bf16: worker→master deltas ride as bf16 halves;
    training still converges (lossy but usable)."""
    proto = dict(DELTA_PROTO, dtype="bf16")
    master = _mnist_pair(21, max_epochs=3)
    workers = {"w1": _mnist_pair(21, max_epochs=3)}
    _drive(master, workers, proto)
    assert master.decision.epoch_number == 3
    assert master.decision.min_validation_err < 0.3


# -- protocol negotiation (satellite: version negotiation) -----------------

def test_negotiate_protocol_matrix():
    cfg = {"mode": "delta", "codec": "gzip", "codec_level": 1,
           "codec_threshold": 64, "dtype": "bf16", "job_ticks": 4,
           "require": False}
    # Old-format peer (no proto key) → pickle-compat, no error.
    proto, err = negotiate_protocol({"cmd": "handshake"}, cfg)
    assert proto == {} and err is None
    # Capable peer → full negotiation.
    hello = {"proto": {"tensor": True, "delta": True, "block": True,
                       "codecs": ("none", "gzip"),
                       "dtypes": ("fp32", "bf16")}}
    proto, err = negotiate_protocol(hello, cfg)
    assert err is None
    assert proto["tensor"] and proto["delta"]
    assert proto["codec"] == "gzip" and proto["dtype"] == "bf16"
    assert proto["ticks"] == 4
    # Peer without block capability → single-tick jobs.
    hello2 = {"proto": {"tensor": True, "delta": True,
                        "codecs": ("none",), "dtypes": ("fp32",)}}
    proto, err = negotiate_protocol(hello2, cfg)
    assert proto["ticks"] == 1
    assert proto["codec"] == "none" and proto["dtype"] == "fp32"
    # Legacy mode config trumps peer capability.
    proto, err = negotiate_protocol(hello, dict(cfg, mode="legacy"))
    assert proto == {} and err is None
    # require + old peer → actionable rejection.
    proto, err = negotiate_protocol({}, dict(cfg, require=True))
    assert proto is None
    assert "net-require" in err and "pickle-compat" in err


class _ProtoWorkflow:
    """Minimal master workflow for raw-socket protocol tests."""

    checksum = "proto-test"
    stopped = False

    def __init__(self):
        self.applied = []
        self.slave_protos = {}

    def generate_initial_data_for_slave(self, slave):
        return None

    def generate_data_for_slave(self, slave=None):
        return {"n": 1}

    def apply_data_from_slave(self, data, slave=None):
        self.applied.append((slave, data))

    def drop_slave(self, slave=None):
        pass

    def note_slave_protocol(self, slave, proto):
        self.slave_protos[slave] = proto

    def should_stop_serving(self):
        return False


def test_old_format_peer_gets_clean_rejection_with_require():
    """An old-format peer against a --net-require master receives an
    actionable error frame (not a frame-parse failure), and the real
    Client surfaces it as a permanent handshake rejection."""
    root.common.net.require = True
    try:
        master = _ProtoWorkflow()
        server = Server(":0", master)
        try:
            from veles_tpu.network_common import connect, machine_id
            chan = Channel(connect("127.0.0.1:%d" % server.port),
                           master.checksum)
            # Old-format hello: no "proto" capability key at all.
            chan.send({"cmd": "handshake",
                       "checksum": master.checksum,
                       "mid": machine_id(), "pid": 1, "power": 1.0})
            reply = chan.recv()
            assert reply["cmd"] == "error"
            assert "upgrade the worker" in reply["error"]
            chan.close()
            # The Client classifies it as permanent (no retry storm).
            slave = _ProtoWorkflow()
            client = Client("127.0.0.1:%d" % server.port, slave,
                            net_legacy=True, reconnect_attempts=0)
            client.run()  # returns promptly: HandshakeRejected
            assert client.id is None
        finally:
            server.stop()
    finally:
        root.common.net.require = False


def test_new_master_serves_old_worker_pickle_compat():
    """Default config: a worker advertising no capabilities is served
    the legacy full-pickle protocol end to end."""
    from tests.test_network import InstrumentedWorkflow
    master = InstrumentedWorkflow(Launcher())
    server = Server(":0", master)
    slave = InstrumentedWorkflow(Launcher())
    client = Client("127.0.0.1:%d" % server.port, slave,
                    net_legacy=True)
    t = threading.Thread(target=client.run, daemon=True)
    t.start()
    server.wait(timeout=20)
    t.join(timeout=5)
    assert master.applied_from_slave == 3
    assert slave.jobs_run == 3
    # The negotiated protocol for that worker is empty (legacy).
    assert all(p == {} for p in master._slave_proto_.values())


def test_capable_peer_negotiates_tensor_frames():
    """Default config end-to-end: the real Client advertises caps and
    the session runs tensor-framed delta mode."""
    from tests.test_network import InstrumentedWorkflow
    master = InstrumentedWorkflow(Launcher())
    server = Server(":0", master)
    slave = InstrumentedWorkflow(Launcher())
    client = Client("127.0.0.1:%d" % server.port, slave)
    t = threading.Thread(target=client.run, daemon=True)
    t.start()
    server.wait(timeout=20)
    t.join(timeout=5)
    assert slave.jobs_run == 3
    protos = list(master._slave_proto_.values())
    assert protos and protos[0].get("tensor") \
        and protos[0].get("delta")


# -- lock split (satellite: serialization outside the lock) ----------------

def test_job_serialization_does_not_block_updates():
    """Regression gate for the lock split: worker A's job
    serialization (slow wire, big payload) must not block
    ``_apply_update`` from worker B — only the bookkeeping half of
    job generation holds the workflow lock."""
    from tests.test_network import (InstrumentedWorkflow,
                                    _handshook_channel)
    master = InstrumentedWorkflow(Launcher())
    master.job_limit = 1000000
    server = Server(":0", master)
    serializing = threading.Event()
    release = threading.Event()
    orig = Server._serialize_job

    def slow_serialize(self, chan, job):
        serializing.set()
        assert release.wait(10), "test deadlock"
        return orig(self, chan, job)

    try:
        chan_a, _ = _handshook_channel(server, master)
        chan_b, _ = _handshook_channel(server, master)
        # B takes a job FIRST (fast path, before A's stall arms).
        chan_b.send({"cmd": "job_request"})
        assert chan_b.recv()["cmd"] == "job"
        server._serialize_job = slow_serialize.__get__(server)
        chan_a.send({"cmd": "job_request"})
        assert serializing.wait(10)
        # While A's job is stuck in serialization, B's update must
        # apply promptly — it only needs the workflow lock.
        t0 = time.time()
        chan_b.send({"cmd": "update", "data": {"echo": 1}})
        ack = chan_b.recv()
        applied_in = time.time() - t0
        assert ack["cmd"] == "update_ack"
        assert applied_in < 5.0
        assert master.applied_from_slave == 1
        release.set()
        assert chan_a.recv()["cmd"] == "job"
        chan_a.close()
        chan_b.close()
    finally:
        release.set()
        server.stop()


# -- no-job backoff (satellite) --------------------------------------------

def test_nojob_backoff_grows_and_resets():
    """The fixed no-job sleep is gone: backoff grows exponentially
    with jitter on the RetryPolicy and resets on the next real job.
    The policy's jitter rng is SEEDED here (production brings its
    own unseeded rng — idle-poll draws are wall-clock-paced by
    nature): the envelope assertions below compare sampled delays
    against each other, and an unlucky draw pair could sit inside
    the jitter band — the pre-ISSUE-13 flake this pins away."""
    import random as _random
    from veles_tpu.resilience import RetryPolicy
    slave = _ProtoWorkflow()
    client = Client(
        "127.0.0.1:1", slave, poll_delay=0.01,
        nojob_policy=RetryPolicy(
            max_attempts=1 << 30, base_delay=0.01, factor=1.5,
            max_delay=2.0, rng=_random.Random(1234)))
    delays = []
    client._sleep_interruptible = delays.append
    for _ in range(8):
        client._nojob_backoff()
    assert client._nojob_streak == 8
    assert len(delays) == 8
    # Exponential envelope: late delays dominate early ones and
    # everything respects the 2 s cap.
    assert max(delays[4:]) > max(delays[:2])
    assert all(0.0 <= d <= 2.5 for d in delays)
    # A real job resets the streak (as the job cycles do).
    client._nojob_streak = 0
    client._nojob_backoff()
    assert delays[-1] <= delays[3] * 2  # back to the small end
    # An hour-plus idle streak must not overflow factor**attempt —
    # the delay just saturates at the cap.
    assert 0.0 < client.nojob_policy.delay(10_000) <= 2.6


# -- multi-tick jobs -------------------------------------------------------

def test_multi_tick_jobs_train_and_account():
    """--job-ticks: jobs carry K same-class minibatches run as one
    scan-block dispatch; epoch/decision accounting matches the
    single-tick path and training converges."""
    proto = dict(DELTA_PROTO, ticks=4)
    master = _mnist_pair(31, max_epochs=3)
    workers = {"w1": _mnist_pair(31, max_epochs=3),
               "w2": _mnist_pair(31, max_epochs=3)}
    _drive(master, workers, proto)
    assert master.decision.epoch_number == 3
    assert bool(master.decision.complete)
    assert master.decision.min_validation_err < 0.25
    # All inflight accounting drained.
    assert master.total_inflight_jobs() == 0
    assert not master.loader._pending_indices_


def test_multi_tick_block_stays_in_one_class():
    """A job's ticks never straddle a class or epoch boundary — the
    (epoch, class) accounting bucket is per job."""
    master = _mnist_pair(33, max_epochs=5)
    master.note_slave_protocol("w1", dict(DELTA_PROTO, ticks=1000))
    seen_classes = []
    for _ in range(6):
        job = master.generate_data_for_slave("w1")
        blk = job["MnistLoader"]["block"]
        classes = numpy.unique(blk["classes"])
        assert len(classes) == 1  # one class per block
        seen_classes.append(int(classes[0]))
        assert blk["indices"].ndim == 2
        assert blk["indices"].shape[0] == blk["mask"].shape[0]
        master.loader.apply_data_from_slave(None, "w1")
        master._inflight_by_slave_.clear()
        master._inflight_count_.clear()
    # A huge tick budget still walks validation THEN train.
    assert 1 in seen_classes and 2 in seen_classes


def test_multi_tick_drop_requeues_every_tick():
    """Dropping a worker with an in-flight multi-tick job requeues
    ALL of its minibatches (the failed-minibatch retry queue), not
    just the last one."""
    master = _mnist_pair(35, max_epochs=5)
    master.note_slave_protocol("w1", dict(DELTA_PROTO, ticks=4))
    job = master.generate_data_for_slave("w1")
    served = job["MnistLoader"]["block"]["indices"].shape[0]
    assert served > 1
    assert not master.loader.failed_minibatches
    master.drop_slave("w1")
    assert len(master.loader.failed_minibatches) == served
    # The requeued indices are exactly the served ones.
    requeued = numpy.sort(numpy.concatenate(
        [idx for idx, _cls in master.loader.failed_minibatches]))
    mask = job["MnistLoader"]["block"]["mask"]
    shipped = numpy.sort(numpy.concatenate([
        row[:int(m.sum())] for row, m in
        zip(job["MnistLoader"]["block"]["indices"], mask)]))
    numpy.testing.assert_array_equal(requeued, shipped)


def test_web_status_comms_row():
    """Heartbeats carrying a comms section render a comms row (and a
    jobs/s worker column) on the dashboard."""
    from veles_tpu.web_status import WebStatusServer
    srv = WebStatusServer(host="127.0.0.1", port=0).start()
    try:
        srv.update({"id": "m1", "workflow": "Wf", "mode": "master",
                    "comms": {"net.bytes_sent": 12345,
                              "net.serialize_us": 99},
                    "slaves": {"w/1": {"state": "WORK",
                                       "jobs_done": 7,
                                       "jobs_per_s": 3.5}}})
        page = srv.render_page()
        assert "comms" in page and "net.bytes_sent" in page
        assert "12345" in page
        assert "jobs/s" in page and "3.5" in page
    finally:
        srv.stop()


# -- bytes-per-job micro-bench (satellite: CI gate) ------------------------

def _loopback_run(seed, epochs, legacy, job_ticks=1):
    """Master + 2 in-process workers over real sockets; returns
    (wire_bytes, seconds, jobs) for the run."""
    old_ticks = root.common.net.job_ticks
    root.common.net.job_ticks = job_ticks
    try:
        master = _mnist_pair(seed, max_epochs=epochs,
                             gradient_moment=0.0,
                             learning_rate=0.03)
        server = Server(":0", master)
        addr = "127.0.0.1:%d" % server.port
        resilience.stats.reset()  # count this run's wire traffic only
        t0 = time.time()
        clients, threads = [], []
        for _ in range(2):
            slave = _mnist_pair(seed, max_epochs=epochs,
                                gradient_moment=0.0,
                                learning_rate=0.03)
            client = Client(addr, slave, net_legacy=legacy)
            clients.append(client)
            t = threading.Thread(target=client.run, daemon=True)
            t.start()
            threads.append(t)
        server.wait(timeout=240)
        for t in threads:
            t.join(timeout=10)
        seconds = time.time() - t0
        assert not server.is_running
        # Departed workers stay reportable: every worker has said bye
        # by now, yet the exit throughput report must still see them.
        # Counted by the sessions that trained: at an orderly finish
        # only the handler that sees it says bye, the other worker
        # redials and is a third session that is handed nothing.
        assert sum(1 for d in server.all_slaves.values()
                   if d.jobs_done) == 2
        assert sum(d.jobs_done
                   for d in server.all_slaves.values()) == \
            sum(c.jobs_done for c in clients)
        # Pipelined serving can overshoot by one epoch before the
        # decision's complete flag reaches the server — normalize by
        # what actually ran rather than flaking on the race.
        epochs_done = master.decision.epoch_number
        assert epochs_done >= epochs
        snap = resilience.stats.snapshot()
        # Sent counters only: recv mirrors them (same loopback wire),
        # and counting both would just double everything.
        return (snap.get("net.bytes_sent", 0), seconds,
                sum(c.jobs_done for c in clients), epochs_done)
    finally:
        root.common.net.job_ticks = old_ticks


def test_bytes_per_job_micro_bench():
    """The CI perf gate (tier-1 fast): deltas + tensor framing +
    multi-tick jobs must cut wire bytes for the SAME training volume
    (2 epochs, tiny MLP, 2 workers) by ≥5× vs. the legacy
    full-pickled-weights path, normalized per minibatch trained
    (one legacy job = one minibatch)."""
    epochs = 2
    legacy_bytes, legacy_s, legacy_jobs, legacy_ep = _loopback_run(
        77, epochs, legacy=True)
    delta_bytes, delta_s, delta_jobs, delta_ep = _loopback_run(
        77, epochs, legacy=False, job_ticks=8)
    assert legacy_jobs > 0 and delta_jobs > 0
    # Identical dataset → identical minibatch count per epoch; the
    # legacy run's jobs ARE its ticks.  Normalizing per epoch keeps
    # the gate honest when a run overshoots by one epoch.
    ticks_per_epoch = legacy_jobs / legacy_ep
    legacy_per_tick = legacy_bytes / (ticks_per_epoch * legacy_ep)
    delta_per_tick = delta_bytes / (ticks_per_epoch * delta_ep)
    ratio = legacy_per_tick / max(delta_per_tick, 1e-9)
    master_loader = _mnist_pair(77, max_epochs=1).loader
    samples = master_loader.total_samples
    print("\nnet micro-bench (%.0f ticks/epoch): legacy %.1f KiB "
          "(%.2f KiB/tick, %.0f img/s) vs delta+framing+%d-tick "
          "%.1f KiB (%.2f KiB/tick, %.0f img/s) -> %.1fx fewer "
          "wire bytes per minibatch" % (
              ticks_per_epoch, legacy_bytes / 1024.0,
              legacy_per_tick / 1024.0,
              legacy_ep * samples / legacy_s, 8,
              delta_bytes / 1024.0, delta_per_tick / 1024.0,
              delta_ep * samples / delta_s, ratio))
    assert ratio >= 5.0, (
        "wire bytes per minibatch shrank only %.2fx (legacy %d B / "
        "%d epochs, delta %d B / %d epochs)" % (
            ratio, legacy_bytes, legacy_ep, delta_bytes, delta_ep))


def test_pipelined_pending_tracking_keeps_every_job():
    """The old single-slot pending map lost all but the last
    in-flight job of a pipelined worker; now every job's ticks are
    tracked and requeued on drop."""
    master = _mnist_pair(37, max_epochs=5)
    master.note_slave_protocol("w1", DELTA_PROTO)
    for _ in range(3):  # pipelined: three jobs in flight
        master.generate_data_for_slave("w1")
    assert len(master.loader._pending_indices_["w1"]) == 3
    master.drop_slave("w1")
    assert len(master.loader.failed_minibatches) == 3
