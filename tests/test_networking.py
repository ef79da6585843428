"""Transport tests: framing (native C + Python fallback), PS service, and a
full async training round over the wire (reference parity:
distkeras/networking.py + SocketParameterServer, minus pickle)."""

import socket
import threading

import numpy as np
import pytest

from distkeras_tpu import networking as net
from distkeras_tpu.models import get_model
from distkeras_tpu.parameter_servers import DeltaParameterServer
from distkeras_tpu.trainers import ADAG
from distkeras_tpu.workers import DOWNPOURWorker

from tests.test_trainers import MODEL_KW, TRAIN_KW, synthetic_dataset


def _loopback_pair():
    srv = socket.socket()
    srv.bind(("127.0.0.1", 0))
    srv.listen(1)
    cli = socket.create_connection(srv.getsockname())
    conn, _ = srv.accept()
    srv.close()
    return cli, conn


@pytest.mark.parametrize("use_native", [True, False])
def test_frame_roundtrip(use_native, monkeypatch):
    if use_native:
        if not net.native_transport_active():
            pytest.skip("no C compiler for native transport")
    else:
        monkeypatch.setattr(net, "_native", False)
    cli, srv = _loopback_pair()
    try:
        payloads = [b"", b"x", b"hello" * 1000, np.random.bytes(1 << 20)]
        for p in payloads:
            net.send_frame(cli, p)
        for p in payloads:
            assert net.recv_frame(srv) == p
        # pytree message round-trip
        tree = {"w": np.arange(12, dtype=np.float32).reshape(3, 4),
                "b": {"c": np.ones(5, dtype=np.float64)}}
        net.send_msg(cli, tree)
        back = net.recv_msg(srv)
        np.testing.assert_array_equal(back["w"], tree["w"])
        np.testing.assert_array_equal(back["b"]["c"], tree["b"]["c"])
    finally:
        cli.close()
        srv.close()


def test_remote_parameter_server_pull_commit():
    center = {"w": np.zeros(4, dtype=np.float32)}
    ps = DeltaParameterServer(center)
    svc = net.ParameterServerService(ps, host="127.0.0.1")
    svc.start()
    try:
        remote = net.RemoteParameterServer("127.0.0.1", svc.port)
        np.testing.assert_array_equal(remote.pull()["w"], np.zeros(4))
        remote.commit({"w": np.ones(4, dtype=np.float32)}, worker=0)
        np.testing.assert_array_equal(remote.pull()["w"], np.ones(4))
        assert remote.num_updates == 1
        remote.close()
    finally:
        svc.stop()


def test_async_training_over_the_wire():
    """Full ADAG run where workers talk to the PS through the TCP transport
    instead of in-process calls — the multi-host (DCN) topology on
    loopback."""
    ds = synthetic_dataset(n=1024, partitions=2)
    model_def = get_model("mlp", **MODEL_KW)

    # host 0: owns the center
    import jax, jax.numpy as jnp

    sample = jnp.asarray(ds.partition(0)["features"][:1])
    params = model_def.init(jax.random.PRNGKey(0), sample)
    from distkeras_tpu.parameter_servers import ADAGParameterServer

    ps = ADAGParameterServer(params, num_workers=2)
    svc = net.ParameterServerService(ps, host="127.0.0.1")
    svc.start()
    try:
        # "host 1": contributes workers over the wire
        trainer = ADAG(
            model_def, params=params, num_workers=2, communication_window=4,
            remote_ps=("127.0.0.1", svc.port),
            **dict(TRAIN_KW, num_epoch=2),
        )
        model = trainer.train(ds, shuffle=True)
        assert ps.num_updates > 0
        from tests.test_trainers import eval_accuracy

        assert eval_accuracy(model, ds) > 0.85
    finally:
        svc.stop()


def test_determine_host_address():
    addr = net.determine_host_address()
    socket.inet_aton(addr)  # parses as IPv4


def test_ps_method_error_returns_error_reply_and_keeps_serving():
    """ADVICE r1: an op that raises on the PS (e.g. pull_with_clock on a
    non-DynSGD server) must produce an {"error": ...} reply, not a dropped
    connection; the same connection keeps working afterwards."""
    import pytest

    center = {"w": np.zeros(4, dtype=np.float32)}
    ps = DeltaParameterServer(center)
    svc = net.ParameterServerService(ps, host="127.0.0.1")
    svc.start()
    try:
        remote = net.RemoteParameterServer("127.0.0.1", svc.port)
        with pytest.raises(RuntimeError, match="AttributeError"):
            remote.pull_with_clock()  # DeltaParameterServer has no clock
        # connection survived the error
        np.testing.assert_array_equal(remote.pull()["w"], np.zeros(4))
        remote.close()
    finally:
        svc.stop()


def test_auth_handshake_required_when_secret_set():
    center = {"w": np.zeros(2, dtype=np.float32)}
    ps = DeltaParameterServer(center)
    svc = net.ParameterServerService(ps, host="127.0.0.1", secret="s3kr1t")
    svc.start()
    try:
        import pytest

        bad = net.RemoteParameterServer("127.0.0.1", svc.port)
        with pytest.raises((ConnectionError, RuntimeError)):
            bad.pull()  # no secret -> rejected
        good = net.RemoteParameterServer("127.0.0.1", svc.port, secret="s3kr1t")
        np.testing.assert_array_equal(good.pull()["w"], np.zeros(2))
        good.close()
    finally:
        svc.stop()


def test_oversized_frame_rejected():
    """The 8-byte length header must not be able to demand an unbounded
    allocation (ADVICE r1)."""
    import pytest
    import socket as socket_mod
    import struct

    srv = socket_mod.socket()
    srv.bind(("127.0.0.1", 0))
    srv.listen(1)
    cli = socket_mod.create_connection(srv.getsockname())
    conn, _ = srv.accept()
    try:
        cli.sendall(struct.pack(">Q", 1 << 62))
        with pytest.raises(ConnectionError, match="exceeds"):
            net.recv_frame(conn, max_bytes=1 << 20)
    finally:
        cli.close()
        conn.close()
        srv.close()


def _drip(sock, data, sizes):
    """``data`` in writes of the given sizes (the last takes the rest)."""
    at = 0
    for n in list(sizes) + [len(data)]:
        if at >= len(data):
            break
        sock.sendall(data[at:at + n])
        at += n


@pytest.mark.parametrize("case", [
    "one_write", "split_in_a_header", "split_in_a_payload", "a_large_frame",
    "eof_before_a_header", "eof_in_a_frame", "over_max_bytes",
    "a_fault_rule_counts_frames"])
def test_messages_sent_and_read_in_bulk(case):
    """``send_msgs`` writes the frames ``send_msg`` would, back to back,
    and ``MsgReader`` hands out every whole frame a ``recv`` holds: the
    same messages in the same order wherever the stream is cut, and the
    contract of ``recv_msg`` at its ends (``None`` on a clean EOF, a
    ``FrameError`` for an EOF inside a frame or a frame over the
    limit); under an armed fault injector both go frame by frame, so
    that a rule's ``nth`` still counts frames."""
    import struct

    from flax import serialization

    msgs = [{"id": i, "t": 1000 + i} for i in range(40)] + [{"ok": 1}]
    cli, srv = _loopback_pair()
    try:
        reader = net.MsgReader(srv, max_bytes=1 << 22)
        if case == "one_write":
            before = net._SENT_FRAMES.value
            net.send_msgs(cli, msgs)
            assert net._SENT_FRAMES.value == before + len(msgs)
            got = reader.recv_msgs()
            # what one recv held came out together
            assert len(got) > 1
            while len(got) < len(msgs):
                got += reader.recv_msgs()
            assert got == msgs
            # a reader of single frames reads the same bytes
            net.send_msgs(cli, msgs[:3])
            assert [net.recv_msg(srv) for _ in range(3)] == msgs[:3]
        elif case in ("split_in_a_header", "split_in_a_payload"):
            data = b"".join(
                struct.pack(">Q", len(p)) + p
                for p in map(serialization.msgpack_serialize, msgs))
            first = len(serialization.msgpack_serialize(msgs[0])) + 8
            cut = first + 3 if case == "split_in_a_header" else first + 11
            t = threading.Thread(target=_drip, args=(cli, data, [cut, 1, 2]))
            t.start()
            got = []
            while len(got) < len(msgs):
                got += reader.recv_msgs()
            t.join()
            assert got == msgs
        elif case == "a_large_frame":
            big = {"blob": np.random.bytes(3 << 20)}
            t = threading.Thread(target=net.send_msgs,
                                 args=(cli, [msgs[0], big, msgs[1]]))
            t.start()
            got = []
            while len(got) < 3:
                got += reader.recv_msgs()
            t.join()
            assert got[0] == msgs[0] and got[2] == msgs[1]
            assert got[1]["blob"] == big["blob"]
        elif case == "eof_before_a_header":
            net.send_msgs(cli, msgs[:2])
            cli.close()
            got = []
            while len(got) < 2:
                got += reader.recv_msgs()
            assert got == msgs[:2] and reader.recv_msgs() is None
        elif case == "eof_in_a_frame":
            cli.sendall(struct.pack(">Q", 100) + b"half")
            cli.close()
            with pytest.raises(net.FrameError, match="truncated"):
                reader.recv_msgs()
        elif case == "over_max_bytes":
            cli.sendall(struct.pack(">Q", 1 << 40))
            with pytest.raises(net.FrameError, match="exceeds") as e:
                reader.recv_msgs()
            assert e.value.limit == 1 << 22
        else:
            injector = net.FaultInjector()
            rule = injector.rule("drop", direction="send", nth=3)
            net.install_fault_injector(injector)
            try:
                net.send_msgs(cli, msgs[:5])
                got = []
                while len(got) < 4:
                    part = reader.recv_msgs()
                    assert len(part) == 1  # frame by frame while armed
                    got += part
            finally:
                net.uninstall_fault_injector()
            assert got == msgs[:2] + msgs[3:5] and rule.fired == 1
    finally:
        cli.close()
        srv.close()
