"""`Connection._send` against a finalizer that sends from inside a send.

The collector may run `ActorHandle.__del__` / `ObjectRef.__del__` (both
`conn.notify`) at any allocation, also while this very thread is inside
`send_msg` holding the connection's send lock: before PR 34 that waited for
its own lock for ever (tests/test_dag_compiled.py hung the suite so)."""

import socket
import threading

from ray_tpu._private import protocol


def _pair():
    a, b = socket.socketpair()
    conn = protocol.Connection(a)
    return conn, b


def test_a_finalizer_inside_a_send_is_queued_behind_it(monkeypatch):
    conn, peer = _pair()
    real, sent = protocol.send_msg, []

    def send_msg(sock, msg, lock=None):
        if msg["type"] == "outer":          # "the collector runs" mid-send
            conn.notify({"type": "from_finalizer"})
        sent.append(msg["type"])
        real(sock, msg, lock)

    monkeypatch.setattr(protocol, "send_msg", send_msg)
    done = threading.Event()

    def outer():
        conn.notify({"type": "outer"})
        done.set()

    threading.Thread(target=outer, daemon=True).start()
    assert done.wait(10), "a send from inside a send waited for its own lock"
    assert sent == ["outer", "from_finalizer"]
    got = [protocol.recv_msg(peer)["type"] for _ in range(2)]
    assert got == ["outer", "from_finalizer"]
    conn.notify({"type": "after"})          # the lock was given back
    assert protocol.recv_msg(peer)["type"] == "after"
    conn.close()
    peer.close()


def test_another_threads_send_waits_its_turn():
    conn, peer = _pair()
    threads = [threading.Thread(
        target=lambda i=i: [conn.notify({"type": "n", "i": i, "k": k})
                            for k in range(50)]) for i in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(10)
    got = [protocol.recv_msg(peer) for _ in range(200)]
    for i in range(4):          # every frame whole, each thread's in order
        assert [m["k"] for m in got if m["i"] == i] == list(range(50))
    conn.close()
    peer.close()


def test_a_send_that_raises_leaves_no_queued_frame_behind(monkeypatch):
    conn, peer = _pair()
    real = protocol.send_msg

    def send_msg(sock, msg, lock=None):
        if msg["type"] == "outer":
            conn.notify({"type": "from_finalizer"})
            raise ValueError("cannot be pickled")
        real(sock, msg, lock)

    monkeypatch.setattr(protocol, "send_msg", send_msg)
    try:
        conn.notify({"type": "outer"})
    except ValueError:
        pass
    else:
        raise AssertionError("the send's own error was swallowed")
    assert not conn._deferred and not conn._sending
    assert protocol.recv_msg(peer)["type"] == "from_finalizer"
    conn.close()
    peer.close()


def test_a_frame_queued_at_the_last_instant_still_goes_out(monkeypatch):
    """A finalizer that runs when the frame in flight has just gone out but
    `_sending` still stands queues its frame: the queue is looked at after
    `_sending` is cleared, so nothing is left for a send that may never
    come."""
    conn, peer = _pair()
    real = protocol.send_msg

    def send_msg(sock, msg, lock=None):
        real(sock, msg, lock)
        if msg["type"] == "outer":
            conn.notify({"type": "late"})

    monkeypatch.setattr(protocol, "send_msg", send_msg)
    conn.notify({"type": "outer"})
    assert not conn._deferred and not conn._sending
    assert [protocol.recv_msg(peer)["type"] for _ in range(2)] == \
        ["outer", "late"]
    conn.close()
    peer.close()
