"""Message transport: length-prefixed pickle frames over unix sockets.

This is the local-node control-plane transport (analog of the reference's
gRPC layer, src/ray/rpc/).  Every client (driver or worker) keeps ONE
connection to its node service; replies are matched to requests by id, and
unsolicited pushes (task execution requests) are routed to a handler —
mirroring how the reference multiplexes PushTask onto core-worker gRPC
streams.

Chaos hooks replicate the reference's RAY_testing_rpc_failure /
RAY_testing_asio_delay_us env-driven fault injection (src/ray/rpc/
rpc_chaos.h:23, ray_config_def.h:833-841).  The injector itself lives in
_private/chaos.py (seeded, re-resolvable schedule); this layer holds the
hook points plus the rpc retry that absorbs injected pre-send failures
— the analog of the reference's gRPC-level retry on transient errors.
"""

from __future__ import annotations

import collections
import pickle
import socket
import struct
import threading
import time
from typing import Any, Callable, Dict, Optional

_LEN = struct.Struct("<Q")

# Pre-send failures (chaos-injected errors/drops) are retried this many
# times with exponential backoff before surfacing to the caller.
_RPC_RETRY_ATTEMPTS = 3
_RPC_RETRY_BASE_S = 0.01


class ConnectionLost(Exception):
    pass


# Re-exported singleton: the seeded chaos schedule (kept under the old
# `protocol.chaos` name for existing imports).  Imported AFTER
# ConnectionLost is defined — chaos.py raises it via a lazy import.
from ray_tpu._private.chaos import chaos  # noqa: E402


def _chaos_gate(msg_type: str, one_way: bool) -> bool:
    """Run the chaos hook with pre-send retry.

    Request/reply rpcs treat an injected drop like the reference treats
    a lost request — a (simulated) timeout absorbed by the retry loop.
    One-way notifies return True ("drop this message"): lossy by
    design, recovery belongs to a higher layer.  Raises ConnectionLost
    when injected failures out-budget the retry."""
    for attempt in range(_RPC_RETRY_ATTEMPTS + 1):
        try:
            action = chaos.maybe_inject(msg_type)
        except ConnectionLost:
            if attempt >= _RPC_RETRY_ATTEMPTS:
                raise
            time.sleep(_RPC_RETRY_BASE_S * (2 ** attempt))
            continue
        if action == "drop":
            if one_way:
                return True
            if attempt >= _RPC_RETRY_ATTEMPTS:
                raise ConnectionLost(
                    f"chaos: dropped rpc {msg_type}")
            time.sleep(_RPC_RETRY_BASE_S * (2 ** attempt))
            continue
        return False
    return False


# Frames below this size still concatenate header+payload (one syscall
# beats one tiny copy); larger payloads are sent as header then payload
# so the full-frame copy never happens.
_SEND_CONCAT_MAX = 64 * 1024


def send_msg(sock: socket.socket, msg: Any, lock: Optional[threading.Lock] = None) -> None:
    data = pickle.dumps(msg, protocol=5)
    header = _LEN.pack(len(data))
    # The caller-passed lock IS this connection's dedicated send
    # lock: holding it across sendall is its entire purpose (frame
    # interleaving corrupts the wire), hence the RT011 suppressions.
    if len(data) <= _SEND_CONCAT_MAX:
        frame = header + data
        if lock:
            with lock:
                sock.sendall(frame)  # ray-tpu: noqa[RT011]
        else:
            sock.sendall(frame)
        return
    if lock:
        with lock:
            sock.sendall(header)  # ray-tpu: noqa[RT011]
            sock.sendall(data)  # ray-tpu: noqa[RT011]
    else:
        sock.sendall(header)
        sock.sendall(data)


def _recv_exact(sock: socket.socket, n: int) -> bytes:
    chunks = []
    while n:
        try:
            chunk = sock.recv(min(n, 4 << 20))
        except (ConnectionResetError, OSError) as e:
            raise ConnectionLost(str(e)) from e
        if not chunk:
            raise ConnectionLost("socket closed")
        chunks.append(chunk)
        n -= len(chunk)
    return b"".join(chunks)


def recv_msg(sock: socket.socket) -> Any:
    (length,) = _LEN.unpack(_recv_exact(sock, _LEN.size))
    return pickle.loads(_recv_exact(sock, length))


# ---------------------------------------------------------------------------
# binary object-transfer plane (reference: object_manager.h chunked
# pushes over dedicated channels).  No pickle anywhere on this path:
# requests and reply headers are fixed-layout structs and chunk payloads
# stream straight between the holder's mmap and the fetcher's
# pre-allocated shm buffer (recv_into).
#
#   request  (fetcher -> holder):  magic 'RTX1', object_id[16],
#                                  u64 offset, u64 length
#   response (holder -> fetcher):  u64 offset, u64 length, payload[length]
#
# One connection serves requests strictly in order, so the fetcher keeps
# a window of outstanding requests and matches replies FIFO.  length ==
# TRANSFER_ERR signals "not servable here" (object gone / truncated) and
# carries no payload.
# ---------------------------------------------------------------------------
TRANSFER_MAGIC = b"RTX1"
TRANSFER_REQ = struct.Struct("<4s16sQQ")
# Request body after the 4-byte magic (the serve loop peeks the magic
# first to tell chunk requests from channel-stream openings).
TRANSFER_REQ_BODY = struct.Struct("<16sQQ")
TRANSFER_RESP = struct.Struct("<QQ")
TRANSFER_ERR = (1 << 64) - 1

# ---------------------------------------------------------------------------
# compiled-DAG channel streams over the same transfer listener.  A
# cross-node channel edge opens ONE persistent connection and promotes
# it with magic 'RTC1'; after the opening frame every item is one
# length-prefixed write answered by an 8-byte ack (the ack doubles as
# per-item flow control: the receiver withholds it while the bounded
# destination queue is full).  No pickle framing, no control-plane
# dispatch — a cross-node hop costs one socket write.
#
#   open (sender -> receiver): magic 'RTC1', u16 key_len, u64 cap,
#                              key[key_len]
#   item (sender -> receiver): u64 length, payload[length]
#   ack  (receiver -> sender): u64 status (0 = ok, 1 = closed)
# ---------------------------------------------------------------------------
CHAN_MAGIC = b"RTC1"
CHAN_OPEN = struct.Struct("<HQ")
CHAN_ITEM = struct.Struct("<Q")
CHAN_ACK = struct.Struct("<Q")
CHAN_ACK_OK = 0
CHAN_ACK_CLOSED = 1


def recv_exact_into(sock: socket.socket, view: memoryview) -> None:
    """Fill `view` completely from the socket (zero-copy receive)."""
    got = 0
    n = len(view)
    while got < n:
        try:
            r = sock.recv_into(view[got:], n - got)
        except (ConnectionResetError, OSError) as e:
            raise ConnectionLost(str(e)) from e
        if not r:
            raise ConnectionLost("socket closed mid-transfer")
        got += r


class Connection:
    """A request/reply + push connection over a unix socket.

    Thread-safe: any thread may `call` (blocking RPC) or `notify`
    (one-way); a dedicated receiver thread routes replies by request id
    and hands pushes to `push_handler`.
    """

    def __init__(self, sock: socket.socket,
                 push_handler: Optional[Callable[[dict], None]] = None,
                 on_disconnect: Optional[Callable[[], None]] = None) -> None:
        self._sock = sock
        # Re-entrant: a finalizer that sends may run INSIDE this thread's own
        # send (see `_send`); `_sending` and `_deferred` are touched under
        # the lock only.
        self._send_lock = threading.RLock()
        self._sending = False
        self._deferred: "collections.deque[dict]" = collections.deque()
        self._push_handler = push_handler
        self._on_disconnect = on_disconnect
        self._pending: Dict[int, "_Waiter"] = {}
        self._pending_lock = threading.Lock()
        self._req_counter = 0
        self._closed = False
        self._recv_thread = threading.Thread(
            target=self._recv_loop, daemon=True, name="rtpu-conn-recv")
        self._recv_thread.start()

    def _next_req_id(self) -> int:
        with self._pending_lock:
            self._req_counter += 1
            return self._req_counter

    def _recv_loop(self) -> None:
        try:
            while True:
                msg = recv_msg(self._sock)
                rid = msg.get("__reply_to__")
                if rid is not None:
                    with self._pending_lock:
                        waiter = self._pending.pop(rid, None)
                    if waiter is not None:
                        waiter.set(msg)
                elif self._push_handler is not None:
                    self._push_handler(msg)
        except (ConnectionLost, pickle.UnpicklingError, EOFError):
            pass
        finally:
            self._closed = True
            with self._pending_lock:
                pending = list(self._pending.values())
                self._pending.clear()
            for w in pending:
                w.fail(ConnectionLost("connection to node service lost"))
            if self._on_disconnect:
                self._on_disconnect()

    def call(self, msg: dict, timeout: Optional[float] = None) -> dict:
        """Blocking request/reply."""
        _chaos_gate(msg.get("type", "?"), one_way=False)
        if self._closed:
            raise ConnectionLost("connection closed")
        rid = self._next_req_id()
        msg["__req_id__"] = rid
        waiter = _Waiter()
        with self._pending_lock:
            self._pending[rid] = waiter
        self._send(msg)
        reply = waiter.wait(timeout)
        if reply is None:
            with self._pending_lock:
                self._pending.pop(rid, None)
            raise TimeoutError(f"rpc {msg.get('type')} timed out")
        if isinstance(reply, Exception):
            raise reply
        err = reply.get("__error__")
        if err is not None:
            raise err if isinstance(err, Exception) else RuntimeError(err)
        return reply

    def notify(self, msg: dict) -> None:
        """One-way message (no reply expected)."""
        if _chaos_gate(msg.get("type", "?"), one_way=True):
            return      # chaos: message dropped on the floor
        self._send(msg)

    def _send(self, msg: dict) -> None:
        """One frame under the connection's send lock.  The collector may
        run a finalizer that sends (an actor handle's or an object ref's
        `__del__` -> `notify`) INSIDE this very thread's send, which holds
        the lock: waiting for a plain lock there never ends (it hung
        tests/test_dag_compiled.py for good, PR 34).  The lock is re-entrant
        and such a frame, which must not split the one in flight, is queued
        and goes out right after it: also when that one raised, and also
        when it was queued at the last instant, because the queue is looked
        at after `_sending` is cleared (from then on a finalizer sends for
        itself)."""
        with self._send_lock:
            if self._sending:
                self._deferred.append(msg)
                return
            self._sending = True
            try:
                send_msg(self._sock, msg)
            finally:
                self._sending = False
                while self._deferred:
                    self._send(self._deferred.popleft())

    def close(self) -> None:
        self._closed = True
        try:
            self._sock.shutdown(socket.SHUT_RDWR)
        except OSError:
            pass
        self._sock.close()
        # Join the recv thread (it wakes with ConnectionLost as soon
        # as the socket dies) — UNLESS close() is running ON it (an
        # on_disconnect callback closing its own connection), where a
        # join would self-deadlock.  An unjoined recv thread is the
        # RT014 class: it holds the fd's last reference and can fire
        # callbacks after the owner thinks the connection is gone.
        t = self._recv_thread
        if t is not threading.current_thread() and t.is_alive():
            t.join(timeout=2.0)


class _Waiter:
    __slots__ = ("_event", "_value")

    def __init__(self) -> None:
        self._event = threading.Event()
        self._value: Any = None

    def set(self, value: Any) -> None:
        self._value = value
        self._event.set()

    def fail(self, exc: Exception) -> None:
        self._value = exc
        self._event.set()

    def wait(self, timeout: Optional[float]) -> Any:
        if not self._event.wait(timeout):
            return None
        return self._value


def wake_and_join_acceptor(thread, family: int, addr,
                           join_timeout: float = 2.0) -> None:
    """Wake a thread blocked in accept() with a dummy connection and join
    it BEFORE closing the listener fd.  A thread left in accept()
    survives close(); when the fd number is reused by a later listener,
    an EINTR retry can make the stale thread steal and instantly drop the
    new listener's first connection."""
    try:
        # Context manager: a refused/raced connect must not leak the
        # dummy socket until GC (RT013 self-finding).
        with socket.socket(family, socket.SOCK_STREAM) as s:
            s.settimeout(1.0)
            s.connect(addr)
    except OSError:
        pass
    if thread is not None and thread.is_alive():
        thread.join(timeout=join_timeout)


def connect_uds(path: str, deadline_s: float = 10.0) -> socket.socket:
    start = time.time()
    while True:
        try:
            sock = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
            sock.connect(path)
            return sock
        except (FileNotFoundError, ConnectionRefusedError):
            if time.time() - start > deadline_s:
                raise
            time.sleep(0.02)


def connect_tcp(host: str, port: int, deadline_s: float = 10.0) -> socket.socket:
    start = time.time()
    while True:
        try:
            sock = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
            sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
            sock.connect((host, port))
            return sock
        except (ConnectionRefusedError, OSError):
            if time.time() - start > deadline_s:
                raise
            time.sleep(0.05)
