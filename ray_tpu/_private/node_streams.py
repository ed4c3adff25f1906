"""Streaming generators + the compiled-DAG channel plane.

Mixin split out of node_service.py (reference: streaming generator
returns in core_worker task_manager; channels
experimental/channel/shared_memory_channel.py).  Shares NodeService's
state and lock; see node_objects.py for the split rationale.
"""

from __future__ import annotations

import queue
import threading
import time
from collections import deque
from typing import Any, Callable, Dict, List, Optional, Tuple

from ray_tpu._private.chaos import chaos
from ray_tpu._private.node_state import (
    FAILED, READY, _ConnCtx)


class StreamChannelMixin:
    # -- streaming generators (reference: streaming generator returns) --
    def _stream_rec(self, stream_id: bytes) -> dict:
        rec = self._streams.get(stream_id)
        if rec is None:
            rec = {"items": [], "done": False, "released": False,
                   "waiters": [], "dropped_upto": 0}
            self._streams[stream_id] = rec
        return rec

    def _advance_stream(self, rec: dict, upto: int) -> None:
        """Drop the stream's creation pins for items the consumer has
        moved past.  Safe ordering: the consumer's borrow add_ref for
        item i is notified on the same connection BEFORE its
        stream_next(i+1), so by the time we process that call the
        borrow is counted.  Keeps store usage O(in-flight), not
        O(total items streamed).  Caller holds the lock."""
        upto = min(upto, len(rec["items"]))
        for pos in range(rec["dropped_upto"], upto):
            self._decref(rec["items"][pos])
        rec["dropped_upto"] = max(rec["dropped_upto"], upto)

    def _h_stream_yield(self, ctx: _ConnCtx, m: dict) -> None:
        oid, loc, data, size, embedded = m["item"]
        with self.lock:
            self._register_object(oid, loc, data, size,
                                  embedded=embedded, creator_pid=ctx.pid)
            rec = self._stream_rec(m["stream_id"])
            if rec["released"]:
                # Consumer is gone but the task still produces: drop the
                # item's creation pin immediately or it leaks forever.
                self._decref(oid)
            else:
                rec["items"].append(oid)
                self._fire_stream_waiters(rec)
            self._schedule()

    def _fire_stream_waiters(self, rec: dict) -> None:
        """Answer parked stream_next calls that can now be satisfied.
        Caller holds the lock."""
        still = []
        for idx, ctx, msg in rec["waiters"]:
            if idx < len(rec["items"]):
                ctx.reply(msg, {"status": "item",
                                "object_id": rec["items"][idx]})
            elif rec["done"]:
                ctx.reply(msg, {"status": "end"})
            else:
                still.append((idx, ctx, msg))
        rec["waiters"] = still

    def finish_stream(self, stream_id: bytes) -> None:
        """Completion object resolved (success or failure): wake every
        parked consumer.  Caller holds the lock."""
        rec = self._streams.get(stream_id)
        if rec is None:
            return
        rec["done"] = True
        self._fire_stream_waiters(rec)
        if rec["released"]:
            self._streams.pop(stream_id, None)

    def _h_stream_next(self, ctx: _ConnCtx, m: dict) -> None:
        """Parked reply (no busy-poll): the answer goes out when the
        item arrives or the stream finishes."""
        home = self._remote_streams.get(m["stream_id"])
        if home is not None and home != self.node_id:
            self._proxy_stream_rpc(ctx, m, home)
            return
        with self.lock:
            rec = self._streams.get(m["stream_id"])
            idx = m["index"]
            if rec is not None:
                # Asking for item idx means items < idx are consumed.
                self._advance_stream(rec, idx)
            if rec is not None and idx < len(rec["items"]):
                ctx.reply(m, {"status": "item",
                              "object_id": rec["items"][idx]})
                return
            done = rec["done"] if rec is not None else False
            if not done:
                e = self.objects.get(m["stream_id"])
                done = e is not None and e.state in (READY, FAILED)
            if done:
                ctx.reply(m, {"status": "end"})
                return
            self._stream_rec(m["stream_id"])["waiters"].append(
                (idx, ctx, m))

    def _proxy_stream_rpc(self, ctx: _ConnCtx, m: dict, home: bytes,
                          oneway: bool = False) -> None:
        """Forward a stream_next/stream_release for a REMOTE actor's
        stream to its home node on a side thread (the home parks the
        stream_next reply until the item lands; blocking this
        connection's dispatch would stall the consumer's other rpcs).
        stream_release is fire-and-forget on both hops."""
        def fwd() -> None:
            ninfo = self._node_info(home)
            wire = {k: v for k, v in m.items()
                    if not k.startswith("__")}
            if ninfo is None:
                # Home node gone: "end" is correct — the completion
                # object's failure (node-death recovery) carries the
                # error to the consumer.
                rep = {"status": "end"}
            elif oneway:
                try:
                    self._peer_conn_to(ninfo).notify(wire)
                except Exception:
                    pass
                return
            else:
                while True:
                    try:
                        rep = self._peer_conn_to(ninfo).call(
                            wire, timeout=60.0)
                        break
                    except TimeoutError:
                        # Slow producer (long gap between yields): keep
                        # waiting, matching the local path's indefinite
                        # park — never truncate the stream silently.
                        if self._shutdown:
                            return
                        continue
                    except Exception:
                        rep = {"status": "end"}
                        break
            try:
                ctx.reply(m, rep)
            except Exception:
                pass

        threading.Thread(target=fwd, daemon=True,
                         name="rtpu-stream-proxy").start()

    def _h_stream_release(self, ctx: _ConnCtx, m: dict) -> None:
        """Consumer dropped its generator: release the stream's item
        holds (each item was born with the creation pin).  A tombstone
        stays until the producing task completes so late yields are
        dropped instead of resurrecting the record."""
        home = self._remote_streams.pop(m["stream_id"], None)
        if home is not None and home != self.node_id:
            self._proxy_stream_rpc(ctx, m, home, oneway=True)
            return
        with self.lock:
            rec = self._streams.get(m["stream_id"])
            if rec is None:
                rec = self._stream_rec(m["stream_id"])
            for oid in rec["items"][rec["dropped_upto"]:]:
                self._decref(oid)
            rec["items"] = []
            rec["dropped_upto"] = 0
            rec["released"] = True
            rec["waiters"] = []
            done = rec["done"]
            if not done:
                # A stream that never recorded completion (e.g. zero
                # yields, or failure before the first yield): consult
                # the completion object so the tombstone doesn't leak.
                e = self.objects.get(m["stream_id"])
                done = e is not None and e.state in (READY, FAILED)
            if done:
                self._streams.pop(m["stream_id"], None)

    # -- compiled-DAG channel plane (cross-node channels) ---------------
    # Reference: python/ray/experimental/channel/shared_memory_channel.py
    # (cross-process channels) + dag/collective_node.py.  Queues are
    # keyed cluster-wide and live on the consumer's node; a producer on
    # another node chan_sends through its local node, which forwards
    # over the persistent peer connection.  Backpressure = parked
    # replies once `cap` items are queued.
    def _dag_queue_rec(self, key: bytes, cap: int = 8) -> dict:
        rec = self._dag_queues.get(key)
        if rec is None:
            rec = {"items": deque(), "closed": False, "cap": cap,
                   "recv_waiters": [], "send_waiters": []}
            self._dag_queues[key] = rec
        return rec

    def _h_chan_send(self, ctx: _ConnCtx, m: dict) -> None:
        dst = m["dst"]
        if dst == self.node_id or not self.multinode:
            self._chan_deliver(ctx, m)
            return
        ninfo = self._node_info(dst)
        if ninfo is None:
            ctx.reply(m, {"ok": False, "closed": True,
                          "error": "destination node is gone"})
            return
        # One persistent forwarder per (destination, channel key): off
        # this connection's thread (a backpressured remote queue must
        # not stall its other RPCs), strictly FIFO per channel
        # (thread-per-message could reorder two sends racing onto the
        # shared peer connection), and NOT shared across channels — a
        # single per-destination forwarder would head-of-line-block
        # every channel to that node behind one backpressured queue
        # (deadlocking collectives whose consumer waits on a sibling
        # channel).  Threads exit after 60s idle.
        fkey = (dst, m["key"])
        with self._peer_lock:
            q = self._chan_fwd_queues.get(fkey)
            if q is None:
                q = queue.Queue()
                self._chan_fwd_queues[fkey] = q
                threading.Thread(target=self._chan_fwd_loop,
                                 args=(fkey, q), daemon=True,
                                 name="rtpu-chan-fwd").start()
        q.put((ctx, m, ninfo))

    def _chan_fwd_loop(self, fkey, q: "queue.Queue") -> None:
        """Per-(destination, key) forwarder.  Steady state rides a
        PERSISTENT streamed edge on the destination's binary transfer
        listener (protocol.CHAN_MAGIC framing): one raw socket write
        per item, answered by an 8-byte ack that doubles as
        backpressure — no per-item control-plane RPC, no pickle
        dispatch on the receiving node.  Falls back to the legacy
        chan_send peer RPC when the peer has no transfer listener or
        the stream breaks mid-edge."""
        dst, key = fkey
        stream = None       # persistent socket in channel-stream mode
        idle = 0
        while not self._shutdown:
            try:
                ctx, m, ninfo = q.get(timeout=0.5)
            except queue.Empty:
                idle += 1
                if idle > 120:        # ~60s idle: retire the thread
                    with self._peer_lock:
                        if q.empty():
                            self._chan_fwd_queues.pop(fkey, None)
                            self._chan_stream_close(stream)
                            return
                continue
            idle = 0
            rep = None
            if not chaos.partitioned(dst):
                if stream is None:
                    stream = self._chan_stream_open(ninfo, key,
                                                    m.get("cap", 8))
                if stream is not None:
                    rep = self._chan_stream_send(stream, m["payload"])
                    if rep is None:
                        # Transport failure MID-ITEM: delivery is
                        # ambiguous (the receiver may have enqueued
                        # the payload before the ack was lost).
                        # Channels are exactly-once-per-slot — a
                        # resend (streamed or via the RPC fallback)
                        # could deliver the item twice and silently
                        # desync every later row's pairing.  Fail the
                        # edge instead; the DAG layer surfaces it.
                        self._chan_stream_close(stream)
                        stream = None
                        rep = {"ok": False, "closed": True,
                               "error": "channel stream failed "
                                        "mid-item (delivery unknown)"}
                    elif rep.get("closed"):
                        self._chan_stream_close(stream)
                        stream = None
            if rep is None:
                # Legacy path: per-item peer RPC — only for peers
                # without a reachable transfer listener (nothing was
                # sent on a stream, so no duplication risk) and for
                # chaos partitions, so injected partitions surface as
                # ConnectionLost instead of silently bypassing.
                try:
                    rep = self._peer_conn_to(ninfo).call(
                        {"type": "chan_send", "dst": dst,
                         "key": m["key"], "payload": m["payload"],
                         "cap": m.get("cap", 8)}, timeout=120.0)
                    self._count_dag_item("rpc")
                except Exception as e:
                    rep = {"ok": False, "closed": True, "error": str(e)}
            try:
                ctx.reply(m, rep)
            except Exception:
                pass
        self._chan_stream_close(stream)

    # -- streamed cross-node channel edges (sender side) ----------------
    def _chan_stream_open(self, ninfo: dict, key: bytes, cap: int):
        """Open + promote one transfer-plane connection into a channel
        stream for `key`; returns the socket or None (no listener /
        connect failure — caller degrades to the RPC path)."""
        from ray_tpu._private.protocol import (CHAN_MAGIC, CHAN_OPEN,
                                               connect_tcp)
        if not self._streamable(ninfo):
            return None
        try:
            sock = connect_tcp(ninfo["host"], ninfo["transfer_port"],
                               deadline_s=5.0)
            # No ack deadline: under backpressure the receiver
            # legitimately withholds the ack for as long as the
            # consumer stalls.  Dead-peer reap comes from TCP
            # keepalive instead (see node_objects._enable_keepalive).
            sock.settimeout(None)
            from ray_tpu._private.node_objects import _enable_keepalive
            _enable_keepalive(sock)
            sock.sendall(CHAN_MAGIC + CHAN_OPEN.pack(len(key), cap)
                         + key)
            return sock
        except Exception:
            return None

    def _chan_stream_send(self, sock, payload) -> Optional[dict]:
        """One item over the streamed edge; returns the reply dict or
        None on a transport failure (caller retries / falls back).
        The send->ack round trip is the remote hop — observed into the
        dag hop histogram on this (sender) node."""
        from ray_tpu._private.protocol import (CHAN_ACK, CHAN_ACK_OK,
                                               CHAN_ITEM, _recv_exact)
        from ray_tpu.util.metrics import (DAG_HOP_BUCKETS,
                                          DAG_HOP_SECONDS_METRIC)
        try:
            t0 = time.perf_counter()
            sock.sendall(CHAN_ITEM.pack(len(payload)))
            sock.sendall(payload)
            (status,) = CHAN_ACK.unpack(
                _recv_exact(sock, CHAN_ACK.size))
        except Exception:
            return None
        if status != CHAN_ACK_OK:
            return {"ok": False, "closed": True}
        self._count_dag_item("stream")
        with self.lock:
            self._observe_hist(
                DAG_HOP_SECONDS_METRIC, {"edge": "remote"},
                time.perf_counter() - t0, DAG_HOP_BUCKETS,
                "compiled-DAG per-edge hop duration")
        return {"ok": True}

    @staticmethod
    def _chan_stream_close(sock) -> None:
        if sock is None:
            return
        try:
            sock.close()
        except OSError:
            pass

    def _count_dag_item(self, path: str) -> None:
        """Per-path cross-node channel item tally (stream vs rpc
        fallback) — surfaced in the state dump so tests and operators
        can verify the steady-state path stays off the control plane."""
        with self.lock:
            self._dag_items[path] = self._dag_items.get(path, 0) + 1

    # -- streamed cross-node channel edges (receiver side) --------------
    def _chan_stream_serve(self, sock) -> None:
        """Receiver half of a promoted channel-stream connection (the
        transfer accept loop hands over after reading CHAN_MAGIC):
        read length-prefixed items, deliver into the bounded dag queue,
        ack each item.  The ack is withheld while the queue is full —
        that parked ack is the cross-node backpressure."""
        from ray_tpu._private.protocol import (CHAN_ACK, CHAN_ACK_CLOSED,
                                               CHAN_ACK_OK, CHAN_ITEM,
                                               CHAN_OPEN, _recv_exact)
        klen, cap = CHAN_OPEN.unpack(_recv_exact(sock, CHAN_OPEN.size))
        key = _recv_exact(sock, klen)
        while not self._shutdown:
            (n,) = CHAN_ITEM.unpack(_recv_exact(sock, CHAN_ITEM.size))
            payload = _recv_exact(sock, n)
            # Stream-listener server telemetry: deliver time includes
            # any backpressure wait (the withheld ack) — exactly the
            # server-side latency an operator needs to see.
            t0 = time.perf_counter()
            ok = self._chan_stream_deliver(key, payload, max(cap, 1))
            self._rpc_record("chan_stream", time.perf_counter() - t0)
            sock.sendall(CHAN_ACK.pack(CHAN_ACK_OK if ok
                                       else CHAN_ACK_CLOSED))

    def _chan_stream_deliver(self, key: bytes, payload, cap: int) -> bool:
        """Deliver one streamed item into the dag queue, blocking while
        the queue is at capacity (the withheld ack blocks the sender).
        Returns False when the channel is closed."""
        while not self._shutdown:
            with self.lock:
                rec = self._dag_queue_rec(key, cap)
                rec["cap"] = cap
                if rec["closed"]:
                    return False
                while rec["recv_waiters"]:
                    w = rec["recv_waiters"].pop(0)
                    if not w["live"]:
                        continue
                    w["live"] = False
                    w["ctx"].reply(w["m"], {"ok": True,
                                            "payload": payload})
                    return True
                if len(rec["items"]) < rec["cap"]:
                    rec["items"].append(payload)
                    return True
            time.sleep(0.0005)
        return False

    def _chan_deliver(self, ctx: _ConnCtx, m: dict) -> None:
        with self.lock:
            rec = self._dag_queue_rec(m["key"], m.get("cap", 8))
            # The consumer's first recv creates the record with the
            # default cap; the producer carries the DAG's real
            # capacity — let it win.
            rec["cap"] = m.get("cap", rec["cap"])
            if rec["closed"]:
                ctx.reply(m, {"ok": False, "closed": True})
                return
            while rec["recv_waiters"]:
                w = rec["recv_waiters"].pop(0)
                if not w["live"]:
                    continue
                w["live"] = False
                w["ctx"].reply(w["m"], {"ok": True,
                                        "payload": m["payload"]})
                ctx.reply(m, {"ok": True})
                return
            if len(rec["items"]) >= rec["cap"]:
                rec["send_waiters"].append((ctx, m))
                return
            rec["items"].append(m["payload"])
            ctx.reply(m, {"ok": True})

    def _h_chan_recv(self, ctx: _ConnCtx, m: dict) -> None:
        with self.lock:
            rec = self._dag_queue_rec(m["key"])
            if rec["items"]:
                payload = rec["items"].popleft()
                # A freed slot admits one parked sender.
                if rec["send_waiters"]:
                    sctx, sm = rec["send_waiters"].pop(0)
                    rec["items"].append(sm["payload"])
                    sctx.reply(sm, {"ok": True})
                ctx.reply(m, {"ok": True, "payload": payload})
                return
            if rec["closed"]:
                ctx.reply(m, {"ok": False, "closed": True})
                return
            waiter = {"ctx": ctx, "m": m, "live": True}
            rec["recv_waiters"].append(waiter)
            block_ms = m.get("block_ms")
            if block_ms is not None:
                # Node-side expiry: the reply ALWAYS comes from under
                # the lock — either an item, closed, or this timeout —
                # so a client that stops waiting never strands a parked
                # reply that would otherwise swallow a delivered item.
                def expire() -> None:
                    with self.lock:
                        if not waiter["live"]:
                            return
                        waiter["live"] = False
                        try:
                            rec["recv_waiters"].remove(waiter)
                        except ValueError:
                            pass
                    try:
                        ctx.reply(m, {"ok": False, "timeout": True})
                    except Exception:
                        pass

                self._add_deadline_waiter(
                    time.time() + block_ms / 1000.0, expire)

    def _h_chan_close(self, ctx: _ConnCtx, m: dict) -> None:
        dst = m["dst"]
        if dst is not None and dst != self.node_id and self.multinode:
            ninfo = self._node_info(dst)
            if ninfo is not None:
                try:
                    self._peer_conn_to(ninfo).call(
                        {"type": "chan_close", "dst": dst,
                         "key": m["key"]}, timeout=10.0)
                except Exception:
                    pass
            ctx.reply(m, {"ok": True})
            return
        with self.lock:
            rec = self._dag_queue_rec(m["key"])
            rec["closed"] = True
            rec["items"].clear()
            recvs = [w for w in rec["recv_waiters"] if w["live"]]
            for w in recvs:
                w["live"] = False
            sends = rec["send_waiters"]
            rec["recv_waiters"] = []
            rec["send_waiters"] = []
            for w in recvs:
                try:
                    w["ctx"].reply(w["m"], {"ok": False, "closed": True})
                except Exception:
                    pass
            for sctx, sm in sends:
                try:
                    sctx.reply(sm, {"ok": False, "closed": True})
                except Exception:
                    pass
        ctx.reply(m, {"ok": True})

    def _h_actor_node(self, ctx: _ConnCtx, m: dict) -> None:
        """Which node hosts this actor (compiled-DAG channel routing)."""
        aid = m["actor_id"]
        with self.lock:
            if aid in self.actors:
                ctx.reply(m, {"node_id": self.node_id})
                return
            home = self._actor_homes.get(aid)
        if home is None and self.multinode:
            try:
                home = self.gcs.get_actor_node(aid)
            except Exception:
                home = None
        ctx.reply(m, {"node_id": home if home is not None
                      else self.node_id})

    def _h_profile_event(self, ctx: _ConnCtx, m: dict) -> None:
        """Custom user span from ray_tpu.util.profiling.span()."""
        ev = dict(m["event"])
        # Worker spans don't know their node; events parked here by a
        # DIFFERENT node (a draining peer preserving its drain record)
        # already carry the originating node id — keep it.
        ev.setdefault("node_id", self.node_id.hex())
        self._emit_event(ev)

    def _h_timeline(self, ctx: _ConnCtx, m: dict) -> None:
        # Under the lock: _h_task_done registers a task's result (which
        # wakes its getter) and emits its lifecycle record in one hold,
        # so a caller that has seen the result also sees the record.
        with self.lock:
            events = list(self._events)
        if m.get("cluster") and self.multinode:
            replies, _ = self._fanout_peers({"type": "timeline",
                                             "cluster": False})
            for _, peer in replies:
                events.extend(peer["events"])
        ctx.reply(m, {"events": events})

    def _h_metrics_push(self, ctx: _ConnCtx, m: dict) -> None:
        """Merge a batch of metric series from a worker/driver process.
        Counters accumulate deltas, gauges keep the latest value,
        histograms merge bucket counts."""
        with self.lock:
            for s in m["series"]:
                key = (s["name"], s["kind"],
                       tuple(sorted(s.get("tags", {}).items())))
                cur = self._metrics.get(key)
                if cur is None:
                    cur = {"name": s["name"], "kind": s["kind"],
                           "tags": dict(s.get("tags", {})),
                           "value": 0.0, "buckets": {}, "sum": 0.0,
                           "count": 0.0,
                           "description": s.get("description", "")}
                    self._metrics[key] = cur
                if s["kind"] == "counter":
                    cur["value"] += s["value"]
                elif s["kind"] == "gauge":
                    cur["value"] = s["value"]
                else:  # histogram
                    for b, c in s.get("buckets", {}).items():
                        cur["buckets"][b] = cur["buckets"].get(b, 0) + c
                    cur["sum"] += s.get("sum", 0.0)
                    cur["count"] += s.get("count", 0.0)
        ctx.reply(m, {"ok": True})

    def _h_metrics_scrape(self, ctx: _ConnCtx, m: dict) -> None:
        """All aggregated series + built-in runtime gauges."""
        from ray_tpu.util.metrics import OBJECT_STORE_BYTES_METRIC
        with self.lock:
            series = [dict(v, buckets=dict(v["buckets"]))
                      for v in self._metrics.values()]
            builtin = {
                "ray_tpu_tasks_pending": float(len(self.pending_queue)),
                "ray_tpu_tasks_total": float(len(self.tasks)),
                "ray_tpu_actors_alive": float(
                    sum(1 for a in self.actors.values()
                        if a.state == "alive")),
                "ray_tpu_workers": float(len(self.workers)),
                "ray_tpu_objects_local": float(len(self.objects)),
            }
            # Memory-accounting gauges: object directory bytes by
            # reference kind (owned/borrowed/pinned_by_actor/spilled/
            # drain_replica) — the Prometheus face of memory_summary().
            for kind, cell in self._memory_kind_bytes_locked().items():
                series.append({
                    "name": OBJECT_STORE_BYTES_METRIC, "kind": "gauge",
                    "tags": {"kind": kind}, "value": cell["bytes"],
                    "buckets": {}, "sum": 0.0, "count": 0.0,
                    "description": "object directory bytes by "
                                   "reference kind"})
            # Control-plane WAL size (from the periodic gcs_status
            # poll): growth between saw-tooth compaction drops is the
            # durable-mutation rate, a flat high line means compaction
            # stopped firing.
            gst = getattr(self, "_gcs_status", None) or {}
            if gst.get("persistent"):
                from ray_tpu.util.metrics import GCS_WAL_BYTES_METRIC
                series.append({
                    "name": GCS_WAL_BYTES_METRIC, "kind": "gauge",
                    "tags": {}, "value": float(gst.get("wal_bytes", 0)),
                    "buckets": {}, "sum": 0.0, "count": 0.0,
                    "description": "GCS write-ahead-log bytes"})
        stats = self._store().stats()
        builtin["ray_tpu_object_store_bytes_used"] = float(
            stats.get("used_bytes", 0))
        builtin["ray_tpu_object_store_capacity_bytes"] = float(
            stats.get("capacity_bytes", 0))
        for name, val in builtin.items():
            series.append({"name": name, "kind": "gauge", "tags": {},
                           "value": val, "buckets": {}, "sum": 0.0,
                           "count": 0.0,
                           "description": "ray_tpu runtime built-in"})
        series.extend(self._rpc_series())
        ctx.reply(m, {"series": series})

    def _rpc_series(self) -> list:
        """Control-plane RPC server telemetry as scrape series, built
        from the dispatch wrapper's per-method aggregates at scrape
        time — folding them into self._metrics would double-count
        across scrapes.  Includes the relay-backlog gauges and the GCS
        server's own per-op histograms (riding the periodic gcs_status
        poll, tagged method="gcs.<op>")."""
        from ray_tpu.util.metrics import (RPC_INFLIGHT_METRIC,
                                          RPC_QUEUE_DEPTH_METRIC,
                                          RPC_SERVER_SECONDS_METRIC,
                                          SLOW_RPC_METRIC)
        series: list = []
        with self._rpc_lock:
            for method, st in sorted(self._rpc_stats.items()):
                series.append({
                    "name": RPC_SERVER_SECONDS_METRIC,
                    "kind": "histogram", "tags": {"method": method},
                    "value": 0.0, "buckets": dict(st["buckets"]),
                    "sum": st["sum"], "count": float(st["count"]),
                    "description": "server-side control-plane RPC "
                                   "handler latency"})
                series.append({
                    "name": RPC_INFLIGHT_METRIC, "kind": "gauge",
                    "tags": {"method": method},
                    "value": float(st["inflight"]), "buckets": {},
                    "sum": 0.0, "count": 0.0,
                    "description": "control-plane RPC handlers "
                                   "currently executing"})
                if st["slow"]:
                    series.append({
                        "name": SLOW_RPC_METRIC, "kind": "counter",
                        "tags": {"method": method},
                        "value": float(st["slow"]), "buckets": {},
                        "sum": 0.0, "count": 0.0,
                        "description": "handlers flagged by the "
                                       "slow-RPC sentinel"})
        # Relay-backlog depth: items queued toward the GCS (per-conn
        # proxy queues), toward peers (task forwarders), and on
        # compiled-DAG channel forwarders — a growing backlog is the
        # control plane falling behind.
        with self.lock:
            gcs_depth = sum(
                c.gcs_q.qsize() for c in self._conns
                if getattr(c, "gcs_q", None) is not None)
            fwd_depth = sum(q.qsize()
                            for q in self._fwd_queues.values())
        with self._peer_lock:
            chan_depth = sum(q.qsize()
                             for q in self._chan_fwd_queues.values())
        for plane, depth in (("gcs_proxy", gcs_depth),
                             ("forward", fwd_depth),
                             ("chan_fwd", chan_depth)):
            series.append({
                "name": RPC_QUEUE_DEPTH_METRIC, "kind": "gauge",
                "tags": {"plane": plane}, "value": float(depth),
                "buckets": {}, "sum": 0.0, "count": 0.0,
                "description": "control-plane relay queue backlog"})
        # GCS server-side per-op latency (from the status poll).
        gst = getattr(self, "_gcs_status", None) or {}
        for op, st in sorted((gst.get("rpc") or {}).items()):
            series.append({
                "name": RPC_SERVER_SECONDS_METRIC, "kind": "histogram",
                "tags": {"method": "gcs." + op}, "value": 0.0,
                "buckets": dict(st.get("buckets") or {}),
                "sum": float(st.get("sum") or 0.0),
                "count": float(st.get("count") or 0.0),
                "description": "server-side control-plane RPC "
                               "handler latency"})
        return series

    def _h_shutdown(self, ctx: _ConnCtx, m: dict) -> None:
        ctx.reply(m, {"ok": True})
        threading.Thread(target=self.shutdown, daemon=True).start()
