"""Continuous-batched LLM serving on TPU over a paged KV cache.

The reference's serving north star (BASELINE.json: "Llama-3 8B Ray
Serve continuous batching") delegates the engine to vLLM/GPU; here the
engine is native.  New requests are admitted into free slots between
decode steps (iteration-level scheduling, the Orca/vLLM idea), so a few
fixed-shape compiled steps serve everything — no recompilation, no
dynamic shapes, MXU fed by the [B,1,D] batch.

The engine is `PagedBatcher`.  KV lives in a shared pool of fixed-size
*blocks* (kv_block_size tokens each) addressed through per-request
block tables: admission allocates exactly ceil((prompt + max_new) /
block_size) blocks, decode gathers through the table with the ragged
paged attention kernel (ops/paged_attention.py), and a refcounted
allocator makes blocks SHAREABLE.  On top sits an SGLang-style
radix/prefix cache: retired requests leave their full prompt blocks in
a per-model radix tree, a new prompt's longest cached block-prefix is
refcount-shared into its table, and device prefill runs only the
uncached suffix — a cache-hit TTFT is route + queue + a suffix-sized
prefill (the TTFT decomposition carries `cache_hit`).  Cold blocks are
LRU-evicted back to the free pool under pressure; when the pool is
empty a new request *queues* for blocks (backpressure) instead of
dying, and finish-reason "cache" is reserved for a single request that
exceeds the whole pool (or its table), never for transient exhaustion.
The engine also folds in serve.multiplex: requests tagged with a
`multiplexed_model_id` hot-swap LoRA adapters (fetched by ObjectRef
over the binary transfer plane, merged via multiplex.merge_adapter,
LRU-resident) without recompiling — same shapes, new weights — and each
model keys its own radix tree so prefix reuse never crosses models.

A dispatch is one device program per tick of the dispatcher thread:
`decode_chunk` decode steps of every live slot, with whatever prefill
is waiting FUSED into the same program (paged_prefill_decode_packed),
so an admission costs no dispatch of its own; with nothing to admit it
is the decode-only program (paged_decode_steps).  Either way a dispatch
is `decode_chunk` walks of the weights and returns `decode_chunk` tokens
for every slot in it: the fused program's prefill pass carries the live
slots' first decode step (their one position each beside the prompt rows,
`prefill.carried_rows` counts them), `decode_chunk - 1` steps follow, and
for a request the dispatch admits the first of its tokens is its prompt's
first token.  All host inputs of a dispatch travel in one int32 upload
(decoding.FusedUpload).  These two programs are all an engine compiles: a
request within a chunk of its last position, be it `max_new` or the cap that
`max_len` put on its allocation, rides the ordinary chunk, `_hand_out` cuts
its column there, and the steps past it write to blocks and state that are
its own until it retires, or to the scratch block.

Prefill: `prompt_pad` is the longest prompt `submit` accepts.  A row of
the fused prefill is a TILE of PREFILL_TILE tokens (a KV block or two) of
one request's uncached suffix, not a request: a request rounds up to whole
rows, a suffix longer than a tile takes several, each against the blocks
the rows before it wrote (and consecutive rows of one request attend
together, so its cached prefix is not read once a row).  A dispatch runs
the narrowest of a short ladder of compiled widths (PREFILL_RUNGS, in
positions) that holds the rows it is given, and the widest is the most
prefill one dispatch carries: PREFILL_CHUNK tokens, however many slots
the engine has.  What
does not fit waits for the next dispatch, first in first served: the
request holds its slot meanwhile without decoding, the other slots decode
on in the same dispatches, and the radix tree takes the prompt's blocks
as they are dispatched.

Pipelining: a loop that synchronizes with the device once per step
(dispatch → block on the token read → repeat) leaves the chip idle for
every host round trip.  The engine keeps up to `pipeline_depth`
dispatches in flight, starts device→host token copies asynchronously at
dispatch time (`copy_to_host_async`), and only materializes the OLDEST
in-flight result — so the chip computes chunk k+1 while chunk k's
tokens travel to the host.  Correctness under lag: every dispatch is
tagged with its (slot → request) ownership at dispatch time; a slot
retired while later dispatches were already in flight just has its
extra tokens dropped (a decode step is safe on retired slots), and the
slot is only re-admitted after the retiring read was processed —
in-order processing makes the attribution exact.

Streaming: `submit` returns a _Request whose tokens can be consumed
incrementally via `stream()` (a blocking iterator fed as decode reads
land) — this is what Serve's SSE path and the streaming-generator
replica methods consume.

Deploy via serve (the class is wrapped into a deployment first; the
replica's worker leases the chip it asks for):

    from ray_tpu import serve
    from ray_tpu.serve.llm import LLMDeployment
    llm = serve.deployment(LLMDeployment,
                           ray_actor_options={"num_tpus": 1})
    handle = serve.run(llm.bind(cfg_kwargs={...},
                                num_slots=8, max_len=256))
    out = ray_tpu.get(handle.generate.remote([1, 2, 3], max_new=16))
"""

from __future__ import annotations

import itertools
import os
import queue
import sys
import threading
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Any, Dict, Iterator, List, Optional

import numpy as np

from ray_tpu.devtools import leaksan
from ray_tpu.util.profiling import host_span

_STREAM_END = object()

# Where the engine's two threads are, on the device profiler's clock
# (profiling.host_span; profiling.idle_attribution lays the device's idle
# gaps at them).  A dispatch's spans on both threads carry its `seq`.
# Dispatcher thread (_engine_loop):
SPAN_PERMIT_WAIT = "engine.permit_wait"  # _slots_sem.acquire: device ahead
SPAN_STARVED = "engine.starved"          # _work.wait: nothing live or queued
SPAN_DISPATCH = "engine.dispatch"        # every _dispatch; its stats: seq,
#   kind fused / decode / none (a tick that found nothing to launch, the
#   rest left out), live, positions (the rung, 0 for decode), rows, admitted
SPAN_ADMIT = "engine.admit"              # ↳ _pop_admissions; stat: waiting
SPAN_PACK = "engine.pack"                # ↳ _fused_dispatch to the jitted call
SPAN_LAUNCH = "engine.launch"            # ↳ the jitted call + copy_to_host_async
SPAN_POST_ADMIT = "engine.post_admit"    # ↳ _post_admit: radix insert, gauges
# Processor thread (_process_loop), beside the dispatcher:
SPAN_READ_WAIT = "engine.read_wait"      # np.asarray(tokens); stat: seq
SPAN_HAND_OUT = "engine.hand_out"        # _hand_out; stat: seq

# A stretch with nothing on the device and work present that lasts this
# long is worth a line on stderr, traced or not.
DEVICE_EMPTY_WARN_S = 1.0


class _Phase:
    """A stretch of the dispatcher thread under one name: a host_span, its
    `seconds` (added to host_s[`key`] where it has one), and its name in
    `engine._where` for the processor's "device empty" line.  `with` gives
    the span, whose set_metadata() takes what is known only inside."""

    __slots__ = ("_eng", "_name", "_key", "_span", "_t0", "_outer",
                 "seconds")

    def __init__(self, eng: "PagedBatcher", name: str, key: str = "",
                 **attrs) -> None:
        self._eng, self._name, self._key = eng, name, key
        self._span = host_span(name, **attrs)

    def __enter__(self):
        self._outer, self._eng._where = self._eng._where, self._name
        self._span.__enter__()
        self._t0 = time.perf_counter()
        return self._span

    def __exit__(self, *exc) -> None:
        self.seconds = time.perf_counter() - self._t0
        self._span.__exit__(*exc)
        if self._key:
            self._eng.host_s[self._key] += self.seconds
        self._eng._where = self._outer


@dataclass
class _Request:
    prompt: List[int]
    max_new: int
    done: threading.Event = field(default_factory=threading.Event)
    tokens: List[int] = field(default_factory=list)
    ttft_s: float = 0.0
    # TTFT decomposition: queue_s = submit -> slot admission (engine
    # queue wait), prefill_s = admission -> first token materialized
    # (device prefill + pipeline/transfer).  ttft_s = queue_s + prefill_s.
    queue_s: float = 0.0
    prefill_s: float = 0.0
    _t0: float = 0.0
    _admit_t: float = 0.0
    slot: int = -1
    error: Optional[Exception] = None
    # "eos" | "length" (hit max_new) | "cache" (request exceeded the KV
    # pool/table; transient exhaustion QUEUES the request instead —
    # "cache" means this one request can never fit)
    finish_reason: str = ""
    # Multiplexing + prefix cache: the adapter/model the request routed
    # with, and whether admission reused cached blocks.
    model_id: str = ""
    cache_hit: bool = False
    cached_tokens: int = 0
    # Chunked prefill: prompt positions already sent to the cache (the
    # matched prefix among them), and whether chunks are still
    # to come — the request then holds its slot without decoding.
    _prefilled: int = 0
    _prefilling: bool = False
    # Max total positions (prompt + generated) this request's block
    # allocation covers (set at admission), and the pool blocks it holds
    # a reference on.
    _pos_cap: int = 0
    _blocks: List[int] = field(default_factory=list)
    _table: Optional[np.ndarray] = None     # `_blocks` as the uploads take it
    _blocks_freed: bool = False
    # A model with per-sequence recurrent state (StateAllocator): the id the
    # request's state lives in, what its next prefill row starts from (a
    # checkpoint held until the dispatch that restores it is launched, then
    # its own id; 0: zeros), and the checkpoints it was asked to leave,
    # {blocks into the prompt: id}, until the radix tree owns them.
    _state_id: int = 0
    _state_from: int = 0
    _state_held: int = 0
    _ckpt_base: int = 0     # blocks its hit used: what a checkpoint it
    #                         leaves at depth d cost to reach is d - base
    _ckpts: Dict[int, int] = field(default_factory=dict)
    # Set for streaming consumers: tokens are ALSO pushed here as the
    # engine processes decode reads, ending with _STREAM_END.
    stream_q: Optional["queue.Queue"] = None
    # Called once, from the engine's thread, when the request is done (an
    # asyncio caller's wake-up: LLMDeployment.generate).
    _on_done: Optional[Any] = None

    def _finish(self) -> None:
        self.done.set()
        wake = self._on_done
        if wake is not None:
            wake()

    def stream(self, timeout: float = 300.0) -> Iterator[int]:
        """Yield tokens as they are decoded (requires submit(...,
        streaming=True))."""
        if self.stream_q is None:
            raise RuntimeError("request was not submitted as streaming")
        while True:
            item = self.stream_q.get(timeout=timeout)
            if item is _STREAM_END:
                if self.error is not None:
                    raise self.error
                return
            yield item



_kv_metrics: Optional[Dict[str, Any]] = None


def _get_kv_metrics() -> Optional[Dict[str, Any]]:
    """Lazy module-level KV metrics (one registration per process;
    multiple engines share the cells).  Returns None when the metrics
    subsystem is unavailable (direct-engine benches outside a runtime
    still work; Gauge creation needs no client, so this only guards
    import-order surprises)."""
    global _kv_metrics
    if _kv_metrics is None:
        try:
            from ray_tpu.util import metrics as m
            _kv_metrics = {
                "blocks": m.Gauge(
                    m.KV_BLOCKS_METRIC,
                    "Paged-KV serving block pool occupancy by state "
                    "(used = refcount > 0, cached = refcount 0 but "
                    "retained in the prefix radix tree, free).  The "
                    "engine tag distinguishes co-located engines — "
                    "the node-side gauge merge is last-write-wins per "
                    "tagset, so untagged replicas would clobber each "
                    "other; consumers sum over engines per state.",
                    tag_keys=("state", "engine")),
                "queries": m.shared_counter(
                    m.PREFIX_CACHE_QUERIES_METRIC,
                    "Admission-time prefix-cache (radix tree) lookups."),
                "hits": m.shared_counter(
                    m.PREFIX_CACHE_HITS_METRIC,
                    "Prefix-cache lookups that reused at least one "
                    "full cached block."),
                "evictions": m.shared_counter(
                    m.KV_EVICTIONS_METRIC,
                    "Cached KV blocks LRU-evicted back to the free "
                    "pool under allocation pressure."),
            }
        except Exception:
            return None
    return _kv_metrics


class BlockAllocator:
    """Refcounted fixed-size KV block allocator over pool ids
    1..num_blocks (id 0 is the kernel's reserved scratch block and is
    never handed out).

    A block is in exactly one of three states:
      used   — refcount > 0 (held by >= 1 active request);
      cached — refcount == 0 but retained by the prefix radix tree
               (reusable by a future prefix hit, evictable under
               pressure);
      free   — in the free list.
    NOT thread-safe; the engine serializes access with its _kv_lock.
    """

    def __init__(self, num_blocks: int) -> None:
        if num_blocks < 1:
            raise ValueError("paged KV pool needs at least one block")
        self.num_blocks = num_blocks
        # pop() hands out low ids first (cosmetic, aids debugging).
        self._free: List[int] = list(range(num_blocks, 0, -1))
        self._ref: Dict[int, int] = {}
        self._cached: set = set()
        # counts(), kept as the states change: it is read at every
        # admission and every retirement, and a recount walks the pool
        self._used = 0          # refcount > 0
        self._idle_cached = 0   # refcount 0, retained by the radix tree

    def available(self) -> int:
        return len(self._free)

    # Leak-ledger hooks (RAY_TPU_LEAKSAN=1): a block is "live" from
    # the moment it leaves the free list (held by a request and/or
    # retained by the prefix tree) until it returns.  Keys include
    # id(self) so two engines' pools in one process never collide.
    def _ls_reg(self, bid: int) -> None:
        leaksan.register("kv_block", (id(self), bid))

    def _ls_dis(self, bid: int) -> None:
        leaksan.discharge("kv_block", (id(self), bid))

    def alloc(self, n: int) -> Optional[List[int]]:
        """n fresh blocks at refcount 1, or None (caller evicts or
        queues — never a partial allocation)."""
        if n > len(self._free):
            return None
        out = [self._free.pop() for _ in range(n)]
        for b in out:
            self._ref[b] = 1
            if leaksan._ENABLED:
                self._ls_reg(b)
        self._used += n
        return out

    def incref(self, bid: int) -> None:
        self.incref_many((bid,))

    def decref(self, bid: int) -> None:
        self.decref_many((bid,))

    def incref_many(self, bids) -> None:
        """One more holder of each block (a prefix hit takes a thousand at
        once: one call, not one per block)."""
        ref, cached = self._ref, self._cached
        for bid in bids:
            r = ref.get(bid, 0)
            if r == 0:
                self._used += 1
                if bid in cached:
                    self._idle_cached -= 1
            ref[bid] = r + 1

    def decref_many(self, bids) -> None:
        ref, cached = self._ref, self._cached
        for bid in bids:
            r = ref.get(bid)
            if r is None or r <= 0:
                raise RuntimeError(
                    f"KV block {bid} double-free (refcount {r!r})")
            r -= 1
            if r == 0:
                self._used -= 1
                if bid in cached:
                    self._idle_cached += 1
                    ref[bid] = 0
                else:
                    del ref[bid]
                    self._free.append(bid)
                    if leaksan._ENABLED:
                        self._ls_dis(bid)
            else:
                ref[bid] = r

    def refcount(self, bid: int) -> int:
        return self._ref.get(bid, 0)

    def mark_cached(self, bid: int) -> None:
        """The radix tree now retains this block (refcount-0 keeps it
        out of the free list until evicted)."""
        if bid not in self._cached:
            self._cached.add(bid)
            if self._ref.get(bid, 0) == 0:
                self._idle_cached += 1

    def release_cached(self, bid: int) -> None:
        """The radix tree evicted this block; if no request holds it,
        it returns to the free list."""
        if bid in self._cached:
            self._cached.discard(bid)
            if self._ref.get(bid, 0) == 0:
                self._idle_cached -= 1
        if self._ref.get(bid, 0) == 0:
            self._ref.pop(bid, None)
            self._free.append(bid)
            if leaksan._ENABLED:
                self._ls_dis(bid)

    def counts(self) -> Dict[str, int]:
        return {"used": self._used, "cached": self._idle_cached,
                "free": len(self._free)}


class StateAllocator:
    """Ids 1..num_states of per-sequence recurrent state (models/decoding.py
    `state_pool`; id 0 is the scratch state and is never handed out), for
    a model whose layers keep a recurrence's carry and not only positions.

    An id is a live request's own (taken at admission, given back when the
    request retires) or a CHECKPOINT: a copy of the state after a whole
    block of some prompt, owned by the radix node of that block
    (`_RadixNode.state`), so that a prefix hit can start from it.
    Which checkpoint goes when an id is needed (`evict_lru`): one that a
    request is about to restore (`hold`) never; a SUPERSEDED one first (a
    deeper checkpoint lies below its node before the path branches: a
    conversation's turn k once turn k + 1 has left its own, which every
    prompt that still matches through it matches on to; read off the tree
    when the id is needed, so a tenant's system prompt, where every
    conversation branches, is never one, however often it was restored);
    then the least recently used; and a DEAR one (it took more
    than `dear_blocks` blocks of prefill to reach from the checkpoint before
    it: a tenant's 16 k system prompt) only when no cheap one is left: a
    turn's checkpoint costs a block or two to make again, a system
    prompt's a thousand, and an LRU alone lets the many cheap ones push the
    few dear ones out.  An id given back `later`
    is handed out again only after `settle()`: the dispatcher calls that at
    the start of a dispatch, so a decode step launched for the id's last
    owner can never run after the id was given to another request.
    NOT thread-safe (engine _kv_lock)."""

    def __init__(self, num_states: int, dear_blocks: int = 128) -> None:
        from collections import OrderedDict
        if num_states < 1:
            raise ValueError("a state pool needs at least one id")
        self.num_states = num_states
        self.dear_blocks = dear_blocks
        self._free: List[int] = list(range(num_states, 0, -1))
        self._limbo: List[int] = []
        self._holds: Dict[int, int] = {}
        # checkpoint id -> its radix node, least recently used first, and
        # the blocks of prefill each cost
        self._ckpts: "OrderedDict[int, _RadixNode]" = OrderedDict()
        self._cost: Dict[int, int] = {}
        self.superseded_evictions = 0

    def used(self) -> int:
        return self.num_states - len(self._free) - len(self._limbo)

    def checkpoints(self) -> int:
        return len(self._ckpts)

    def alloc(self) -> Optional[int]:
        return self._free.pop() if self._free else None

    def free(self, sid: int, later: bool = False) -> None:
        (self._limbo if later else self._free).append(sid)

    def settle(self) -> None:
        if self._limbo:
            self._free.extend(self._limbo)
            self._limbo.clear()

    def adopt(self, sid: int, node: "_RadixNode", cost: int = 0) -> None:
        """`node` now owns checkpoint `sid` (newest in the LRU), which took
        `cost` blocks of prefill to reach."""
        node.state = sid
        self._ckpts[sid] = node
        self._cost[sid] = cost

    def touch(self, sid: int) -> None:
        self._ckpts.move_to_end(sid)

    def hold(self, sid: int) -> None:
        self._holds[sid] = self._holds.get(sid, 0) + 1

    def release(self, sid: int) -> None:
        n = self._holds.get(sid, 0) - 1
        if n > 0:
            self._holds[sid] = n
        else:
            self._holds.pop(sid, None)

    def drop(self, sid: int) -> None:
        """A checkpoint's node is gone (or gives it up): the id is free."""
        node = self._ckpts.pop(sid)
        node.state = None
        self._cost.pop(sid, None)
        self.free(sid)

    def superseded(self, sid: int) -> bool:
        """Whether checkpoint `sid` has a deeper one below it before its
        path branches (the blocks between two turns' checkpoints: a leaf or
        a node with several children ends the walk at once)."""
        node = self._ckpts[sid]
        while len(node.children) == 1:
            (node,) = node.children.values()
            if node.state is not None:
                return True
        return False

    def evict_lru(self) -> bool:
        """Free a cheap checkpoint nobody holds: the oldest superseded one,
        the least recently used where none is; a dear one, in the same
        order, where no cheap one is left.  Its block stays cached."""
        for dear in (False, True):
            oldest = 0
            for sid, node in self._ckpts.items():
                if sid in self._holds or not (
                        dear or self._cost[sid] <= self.dear_blocks):
                    continue
                # (most are leaves, a conversation's newest: no call)
                if len(node.children) == 1 and self.superseded(sid):
                    self.superseded_evictions += 1
                    self.drop(sid)
                    return True
                oldest = oldest or sid
            if oldest:
                self.drop(oldest)
                return True
        return False

    def drop_all(self) -> None:
        for sid in list(self._ckpts):
            self.drop(sid)


class _RadixNode:
    __slots__ = ("children", "parent", "key", "block", "last_used", "state")

    def __init__(self, parent=None, key=None, block=None):
        self.children: Dict[tuple, "_RadixNode"] = {}
        self.parent = parent
        self.key = key
        self.block = block
        self.last_used = 0
        # a checkpoint of the recurrent state AFTER this node's block
        # (StateAllocator), where the model has such state and one was taken
        self.state: Optional[int] = None


class RadixCache:
    """Radix/prefix tree over FULL KV blocks for one model id
    (SGLang-style).  Each edge is one block's worth of tokens; a path
    from the root spells a prompt prefix and its nodes carry the
    physical blocks holding that prefix's KV.  Only whole blocks are
    shareable — the partial tail block of a prompt stays private, so
    decode writes never touch shared state.  NOT thread-safe (engine
    _kv_lock)."""

    def __init__(self, block_size: int, clock=None) -> None:
        self.block_size = block_size
        self.root = _RadixNode()
        # LRU clock: the engine passes ONE shared counter to all its
        # per-model trees so last_used values are comparable across
        # models in the global eviction sort (per-tree ticks would
        # evict a low-traffic model's hot blocks before a high-traffic
        # model's cold ones).
        self._clock = clock
        self._tick = 0
        self.size = 0          # cached nodes/blocks in this tree

    def _now(self) -> int:
        """One reading for a whole walk: the nodes of one prompt's path are
        used together (a 16 k prompt is a thousand of them)."""
        if self._clock is not None:
            return self._clock()
        self._tick += 1
        return self._tick

    def match(self, tokens: List[int]) -> List[int]:
        """Longest cached block-prefix of `tokens`, capped at
        len(tokens) - 1 so at least one token is always left for the
        suffix prefill (the request needs fresh last-position logits).
        Returns the physical block ids, root-first."""
        return self.match_with_state(tokens)[0]

    def match_with_state(self, tokens: List[int]):
        """`match` for a model with per-sequence recurrent state: a hit can
        only be USED as far as a checkpoint of that state reaches.  ->
        (the matched blocks m, root-first, h <= m: the depth of the deepest
        matched node that carries a checkpoint, that checkpoint's id or 0).
        K/V of the blocks between h and m are recomputed with the rest."""
        bs = self.block_size
        out: List[int] = []
        node = self.root
        h, sid = 0, 0
        now = self._now()
        for i in range((len(tokens) - 1) // bs):
            child = node.children.get(tuple(tokens[i * bs:(i + 1) * bs]))
            if child is None:
                break
            child.last_used = now
            out.append(child.block)
            if child.state is not None:
                h, sid = i + 1, child.state
            node = child
        return out, h, sid

    def insert(self, tokens: List[int], blocks: List[int],
               allocator: BlockAllocator,
               states: Optional[Dict[int, int]] = None,
               state_alloc: Optional[StateAllocator] = None,
               base: int = 0) -> int:
        """Cache every full-block chunk of `tokens` along one path.
        `blocks` is the request's block table (position-ordered), so
        blocks[i] holds chunk i's KV.  Existing nodes win collisions
        (the caller's duplicate block stays private and is freed at
        retire); new nodes mark their block cached.  Returns the
        number of NEW nodes.  `states` {depth: checkpoint id}: the node
        at `depth` blocks takes the checkpoint (`state_alloc.adopt`) if it
        has none, and the entry leaves `states`: what is left found no node,
        or one that has a checkpoint already.  `base`: the depth the
        request started from, so that a checkpoint knows what it cost."""
        bs = self.block_size
        node = self.root
        added = 0
        n = min(len(tokens) // bs, len(blocks))
        now = self._now()
        for i in range(n):
            chunk = tuple(tokens[i * bs:(i + 1) * bs])
            child = node.children.get(chunk)
            if child is None:
                child = _RadixNode(parent=node, key=chunk,
                                   block=blocks[i])
                node.children[chunk] = child
                allocator.mark_cached(blocks[i])
                self.size += 1
                added += 1
            elif child.block != blocks[i]:
                # Same-prefix race within one admission batch: keep
                # the cached block, the caller keeps its private copy.
                pass
            child.last_used = now
            node = child
            if states and child.state is None and i + 1 in states:
                state_alloc.adopt(states.pop(i + 1), child, i + 1 - base)
        return added

    def branches_at(self, tokens: List[int], depth: int) -> bool:
        """Whether the node `depth` cached blocks down `tokens`' path has a
        second child once `tokens`' next block is inserted below it (not
        where the path has been evicted since it was matched: `tokens`
        will make it anew, alone)."""
        bs = self.block_size
        node = self.root
        for i in range(depth):
            node = node.children.get(tuple(tokens[i * bs:(i + 1) * bs]))
            if node is None:
                return False
        nxt = tuple(tokens[depth * bs:(depth + 1) * bs])
        return len(node.children) + (nxt not in node.children) > 1

    def evictable(self) -> List[tuple]:
        """(last_used, node) for every LEAF whose block no request
        references — the LRU eviction candidates.  Leaf-only eviction
        keeps the prefix property: a cached chunk's ancestors stay
        cached."""
        out = []
        stack = [self.root]
        while stack:
            node = stack.pop()
            stack.extend(node.children.values())
            if node is self.root or node.children:
                continue
            out.append((node.last_used, node))
        return out

    def remove_leaf(self, node: "_RadixNode",
                    allocator: BlockAllocator) -> None:
        if node.children or node.parent is None:
            raise RuntimeError("can only evict leaf radix nodes")
        del node.parent.children[node.key]
        node.parent = None
        allocator.release_cached(node.block)
        self.size -= 1


# What the fused prefill rounds up to.  A row holds PREFILL_TILE tokens of
# ONE request's uncached suffix, so a request rounds up to whole rows: a
# block or two (kv_block_size 16 is the floor: a prefix-cache hit is whole
# blocks), since a session turn's suffix (a message, the last reply, a
# partial block) is 17-40 tokens and a row of 64 was half padding (PERF.md
# section 6, PR 33 has the measurement against 64 and 32).  A dispatch
# rounds up to the next of PREFILL_RUNGS positions: the dense products see
# rows x tile positions whatever the rows are.  Every rung is a program
# every engine traces and loads at warm-up (a twentieth of a serving
# cell's set-up each), so there are four, as before, placed by the
# admitted positions a dispatch that the serving cells' counters show (a
# lone chat prompt under 256; session turns 400-640 two dispatches in
# three, under 896 nearly all the rest), not at powers of two: measured
# against 256 / 512 / 768 / 1,024 / 2,048 in the same section.  A dispatch
# carries at most PREFILL_CHUNK tokens of prefill, the widest program; what
# an admission brings that does not fit waits for the next dispatch,
# holding its slot, while the other slots go on decoding.
PREFILL_TILE = 16
PREFILL_CHUNK = 2048
PREFILL_RUNGS = (256, 640, 896, 2048)


def prefill_shapes(num_slots: int, prompt_pad: int, block_size: int):
    """(tokens a row, [rows of each compiled fused prefill]) of an engine:
    a row is PREFILL_TILE tokens in whole KV blocks, the widest program
    holds PREFILL_CHUNK tokens whatever the slots are (or every slot's
    longest prompt, where that is less), the others are the rungs of
    PREFILL_RUNGS below it."""
    tile = min(-(-PREFILL_TILE // block_size) * block_size, PREFILL_CHUNK,
               prompt_pad)
    widest = max(1, min(PREFILL_CHUNK // tile,
                        num_slots * -(-prompt_pad // tile)))
    return tile, sorted({min(-(-r // tile), widest) for r in PREFILL_RUNGS}
                        | {widest})


def find_shared_prefixes(tables: Dict[int, np.ndarray], block_size: int,
                         num_slots: int):
    """The prefixes that sets of decoding slots share, from their block lists
    (`tables`: slot -> its request's blocks; requests hold the SAME blocks
    over what the radix cache gave them both) -> (members [P, 8] slot ids, -1
    nobody; leader [P]; shared_len [P] positions, 0: no program), P =
    num_slots // 2: ops/paged_attention.py SharedPrefixes.  One level: a
    set is the slots whose lists start on one block, what it shares the
    longest prefix common to ALL of them (a tenant's system prompt; where
    some of a set share more, a conversation's earlier turns, that part is
    read by each); it counts from the one DMA group of the paged kernel
    on, and is cut into programs of 8 members."""
    from ray_tpu.ops.paged_attention import (SHARED_MEMBERS,
                                             SHARED_MIN_POSITIONS)
    P = num_slots // 2
    members = np.full((P, SHARED_MEMBERS), -1, np.int32)
    leader, shared_len = np.zeros((P,), np.int32), np.zeros((P,), np.int32)
    sets: Dict[int, List[int]] = {}
    for slot, table in tables.items():
        if len(table):
            sets.setdefault(int(table[0]), []).append(slot)
    p = 0
    for slots in sets.values():
        if len(slots) < 2:
            continue
        n = min(len(tables[s]) for s in slots)
        first = tables[slots[0]][:n]
        same = np.ones((n,), bool)
        for s in slots[1:]:
            same &= tables[s][:n] == first
        blocks = n if same.all() else int(same.argmin())
        if blocks * block_size < SHARED_MIN_POSITIONS:
            continue
        for i in range(0, len(slots), SHARED_MEMBERS):
            part = slots[i:i + SHARED_MEMBERS]
            members[p, :len(part)] = part
            leader[p], shared_len[p] = part[0], blocks * block_size
            p += 1
    return members, leader, shared_len


class PagedBatcher:
    """Slot-based continuous batching engine over a paged KV cache: a
    block pool, a radix prefix cache and multiplexed adapter hot-swap
    (host loop + jitted steps; see the module docstring).

    Thread-safe submit(); a dispatcher thread interleaves admissions
    (fused into the decode dispatch) with chunked decode dispatches,
    keeping `pipeline_depth` of them in flight, and a processor thread
    reads their tokens.  Admission allocates refcounted blocks (evicting
    cold cached blocks, then QUEUEING under pressure), prefill runs only
    the prompt's uncached suffix via paged_prefill_decode_packed (as
    rows of PREFILL_TILE tokens in the narrowest program of PREFILL_RUNGS
    positions that holds them, at most PREFILL_CHUNK tokens a dispatch), and
    decode gathers KV through block tables with the ragged paged
    attention kernel.
    """

    def __init__(self, params, cfg, num_slots: int = 8,
                 max_len: int = 512, prompt_pad: int = 64,
                 eos_id: Optional[int] = None,
                 decode_chunk: int = 8,
                 pipeline_depth: int = 2,
                 kv_block_size: Optional[int] = None,
                 kv_num_blocks: Optional[int] = None,
                 prefix_cache: Optional[bool] = None,
                 adapters: Optional[Dict[str, Any]] = None,
                 max_resident_models: int = 3,
                 attn_impl: str = "auto",
                 max_queue: int = 0,
                 num_states: Optional[int] = None) -> None:
        from collections import OrderedDict

        from ray_tpu._private.config import config
        from ray_tpu.models import decoding
        self._dec = decoding
        self.params = params
        self.cfg = cfg
        self.num_slots = num_slots
        self.max_len = max_len
        self.prompt_pad = prompt_pad
        self.eos_id = eos_id
        # Admission backstop: submit() sheds (typed rejection) once
        # this many requests are queued ahead of slot admission.
        # 0 = unlimited.  The check runs BEFORE anything touches the
        # KV path, so a shed request never allocates blocks or
        # queries the prefix cache.
        self.max_queue = max(int(max_queue), 0)
        # Tokens decoded per device dispatch: >1 amortizes dispatch
        # overhead at the cost of admission/EOS granularity.
        self.decode_chunk = max(decode_chunk, 1)
        self.pipeline_depth = max(pipeline_depth, 1)
        self.block_size = int(kv_block_size or config.kv_block_size)
        if self.block_size < 1:
            raise ValueError("kv_block_size must be >= 1")
        self.table_width = decoding.paged_table_width(
            max_len, self.block_size)
        auto_blocks = num_slots * self.table_width
        self.num_blocks = int(kv_num_blocks or config.kv_num_blocks
                              or auto_blocks)
        if prefix_cache is None:
            prefix_cache = bool(config.prefix_cache_enabled)
        self.prefix_cache_enabled = prefix_cache
        # The allocator, the radix trees and the counters below are
        # shared between the dispatcher and processor threads ->
        # guarded by _kv_lock.  _waiting is dispatcher-only: other
        # threads hand work to it through _pending and failures
        # through _waiting_fail, never by mutating the deque.
        self._kv_lock = threading.Lock()
        # The fused prefill's shapes: rows of `_tile` tokens, and a ladder
        # of row counts; the widest is the budget of one dispatch.  A
        # dispatch costs what it admits, to within a rung: a prefix-cache
        # hit that leaves a short suffix pays for a row or two, not for a
        # prompt-wide one.
        self._tile, self._prefill_rows = prefill_shapes(
            num_slots, prompt_pad, self.block_size)
        if self._tile % self.block_size:
            raise ValueError(
                f"prompts are prefilled in tiles of {self._tile} tokens "
                f"(prefill_shapes: prompt_pad {prompt_pad}), which must be "
                f"whole blocks of kv_block_size {self.block_size}")
        self._alloc = BlockAllocator(self.num_blocks)
        self._radix: Dict[str, RadixCache] = {}
        # One LRU clock shared by every model's tree (comparable
        # last_used across models for the global eviction sort) and a
        # per-engine gauge tag (co-located engines would otherwise
        # clobber each other's series in the node-side merge).
        _counter = itertools.count(1)
        self._radix_clock = lambda: next(_counter)
        self._engine_tag = f"{os.getpid():x}.{id(self):x}"
        self._waiting: deque = deque()
        self._waiting_fail: Optional[Exception] = None
        self._attn_impl = attn_impl
        self._base_params = params
        self._adapters = dict(adapters or {})
        self._models: "OrderedDict[str, Any]" = OrderedDict()
        self._models[""] = params
        self._max_resident = max(max_resident_models, 1)
        self._model_id = ""
        self._cache_queries = 0
        self._cache_hits = 0
        self._cache_hit_tokens = 0
        self._evictions = 0
        # Counted for stats(): prefill chunks (one per request and
        # dispatch) and their tokens, the positions the dispatches' rows
        # held (rows x tile: chunk_tokens / padded_tokens is how full they
        # were), how many fused dispatches ran each compiled width (by its
        # positions), requests that took more than one dispatch; what an
        # expert model's layers
        # report per dispatch (models/afmoe.py MOE_COUNTS); and, per
        # dispatch over owned slots and sliding layers, the positions
        # held against those still inside a window (one block id serves
        # every layer, so none beyond a window is freed yet).
        self._prefill_counts = {"chunks": 0, "chunk_tokens": 0,
                                "padded_tokens": 0,
                                "multi_chunk_requests": 0,
                                "carried_rows": 0}
        self._rung_dispatches = {str(n * self._tile): 0
                                 for n in self._prefill_rows}
        self._moe_counts = [0, 0, 0, 0, 0]
        # What the decode steps' attention had to read and what it read,
        # in cached positions a step: every decoding slot's context, and
        # the same with a prefix that a set shares counted once a program
        # (find_shared_prefixes).  `_own_start` [slot] and `_shared_read`
        # are the sets the device holds: the last fused dispatch's.
        self._decode_reads = {"context_positions": 0,
                              "streamed_positions": 0,
                              # summed over fused dispatches: the programs
                              # of their sets, the slots in them and the
                              # positions a program shares
                              "shared_programs": 0, "shared_members": 0,
                              "shared_positions": 0}
        self._own_start = np.zeros((num_slots,), np.int64)
        self._shared_read = 0
        self._sliding_layers = sum(
            1 for m, _ in (cfg.layer_kinds or ()) if m == "sliding")
        self._sliding_held = 0
        self._sliding_in_window = 0
        # Per-sequence state, where the model has layers that keep any (a
        # linear layer's carry, a ring layer's window):
        # `num_states` ids, one a live slot and the rest checkpoints that
        # the radix cache owns (default: four checkpoints a slot).  No other
        # model has an allocator, and nothing below runs for it.
        self._states: Optional[StateAllocator] = None
        self.num_states = 0
        if any(m in decoding.STATE_MIXERS
               for m, _ in (cfg.layer_kinds or ())):
            self.num_states = int(num_states or config.kv_num_states
                                  or 5 * num_slots)
            if self.num_states < num_slots:
                raise ValueError(
                    f"{self.num_states} state ids for {num_slots} slots")
            self._states = StateAllocator(
                self.num_states, PREFILL_CHUNK // self.block_size)
        self._state_counts = {"restores": 0, "snapshots": 0,
                              "snapshot_evictions": 0, "snapshots_skipped": 0,
                              "matched_tokens": 0, "unbacked_tokens": 0,
                              "full_restores": 0}
        self.caches = decoding.init_paged_caches(
            cfg, num_slots, self.num_blocks, self.block_size, max_len,
            self.num_states)
        self._upload = decoding.FusedUpload.of(self._tile, self.caches)
        # State that is not positions, where the caches hold any: layers
        # whose state at a block boundary is a tail kept under the block's
        # id (decoding.PagedDecodeCaches.tail_pool).  A prefix hit then also
        # means "start from the tail of the last shared block", which the
        # prefill's rows read through the table they have anyway: the
        # engine does nothing more for such a layer, and counts nothing.
        # SLO windows for the serve autoscaler (slo_snapshot): engine
        # TTFT samples and inter-token latency derived from decode
        # entry processing cadence.  Guarded by _slo_lock (processor
        # thread appends, actor threads snapshot).
        self._slo_lock = threading.Lock()
        self._ttft_win: deque = deque(maxlen=128)
        self._itl_win: deque = deque(maxlen=256)
        self._last_entry_t: Optional[float] = None
        # Slot ownership/length AT DISPATCH TIME (the engine's view of
        # the device); processing updates the per-request state.
        self._owner: List[Optional[_Request]] = [None] * num_slots
        self._disp_len = [0] * num_slots
        self._pending: "queue.Queue[_Request]" = queue.Queue()
        # In-flight dispatches, oldest first: (_launch's (tokens, counts,
        # label, stamp), admitted, pairs, seq) with
        #   fused:  admitted [(slot, req)] take a first token,
        #           pairs [(slot, req)] the chunk's decode tokens
        #   decode: admitted ()
        self._inflight: deque = deque()
        self._shutdown = False
        self._work = threading.Event()
        self.steps = 0
        # Where the engine's two threads spend their time, in seconds
        # (stats()["host"]).  Dispatcher: waiting for a pipeline permit
        # (the device is ahead: healthy), building and launching a
        # dispatch, and starved (no live slot, nothing waiting).
        # Processor: waiting for a dispatch's tokens, and handing them out.
        # Inside `dispatch`: admit, pack, launch, post_admit (the spans of
        # the table above), and inside those the radix walks and the
        # eviction sweeps, and `state`: the state allocator and the
        # checkpoint bookkeeping of a model with recurrent state (inside
        # admit and post_admit); counts of launched dispatches.  And whether the
        # DEVICE had anything to run, on this clock: from a result's
        # arrival (_process_entry) at which every launched dispatch has
        # been read back, to the next launch's return, nothing is in
        # flight.  Such seconds with a request waiting or a slot live are
        # `device_starved` (the host's doing), the rest `device_unasked`
        # (the traffic's).  About a lower bound of the device's idle time:
        # a result is seen after the device finished it; a launch is
        # stamped when its call returns, which the device's start may
        # precede, so a stretch can over-read by up to `launch` ÷
        # `dispatches`.
        self.host_s = {"permit_wait": 0.0, "dispatch": 0.0, "starved": 0.0,
                       "read_wait": 0.0, "process": 0.0,
                       "admit": 0.0, "pack": 0.0, "launch": 0.0,
                       "post_admit": 0.0, "radix_match": 0.0,
                       "radix_insert": 0.0, "evict": 0.0, "state": 0.0,
                       "dispatches": 0,
                       "device_starved": 0.0, "device_unasked": 0.0}
        # What each of the engine's programs costs on the device, on this
        # clock (stats()["program"]): by label ("decode", a fused pass's
        # positions as `_rung_dispatches` keys them), the dispatches read
        # back and their device seconds.  A dispatch's seconds run from the
        # arrival of the dispatch before it, or from its own launch's
        # return where the device was empty then, to its own arrival
        # (_process_entry): with two dispatches in flight the device runs
        # them back to back, so arrival to arrival is the program's time,
        # and a stretch the device sat empty before a launch is left out
        # (it is `device_starved` / `device_unasked` above).  It over-reads
        # by how late the processor thread saw the result (it was handing
        # out the one before), and the next dispatch under-reads by the
        # same: sums hold, and a program's mean is off only as far as the
        # lateness follows the program kind.
        labels = ["decode"] + list(self._rung_dispatches)
        self.program = {"device_s": dict.fromkeys(labels, 0.0),
                        "dispatches": dict.fromkeys(labels, 0)}
        self._last_got = 0.0        # the newest arrival (processor thread)
        # Shared by the two threads, under _dev_lock for stamps only: when
        # the device went empty (None while a dispatch is in flight), and
        # whether this stretch has had its line.  _where: the dispatcher's
        # innermost span.
        self._dev_lock = threading.Lock()
        self._empty_since: Optional[float] = None
        self._empty_warned = False
        self._where = "warm-up"
        # Device-resident active-mask cache: skips one host->device
        # transfer per decode dispatch.  In steady state the mask rarely
        # changes (drained-readmission keeps slots full), so the device
        # array is keyed by the mask bytes.
        self._active_key: Optional[bytes] = None
        self._active_dev = None
        # Dispatcher/processor split: one thread submits dispatches
        # while another blocks on result reads, so submission never
        # waits behind result processing.  _state_lock guards
        # _owner/_disp_len (both threads mutate them); _inflight moves
        # entries from dispatcher to processor; _slots_sem bounds the
        # pipeline depth.
        self._state_lock = threading.Lock()
        self._proc_wake = threading.Event()
        self._slots_sem = threading.Semaphore(self.pipeline_depth)
        # Warm-up (every dispatch shape compiled) runs on the engine
        # thread; requests submitted meanwhile queue behind it.
        self._warmed = False
        self.warmup_s = 0.0        # compile + first run of every shape
        self._engine_error: Optional[Exception] = None
        self._thread = threading.Thread(target=self._engine_loop,
                                        daemon=True, name="rtpu-llm")
        self._thread.start()
        self._proc_thread = threading.Thread(
            target=self._process_loop, daemon=True, name="rtpu-llm-proc")
        self._proc_thread.start()
        leaksan.track_thread(self._thread)
        leaksan.track_thread(self._proc_thread)

    # -- public ------------------------------------------------------------
    def queue_depth(self) -> int:
        """Requests queued ahead of slot admission (not yet decoding):
        the submit queue plus the dispatcher-side waiting deque, which
        holds requests already popped from _pending but still blockless
        (backpressure).  len() is a GIL-atomic read, good enough for a
        shed threshold."""
        return self._pending.qsize() + len(self._waiting)

    def slo_snapshot(self) -> Dict[str, Any]:
        """The serve autoscaler's engine-side SLO view (consumed via
        the replica's __rtpu_slo_stats__ hook): engine queue depth,
        TTFT p95, and decode inter-token latency p95 over the rolling
        time-decayed windows (one shared window constant + percentile
        helper with the replica's request-latency signal)."""
        from ray_tpu.serve._replica import _SLO_WINDOW_S, _p95_ms

        def p95(xs):
            v = _p95_ms(xs)
            return round(v, 3) if v is not None else None

        cutoff = time.time() - _SLO_WINDOW_S
        with self._slo_lock:
            ttfts = [v for t, v in self._ttft_win if t >= cutoff]
            itls = [v for t, v in self._itl_win if t >= cutoff]
        return {"queue_depth": self.queue_depth(),
                "ttft_p95_ms": p95(ttfts),
                "itl_p95_ms": p95(itls)}

    def submit(self, prompt: List[int], max_new: int = 32,
               streaming: bool = False, model_id: str = "") -> _Request:
        """Enqueue a request.  `model_id` selects a multiplexed
        adapter ("" is the base model).

        With `max_queue` set, a submit that finds that many requests
        already queued raises the typed RequestRejectedError HERE —
        before the request touches the engine at all.  That ordering
        is load-bearing: a shed request must never query the prefix
        cache or hold KV blocks, so rejection can never evict a live
        request's cache entries.  The "llm-engine" label is a
        placeholder: the serving Replica re-tags the rejection with its
        real deployment name (and counts the shed there) on the way
        out."""
        self._raise_if_dead()
        if self.max_queue and self.queue_depth() >= self.max_queue:
            from ray_tpu.serve._admission import RequestRejectedError
            raise RequestRejectedError(
                deployment="llm-engine", reason="queue_full",
                retry_after_s=0.5)
        if len(prompt) > self.prompt_pad:
            raise ValueError(f"prompt of {len(prompt)} tokens exceeds "
                             f"prompt budget {self.prompt_pad}")
        req = _Request(prompt=list(prompt), max_new=max_new,
                       model_id=model_id,
                       stream_q=queue.Queue() if streaming else None)
        req._t0 = time.time()
        self._pending.put(req)
        self._work.set()
        self._raise_if_dead()       # warm-up failed while we enqueued
        return req

    def _raise_if_dead(self) -> None:
        if self._engine_error is not None:
            raise RuntimeError(
                f"LLM engine failed its warm-up and serves nothing: "
                f"{self._engine_error!r}") from self._engine_error

    def generate(self, prompt: List[int], max_new: int = 32,
                 timeout: float = 300.0,
                 model_id: str = "") -> Dict[str, Any]:
        req = self.submit(prompt, max_new, model_id=model_id)
        if not req.done.wait(timeout):
            raise TimeoutError("generation timed out")
        if req.error is not None:
            raise req.error
        return {"tokens": req.tokens, "ttft_s": req.ttft_s,
                "queue_s": req.queue_s, "prefill_s": req.prefill_s,
                "cache_hit": req.cache_hit,
                "cached_tokens": req.cached_tokens,
                "finish_reason": req.finish_reason}

    def generate_stream(self, prompt: List[int], max_new: int = 32,
                        timeout: float = 300.0,
                        model_id: str = "") -> Iterator[int]:
        """Blocking token iterator (the serve streaming data plane)."""
        req = self.submit(prompt, max_new, streaming=True,
                          model_id=model_id)
        return req.stream(timeout=timeout)

    def stop(self) -> None:
        self._shutdown = True
        self._work.set()
        self._proc_wake.set()
        # Join the engine threads: exiting the process while a daemon
        # thread is inside an XLA compile/dispatch (e.g. stop() racing
        # warmup) crashes interpreter teardown.  Both loops observe
        # _shutdown at the next iteration, so this is bounded by one
        # warmup/dispatch.
        for t in (self._thread, self._proc_thread):
            if t is not threading.current_thread():
                t.join(timeout=120.0)
            # Only a thread that actually EXITED leaves the ledger: a
            # join that timed out (wedged dispatch) must stay visible
            # — that is the class the ledger exists to catch.
            if not t.is_alive():
                leaksan.discharge_thread(t)
        # Terminal discharge: anything still owned/queued can never
        # finish now that the loops are gone.  Leaving it parked
        # strands its caller until the generate() timeout — and keeps
        # its KV blocks refcounted forever (leak-ledger self-finding).
        # _fail_all also drops the prefix cache, so a stopped engine
        # holds zero blocks.
        self._fail_all(RuntimeError("engine stopped"))
        # Threads are joined now; remove this engine's gauge series —
        # remove() queues one final zero sample, so a cleanly-stopped
        # engine neither leaves stale occupancy in the node-side
        # aggregate nor leaks three dead cells per construct/stop
        # cycle in this process's registry.
        km = _get_kv_metrics()
        if km is not None:
            for state in ("used", "cached", "free"):
                km["blocks"].remove(tags={"state": state,
                                          "engine": self._engine_tag})

    def host_stats(self) -> Dict[str, float]:
        """host_s (stats()["host"]) and `work`: the dispatcher loop's
        seconds outside `starved`, what `device_starved` is a share of."""
        h = dict(self.host_s)
        h["work"] = h["permit_wait"] + h["dispatch"]
        return h

    def program_stats(self) -> Dict[str, Dict[str, float]]:
        """`self.program` (stats()["program"]): by program label, the
        dispatches read back and their device seconds on the host's
        clock."""
        return {name: dict(by_label)
                for name, by_label in self.program.items()}

    def kv_stats(self) -> Dict[str, Any]:
        """Block-pool + prefix-cache occupancy (also what the bench
        and state.memory_summary() surface)."""
        with self._kv_lock:
            counts = self._alloc.counts()
            return {
                "block_size": self.block_size,
                "num_blocks": self.num_blocks,
                "blocks": counts,
                "prefix_cache": {
                    "enabled": self.prefix_cache_enabled,
                    "queries": self._cache_queries,
                    "hits": self._cache_hits,
                    "hit_tokens": self._cache_hit_tokens,
                    "evictions": self._evictions,
                    "cached_blocks": sum(t.size
                                         for t in self._radix.values()),
                },
                "models_resident": list(self._models),
                "model_id": self._model_id,
                "prefill": dict(
                    self._prefill_counts,
                    rung_dispatches=dict(self._rung_dispatches)),
                # (models/afmoe.py MOE_COUNTS, then PADDED_ROWS where the
                # model's layers count it, and the picks of valid tokens:
                # those routed here and those whose expert is held elsewhere)
                "moe": dict(zip(("layer_steps", "routed_rows",
                                 "busiest_expert_rows", "experts_touched",
                                 "absent_rows", "padded_rows"),
                                self._moe_counts),
                            picked_rows=self._moe_counts[1]
                            + self._moe_counts[4]),
                "decode": dict(self._decode_reads),
                **({} if self._states is None else {"state": dict(
                    self._state_counts, ids_used=self._states.used(),
                    checkpoints=self._states.checkpoints(),
                    superseded_evictions=self._states.superseded_evictions,
                    num_states=self.num_states)}),
                "kv": {"sliding_positions_held": self._sliding_held,
                       "sliding_positions_in_window":
                           self._sliding_in_window,
                       "sliding_positions_dead":
                           self._sliding_held - self._sliding_in_window},
            }

    def resident_models(self) -> List[str]:
        # _kv_lock: _swap_model mutates _models on the dispatcher
        # thread while the router's multiplex probe calls this from a
        # request thread.
        with self._kv_lock:
            return [m for m in self._models if m]

    # -- allocator / prefix cache ------------------------------------------
    def _radix_for(self, model_id: str) -> RadixCache:
        tree = self._radix.get(model_id)
        if tree is None:
            tree = self._radix[model_id] = RadixCache(
                self.block_size, clock=self._radix_clock)
        return tree

    def _evict_locked(self, need: int) -> int:
        """Free up to `need` blocks by LRU-evicting refcount-0 cached
        leaves across ALL models' radix trees (global LRU).  Caller
        holds _kv_lock."""
        freed = 0
        while freed < need:
            candidates = []
            for tree in self._radix.values():
                for last_used, node in tree.evictable():
                    if self._alloc.refcount(node.block) == 0:
                        candidates.append((last_used, node, tree))
            if not candidates:
                break
            candidates.sort(key=lambda c: c[0])
            for _, node, tree in candidates:
                if freed >= need:
                    break
                if node.children or node.parent is None:
                    continue       # a sibling eviction re-parented it
                tree.remove_leaf(node, self._alloc)
                if node.state is not None:  # its checkpoint goes with it
                    self._states.drop(node.state)
                    self._state_counts["snapshot_evictions"] += 1
                freed += 1
                self._evictions += 1
        if freed:
            km = _get_kv_metrics()
            if km is not None:
                km["evictions"].inc(freed)
        return freed

    def _update_kv_gauges(self) -> None:
        km = _get_kv_metrics()
        if km is None:
            return
        with self._kv_lock:
            counts = self._alloc.counts()
        for state, n in counts.items():
            km["blocks"].set(n, tags={"state": state,
                                      "engine": self._engine_tag})

    def _flush_prefix_cache_locked(self) -> None:
        """Drop every cached prefix across all models' trees.
        Refcount-0 blocks return to the free list via release_cached;
        a block some racing admission still holds is merely unmarked
        and frees on its last decref.  Caller holds _kv_lock."""
        for tree in self._radix.values():
            stack = list(tree.root.children.values())
            while stack:
                node = stack.pop()
                stack.extend(node.children.values())
                self._alloc.release_cached(node.block)
        self._radix = {}
        if self._states is not None:
            self._states.drop_all()

    def _take_state_locked(self) -> Optional[int]:
        """A free state id, at the price of a checkpoint nobody holds
        (`StateAllocator.evict_lru`'s choice) where none is free.  Caller
        holds _kv_lock."""
        sid = self._states.alloc()
        if sid is None and self._states.evict_lru():
            self._state_counts["snapshot_evictions"] += 1
            sid = self._states.alloc()
        return sid

    # -- multiplexing ------------------------------------------------------
    def _load_model(self, model_id: str):
        """Resolve + merge an adapter.  ObjectRef specs are fetched
        from the object store (the PR-4 binary transfer plane moves
        the bytes when the ref lives on another node)."""
        if model_id == "":
            return self._base_params
        spec = self._adapters.get(model_id)
        if spec is None:
            raise KeyError(f"unknown multiplexed model {model_id!r} "
                           f"(registered: {sorted(self._adapters)})")
        if type(spec).__name__ == "ObjectRef" or hasattr(spec, "id"):
            import ray_tpu
            spec = ray_tpu.get(spec)
        from ray_tpu.serve.multiplex import merge_adapter
        return merge_adapter(self._base_params, spec)

    def _swap_model(self, model_id: str) -> None:
        """Hot-swap the active adapter.  Same shapes -> the compiled
        prefill/decode steps are reused; swap cost is the LRU-missed
        merge + weight upload only.  Caller (dispatcher) guarantees no
        live slots and an empty pipeline."""
        with self._kv_lock:
            params = self._models.get(model_id)
        if params is None:
            # Merge outside the lock (jax work); only the dict
            # mutations below need it (resident_models()/kv_stats()
            # iterate _models from other threads).
            params = self._load_model(model_id)
        with self._kv_lock:
            self._models[model_id] = params
            while len(self._models) > self._max_resident:
                # Never evict the base entry ("" is also the merge
                # source for every future adapter) or the adapter
                # being swapped IN (max_resident_models=1 would
                # otherwise evict it right here and the activation
                # below would KeyError).
                for mid in self._models:
                    if mid != "" and mid != model_id:
                        del self._models[mid]
                        break
                else:
                    break
            self._models.move_to_end(model_id)
        self.params = params
        self._model_id = model_id

    def _can_swap(self) -> bool:
        with self._state_lock:
            busy = any(r is not None for r in self._owner)
        return not busy and not self._inflight

    # -- admission ---------------------------------------------------------
    def _try_admit(self, req: "_Request"):
        """Reserve blocks for `req`.  Returns True (admitted: blocks +
        prefix share installed on the request), None (transient
        exhaustion -> caller keeps it queued: backpressure), or
        "cache" (this single request exceeds the whole pool / its
        table and can NEVER be admitted)."""
        bs = self.block_size
        plen = len(req.prompt)
        want = plen + req.max_new
        # Positions are bounded by the table AND max_len: the table
        # rounds max_len UP to a block multiple, and decoding into
        # that rounding slack would run past the configured max_len
        # (and potentially cfg.max_seq, where gpt2's pos-embed clip
        # silently reuses the last embedding).
        hard_cap = min(self.table_width * bs, self.max_len)
        alloc_tokens = min(want, hard_cap)
        total_blocks = -(-alloc_tokens // bs)
        if plen + 1 > hard_cap or total_blocks > self.num_blocks:
            return "cache"
        with self._kv_lock:
            prefix_blocks: List[int] = []
            if self.prefix_cache_enabled:
                t_m = time.perf_counter()
                tree = self._radix_for(req.model_id)
                if self._states is None:
                    prefix_blocks = tree.match(req.prompt)
                else:
                    # usable as far as a checkpoint reaches: h of m blocks
                    matched, h, state_from = tree.match_with_state(
                        req.prompt)
                    prefix_blocks = matched[:h]
                    # held BEFORE any sweep, as the blocks are: taking this
                    # request's own id may cost a checkpoint, never this one
                    req._state_held = state_from
                    if state_from:
                        self._states.hold(state_from)
                self.host_s["radix_match"] += time.perf_counter() - t_m
                # Hold the matched blocks BEFORE the eviction sweep so
                # it can never reclaim them out from under the hit (the
                # sweep skips refcount > 0).
                self._alloc.incref_many(prefix_blocks)
            try:
                need = total_blocks - len(prefix_blocks)
                if need > self._alloc.available():
                    t_e = time.perf_counter()
                    self._evict_locked(need - self._alloc.available())
                    self.host_s["evict"] += time.perf_counter() - t_e
                if need > self._alloc.available():
                    # backpressure: undo hold
                    self._alloc.decref_many(prefix_blocks)
                    self._unhold_state_locked(req)
                    return None
                if self._states is not None:
                    t_s = time.perf_counter()
                    own_state = self._take_state_locked()
                    self.host_s["state"] += time.perf_counter() - t_s
                    if own_state is None:   # every id a live request's
                        self._alloc.decref_many(prefix_blocks)
                        self._unhold_state_locked(req)
                        return None
                # Count queries/hits per ADMITTED request, not per
                # attempt: a backpressured request retries admission
                # every tick and would otherwise inflate the hit ratio.
                if self.prefix_cache_enabled:
                    self._cache_queries += 1
                    km = _get_kv_metrics()
                    if km is not None:
                        km["queries"].inc()
                    if prefix_blocks:
                        self._cache_hits += 1
                        self._cache_hit_tokens += len(prefix_blocks) * bs
                        if km is not None:
                            km["hits"].inc()
                new_blocks = self._alloc.alloc(need)
                req._blocks = prefix_blocks + (new_blocks or [])
                req._table = np.asarray(req._blocks, np.int32)
                if self._states is not None:
                    t_s = time.perf_counter()
                    self._admit_state_locked(
                        req, own_state,
                        len(matched) if self.prefix_cache_enabled else 0,
                        len(prefix_blocks))
                    self.host_s["state"] += time.perf_counter() - t_s
            except Exception:
                # Exception edge between incref and handoff (a raising
                # eviction sweep / metric sink): the prefix holds would
                # leak forever — _retire only frees blocks that made it
                # into req._blocks.  RT013 self-finding.
                self._alloc.decref_many(prefix_blocks)
                self._unhold_state_locked(req)
                raise
        req.cached_tokens = req._prefilled = len(prefix_blocks) * bs
        req.cache_hit = bool(prefix_blocks)
        req._pos_cap = alloc_tokens
        return True

    def _unhold_state_locked(self, req: "_Request") -> None:
        if self._states is not None and req._state_held:
            self._states.release(req._state_held)
            req._state_held = 0

    def _admit_state_locked(self, req: "_Request", sid: int, m: int,
                            h: int) -> None:
        """The state side of an admission whose radix match was `m` blocks
        of which `h` are used (the deepest checkpoint, held since the match
        as `req._state_held`, until the dispatch that reads it is
        launched): `sid`
        for the request's own state, and at most two checkpoints to leave
        behind, known here, before the dispatch: at block m where m > h
        and the tree BRANCHES there (where this prompt left what was
        cached: the next prompt to branch there hits in full; where the
        prompt only lengthens the path, the checkpoint below would
        supersede this one the moment both are adopted) and at the prompt's
        last whole block (what the conversation's next turn matches
        through).  A checkpoint id that cannot be had is skipped and
        counted; an admission never waits on one.  Caller holds _kv_lock."""
        counts = self._state_counts
        req._state_id = sid
        req._state_from = req._ckpt_base = 0
        if req._state_held:
            req._state_from, req._ckpt_base = req._state_held, h
            self._states.touch(req._state_held)
            counts["restores"] += 1
        counts["matched_tokens"] += m * self.block_size
        counts["unbacked_tokens"] += (m - h) * self.block_size
        if 0 < m == h:
            counts["full_restores"] += 1
        if self.prefix_cache_enabled and self._tile == self.block_size:
            last = len(req.prompt) // self.block_size
            for depth in sorted({m, last}):
                if depth <= h or (depth < last and not self._radix_for(
                        req.model_id).branches_at(req.prompt, depth)):
                    continue
                ckpt = self._take_state_locked()
                if ckpt is None:
                    counts["snapshots_skipped"] += 1
                else:
                    req._ckpts[depth] = ckpt

    def _release_state_locked(self, req: "_Request") -> None:
        """What a request still has of the state pool goes back: its own id
        (`later`: a decode step of it may have been launched already), the
        checkpoints no node took, its hold.  Caller holds _kv_lock."""
        if req._state_held:
            self._states.release(req._state_held)
            req._state_held = 0
        for sid in (req._state_id, *req._ckpts.values()):
            if sid:
                self._states.free(sid, later=True)
        req._state_id = 0
        req._ckpts = {}

    def _tiles_left(self, req: "_Request") -> int:
        return -(-(len(req.prompt) - req._prefilled) // self._tile)

    def _admit(self, free: List[int], room: int) -> List[tuple]:
        """FIFO admission with head-of-line backpressure: pop waiting
        requests while slots, blocks AND the dispatch's `room` (prefill
        rows) last; the last one admitted may bring more tiles than are
        left and finishes over the next dispatches.  A model mismatch at
        the head drains current-model slots, then hot-swaps."""
        admitted: List[tuple] = []
        while self._waiting and len(admitted) < len(free) and room > 0:
            req = self._waiting[0]
            if req.done.is_set():          # failed/cancelled upstream
                self._waiting.popleft()
                continue
            if req.model_id != self._model_id:
                if admitted or not self._can_swap():
                    break                  # drain, then swap next tick
                try:
                    self._swap_model(req.model_id)
                except Exception as e:     # unknown adapter/fetch fail
                    self._waiting.popleft()
                    self._finish_request(req, error=e)
                    continue
            got = self._try_admit(req)
            if got is None:
                break                      # queue for blocks
            self._waiting.popleft()
            if got == "cache":
                # A single request larger than the whole pool: the
                # one case that still reports finish_reason "cache".
                self._finish_request(req, reason="cache")
                continue
            admitted.append((free[len(admitted)], req))
            room -= self._tiles_left(req)
        return admitted

    def _pop_admissions(self, free: List[int]) -> List[tuple]:
        """This dispatch's prefill: [(slot, req)], requests that hold a
        slot with tiles still to come first, then new admissions."""
        # Apply a parked failure BEFORE pulling new submissions out of
        # _pending: only requests that were already waiting when the
        # engine failed get the error — anything submitted after the
        # failure (still in _pending) is served by the recovered
        # engine.
        err, self._waiting_fail = self._waiting_fail, None
        if err is not None:         # parked by a processor _fail_all
            self._drain_waiting(err)
        while True:                 # drain submit queue -> FIFO deque
            try:
                self._waiting.append(self._pending.get_nowait())
            except queue.Empty:
                break
        # One dispatch carries at most the widest program's rows.  Requests
        # whose prompt has tiles still to come go first, oldest admission
        # first (they hold their slots already; one admission's requests
        # took slots in ascending order); then the queue, while rows last.
        with self._state_lock:
            held = sorted(((i, r) for i, r in enumerate(self._owner)
                           if r is not None and r._prefilling
                           and not r.done.is_set()),
                          key=lambda sr: sr[1]._admit_t)
        room = self._prefill_rows[-1]
        batch = []
        for slot, req in held:
            if room <= 0:
                break
            batch.append((slot, req))
            room -= self._tiles_left(req)
        if free and self._waiting:
            batch += self._admit(free, room)
        return batch

    # -- engine ------------------------------------------------------------
    def _retire(self, slot: int, req: _Request) -> None:
        with self._state_lock:
            if self._owner[slot] is req:
                self._owner[slot] = None
        req._finish()
        if req.stream_q is not None:
            req.stream_q.put(_STREAM_END)
        with self._kv_lock:
            if req._blocks and not req._blocks_freed:
                req._blocks_freed = True
                self._alloc.decref_many(req._blocks)
            if self._states is not None:
                self._release_state_locked(req)
        self._update_kv_gauges()

    def _finish_request(self, req: "_Request",
                        error: Optional[Exception] = None,
                        reason: str = "") -> None:
        """Terminal bookkeeping for a request that never reaches
        _retire (failed, rejected, or swept before getting a slot)."""
        if error is not None:
            req.error = error
        if reason:
            req.finish_reason = reason
        req._finish()
        if req.stream_q is not None:
            req.stream_q.put(_STREAM_END)

    def _fail_all(self, e: Exception) -> None:
        # Snapshot the slot table under _state_lock (the dispatcher
        # mutates _owner concurrently; an RT010 self-finding), then
        # retire outside it — _retire takes the lock itself.
        with self._state_lock:
            owned = [(i, req) for i, req in enumerate(self._owner)
                     if req is not None]
        for i, req in owned:
            req.error = e
            self._retire(i, req)
        while not self._pending.empty():
            try:
                req = self._pending.get_nowait()
            except queue.Empty:
                break
            self._finish_request(req, error=e)
        # Drain (don't clear): each in-flight entry holds a pipeline
        # permit that must come back, and popleft is atomic against a
        # concurrently-draining processor.
        while True:
            try:
                self._inflight.popleft()
            except IndexError:
                break
            self._slots_sem.release()
        with self._dev_lock:        # nothing is in flight any more
            if self._empty_since is None:
                self._empty_since = time.perf_counter()
        # _post_admit inserts a batch's blocks into the radix tree at
        # LAUNCH, so a dispatch that later fails device-side leaves
        # cached blocks whose KV was never written — a prefix hit on
        # them would silently decode garbage.  Every owner is retired
        # above (blocks decref'd); drop the whole prefix cache so
        # nothing can match unwritten KV.
        with self._kv_lock:
            self._flush_prefix_cache_locked()
        self._update_kv_gauges()
        # _waiting is dispatcher-only and _admit's peek-then-popleft
        # is not atomic, so a processor-thread failure must not drain
        # the deque here — park the error and let the dispatcher fail
        # the queue at its next _pop_admissions tick.  On the
        # dispatcher thread itself draining now is safe (and keeps the
        # parked error from leaking onto requests submitted AFTER the
        # failure).
        if threading.current_thread() is self._thread \
                or (self._shutdown and not self._thread.is_alive()):
            # Dispatcher thread itself, or stop() after the join —
            # either way no dispatcher can race the deque.
            self._drain_waiting(e)
        else:
            self._waiting_fail = e

    def _drain_waiting(self, e: Exception) -> None:
        while self._waiting:
            req = self._waiting.popleft()
            if not req.done.is_set():
                self._finish_request(req, error=e)

    def _drained(self, slot: int, req: "_Request") -> bool:
        """Everything `req` needs is already dispatched (caller holds
        _state_lock)."""
        gen = 1 + self._disp_len[slot] - len(req.prompt)
        return (gen >= req.max_new
                or self._disp_len[slot] >= req._pos_cap)

    def _warmup(self, jnp) -> None:
        """Compile every dispatch shape up front (each fused width + the
        decode-only chunk) so no request ever stalls behind a mid-run
        XLA compile."""
        for N in self._prefill_rows:
            self.caches, _, _ = self._dec.paged_prefill_decode_packed(
                self.params, self.caches,
                jnp.asarray(self._upload.empty(N)), self.cfg,
                self.decode_chunk, self._tile, attn_impl=self._attn_impl)
        self.caches, toks, _ = self._dec.paged_decode_steps(
            self.params, self.caches, jnp.zeros((self.num_slots,), bool),
            self.cfg, self.decode_chunk, attn_impl=self._attn_impl)
        np.asarray(toks)

    def _fused_dispatch(self, jnp, batch: List[tuple], live: List[tuple],
                        active):
        """`batch` as _pop_admissions cut it: every request in it gets at
        least one row.  Its uncached tokens go into rows of `_tile`, in
        order, until the widest program is full; a request cut short there
        comes back with the next dispatch.  `live`: the (slot, request)
        pairs of `active`.  -> (device arrays, rows of the program that
        ran)."""
        T, up, chunk = self._tile, self._upload, self.decode_chunk
        with _Phase(self, SPAN_PACK, "pack"):
            room = self._prefill_rows[-1]
            takes = []
            for _, req in batch:
                tiles = min(self._tiles_left(req), room)
                takes.append(min(len(req.prompt) - req._prefilled,
                                 tiles * T))
                room -= tiles
            N = next(n for n in self._prefill_rows
                     if n >= self._prefill_rows[-1] - room)
            packed = up.empty(N)
            row = 0
            for (slot, req), take in zip(batch, takes):
                done, end = req._prefilled, req._prefilled + take
                first = row
                for start in range(done, end, T):
                    n = min(T, end - start)
                    packed[row, :n] = req.prompt[start:start + n]
                    packed[row, up.scalars] = (n, start, slot, up.MORE)
                    row += 1
                packed[first:row, up.table][:, :len(req._table)] = req._table
                if end == len(req.prompt):
                    packed[row - 1, up.flag] = up.CLOSES
                if self._states is not None:
                    # the request's first row of this call starts from a
                    # checkpoint, its own state or zeros, the others from
                    # the row before; the last leaves the state in its id,
                    # a row that ends where a checkpoint was asked for also
                    # there
                    packed[first:row, up.state_from] = -1
                    packed[first, up.state_from] = req._state_from
                    packed[row - 1, up.state_to][0] = req._state_id
                    for depth, ckpt in req._ckpts.items():
                        r = first + (depth * self.block_size - done) // T - 1
                        if first <= r < row:
                            packed[r, up.state_to][1] = ckpt
            packed[N, up.active] = active
            # The tables the slots decode from once this dispatch's rows
            # have set theirs, and what sets of them share.
            tables = {slot: req._table for slot, req in live}
            for (slot, req), take in zip(batch, takes):
                tables.pop(slot, None)
                if req._prefilled + take == len(req.prompt):
                    tables[slot] = req._table
            shared = find_shared_prefixes(tables, self.block_size,
                                          self.num_slots)
            up.put_sets(packed, *shared)
        devs = self._launch(lambda: self._dec.paged_prefill_decode_packed(
            self.params, self.caches, jnp.asarray(packed),
            self.cfg, chunk, T, attn_impl=self._attn_impl), str(N * T))
        # Launched: only now do the requests move on.
        for (_, req), take in zip(batch, takes):
            req._prefilled += take
            req._state_from = req._state_id     # where the rows left it
            more = req._prefilled < len(req.prompt)
            if more and not req._prefilling:    # the first of several
                self._prefill_counts["multi_chunk_requests"] += 1
            req._prefilling = more
        self._prefill_counts["chunks"] += len(batch)
        self._prefill_counts["chunk_tokens"] += sum(takes)
        self._prefill_counts["padded_tokens"] += N * T
        self._rung_dispatches[str(N * T)] += 1
        # The pass's carried steps are read under the sets of the dispatch
        # before, the steps after it under these.
        admitted = {slot: len(req.prompt) for slot, req in batch}
        self._count_decode_reads(
            [i for i, _ in live if i not in admitted], 1)
        self._hold_shared(shared[0], shared[2])
        self._count_decode_reads(list(tables), chunk - 1, admitted)
        return devs, N

    def _hold_shared(self, members, shared_len) -> None:
        """The sets the device holds from here on (find_shared_prefixes)."""
        self._own_start[:] = 0
        for row, positions in zip(members, shared_len):
            self._own_start[row[row >= 0]] = positions
        self._shared_read = int(shared_len.sum())
        with self._kv_lock:
            reads = self._decode_reads
            reads["shared_programs"] += int((shared_len > 0).sum())
            reads["shared_members"] += int((members >= 0).sum())
            reads["shared_positions"] += self._shared_read

    def _count_decode_reads(self, slots: List[int], steps: int,
                            admitted: Optional[Dict[int, int]] = None
                            ) -> None:
        """`steps` decode steps of `slots`, each at the context the
        dispatches before this one left it (`admitted`: at its prompt),
        under the sets the device holds."""
        with self._state_lock:
            context = sum((admitted or {}).get(i, self._disp_len[i])
                          for i in slots)
        streamed = context + self._shared_read - int(
            self._own_start[slots].sum())
        with self._kv_lock:
            self._decode_reads["context_positions"] += steps * context
            self._decode_reads["streamed_positions"] += steps * streamed

    def _launch(self, call, label: str) -> tuple:
        """`call()`: one of the engine's two programs (decoding.
        paged_prefill_decode_packed, paged_decode_steps), its upload among
        its arguments, handed to the device and its results asked for ->
        (tokens [decode_chunk, B], an expert model's counts or None, both on
        the device; `label`, the program's key in `self.program`; the
        moment the call returned).  When it returns the device has work
        again: the launch is counted and stamped."""
        with _Phase(self, SPAN_LAUNCH, "launch"):
            self.caches, toks, counts = call()
            for dev in (toks, counts):
                try:
                    if dev is not None:
                        dev.copy_to_host_async()
                except Exception:
                    pass
        now = time.perf_counter()
        with self._dev_lock:
            if self._empty_since is not None:
                self.host_s["device_starved"] += now - self._empty_since
                self._empty_since = None
            self.host_s["dispatches"] += 1
        return toks, counts, label, now

    def _count_dispatch(self, counts) -> None:
        """A dispatch's expert counts (None: the model has no expert
        layers), once it has been read."""
        if counts is not None:
            counts = np.asarray(counts).tolist()
            with self._kv_lock:
                self._moe_counts = [a + b for a, b in itertools.zip_longest(
                    self._moe_counts, counts, fillvalue=0)]

    def _post_admit(self, batch: List[tuple]) -> None:
        """Bookkeeping after a fused dispatch launched: radix insertion
        and the gauges."""
        # Optimistic radix insertion AFTER the batch is packed, of the
        # prompt as far as it has been dispatched (a chunked prompt's
        # later blocks follow with their chunks): in-order device
        # execution guarantees these blocks are written before any
        # LATER dispatch's prefill gathers them, but rows within THIS
        # batch run concurrently — so same-batch duplicates must miss
        # (each keeps a private copy) and only future admissions share.
        if self.prefix_cache_enabled:
            t_i = time.perf_counter()
            with self._kv_lock:
                for _, req in batch:
                    ready = None
                    if req._ckpts:      # those whose rows are dispatched
                        ready = {d: req._ckpts.pop(d) for d in list(
                            req._ckpts) if d * self.block_size
                            <= req._prefilled}
                    added = len(ready or ())
                    self._radix_for(req.model_id).insert(
                        req.prompt[:req._prefilled], req._blocks,
                        self._alloc, ready, self._states, req._ckpt_base)
                    if ready is not None:
                        t_s = time.perf_counter()
                        self._state_counts["snapshots"] += added - len(ready)
                        for sid in ready.values():  # a node had one already
                            self._states.free(sid, later=True)
                        self.host_s["state"] += time.perf_counter() - t_s
            self.host_s["radix_insert"] += time.perf_counter() - t_i
        if self._states is not None:
            # the dispatch that reads the checkpoints is launched: they may
            # be evicted again (never handed out as a `state_to` of the
            # dispatch that reads them)
            t_s = time.perf_counter()
            with self._kv_lock:
                for _, req in batch:
                    if req._state_held:
                        self._states.release(req._state_held)
                        req._state_held = 0
            self.host_s["state"] += time.perf_counter() - t_s
        self._update_kv_gauges()

    def _dispatch(self, jnp, span) -> bool:
        """One device dispatch per tick: chunked decode of every live
        slot, with any waiting admissions FUSED into the same dispatch
        (paged_prefill_decode_packed), so an admission costs no
        dispatch of its own.  `span` is the tick's engine.dispatch, which
        is told here what was launched; -> whether anything was."""
        if self._states is not None:
            with self._kv_lock:     # before `live` is read: StateAllocator
                self._states.settle()
        with self._state_lock:
            # A slot is admittable when empty OR "drained": every token
            # its current request needs is already covered by in-flight
            # dispatches (predictable for length/cache finishes — the
            # dispatcher knows max_new).  Re-admitting a drained slot
            # immediately removes the retire->readmit pipeline bubble
            # that cost ~25% of throughput; the old request's entries
            # still deliver its tokens (per-entry pairs + take bounds),
            # and in-order device execution puts the new prefill after
            # the old request's last chunk.  With an eos_id the finish
            # point is NOT predictable, so only empty slots qualify.
            free = [i for i, r in enumerate(self._owner)
                    if r is None or (self.eos_id is None
                                     and self._drained(i, r))]
            # (a slot short of its cap takes the whole chunk: `_disp_len`
            # passes the cap, as it passes prompt + max_new, by up to
            # decode_chunk - 1 steps whose writes stay in the request's own
            # blocks and state, or the scratch block, and whose tokens
            # `_hand_out` drops)
            live = [(i, r) for i, r in enumerate(self._owner)
                    if r is not None and not r._prefilling
                    and self._disp_len[i] < r._pos_cap]
        chunk = self.decode_chunk
        seq = self.host_s["dispatches"]
        admit = _Phase(self, SPAN_ADMIT, waiting=self.queue_depth())
        with admit:
            batch = self._pop_admissions(free)
        # NOTE: slots whose request already has max_new covered by
        # in-flight dispatches stay in the batch anyway — the decode is
        # fixed-shape, so excluding them saves nothing, while skipping
        # the dispatch when "nothing needs tokens" drains the pipeline
        # and costs ~30% throughput (measured).  Their extra tokens are
        # dropped at processing time.
        if not live and not batch:
            span.set_metadata(kind="none")
            return False
        # Only a tick that launches adds to `admit`, as only it adds to
        # `dispatch` and `dispatches` (_engine_loop).
        self.host_s["admit"] += admit.seconds
        active = np.zeros((self.num_slots,), bool)
        for i, _ in live:
            active[i] = True

        if batch:
            # Admission happens HERE (slots are committed); stamp it
            # before the prefill dispatch so compile/dispatch time
            # lands in prefill_s, not queue_s.
            admit_t = time.time()
            try:
                devs, N = self._fused_dispatch(jnp, batch, live, active)
            except Exception as e:
                # The batch is already out of _waiting/_pending with
                # KV blocks held, but not yet in _owner — _fail_all
                # can't reach it.  Fail + retire each request here
                # (retire frees its blocks) before re-raising into
                # the engine loop's recovery path, or callers hang to
                # timeout and the blocks leak for the engine's life.
                for slot, req in batch:
                    req.error = e
                    self._retire(slot, req)
                raise
            # A row whose prompt has chunks still to come holds its slot
            # and yields no token yet; the others are admitted for good.
            admitted = [a for a in batch if not a[1]._prefilling]
            with self._state_lock:
                for slot, req in batch:
                    self._owner[slot] = req
                    req._admit_t = req._admit_t or admit_t
                    # prompt + the steps after the pass (its first token
                    # is the pass's: `chunk` tokens, like every live slot)
                    self._disp_len[slot] = (
                        req._prefilled if req._prefilling
                        else len(req.prompt) + chunk - 1)
            with _Phase(self, SPAN_POST_ADMIT, "post_admit"):
                self._post_admit(batch)
            entry = (devs, admitted, live + admitted, seq)
            # The live slots whose step rode in the pass: all but those
            # this dispatch re-admits (drained: the new request's now).
            admitted_slots = {slot for slot, _ in admitted}
            self._prefill_counts["carried_rows"] += len(
                {i for i, _ in live} - admitted_slots)
            span.set_metadata(kind="fused", live=len(live),
                              positions=N * self._tile, rows=N,
                              admitted=len(batch))
        else:
            key = active.tobytes()
            if key != self._active_key:
                self._active_key = key
                self._active_dev = jnp.asarray(active)
            entry = (self._launch(lambda: self._dec.paged_decode_steps(
                self.params, self.caches, self._active_dev, self.cfg, chunk,
                attn_impl=self._attn_impl), "decode"), (), live, seq)
            self._count_decode_reads([i for i, _ in live], chunk)
            admitted_slots = set()
            span.set_metadata(kind="decode", live=len(live), positions=0,
                              rows=0, admitted=0)
        with self._state_lock:
            for i, _ in live:
                # A drained-readmitted slot already had its _disp_len
                # reset to prompt + chunk - 1 above; adding chunk again
                # would report it "drained" one chunk early and strand
                # its final chunk.
                if i not in admitted_slots:
                    self._disp_len[i] += chunk
        self._inflight.append(entry)
        self._proc_wake.set()
        self.steps += chunk
        if self._sliding_layers:
            with self._state_lock:
                held = [self._disp_len[i]
                        for i, r in enumerate(self._owner) if r is not None]
            window = self.cfg.sliding_window
            with self._kv_lock:
                self._sliding_held += self._sliding_layers * sum(held)
                self._sliding_in_window += self._sliding_layers * sum(
                    min(n, window) for n in held)
        return True

    def _process_entry(self, entry) -> None:
        (toks, counts, label, t_launch), admitted, pairs, seq = entry
        t_read = time.perf_counter()
        with host_span(SPAN_READ_WAIT, seq=seq):
            toks = np.asarray(toks)             # waits for the dispatch
        t_got = time.perf_counter()
        self.host_s["read_wait"] += t_got - t_read
        self.program["device_s"][label] += t_got - max(t_launch,
                                                       self._last_got)
        self.program["dispatches"][label] += 1
        self._last_got = t_got
        with self._dev_lock:
            # In-order processing: the newest launch read back means
            # nothing is in flight.
            if seq + 1 == self.host_s["dispatches"]:
                self._empty_since = t_got
                self._empty_warned = False
        try:
            with host_span(SPAN_HAND_OUT, seq=seq):
                self._count_dispatch(counts)
                self._hand_out(toks, admitted, pairs)
        finally:
            self.host_s["process"] += time.perf_counter() - t_got

    def _warn_device_empty(self) -> None:
        """The processor's idle tick: one line for a stretch of
        DEVICE_EMPTY_WARN_S with nothing in flight and work present, so
        that a run far from the rest leaves its cause in its own log."""
        with self._dev_lock:
            since, warned = self._empty_since, self._empty_warned
        if since is None or warned:
            return
        empty = time.perf_counter() - since
        if empty < DEVICE_EMPTY_WARN_S:
            return
        waiting = self.queue_depth()
        with self._state_lock:
            live = sum(r is not None for r in self._owner)
        if not waiting and not live:
            return
        with self._dev_lock:
            if self._empty_since != since:
                return              # a launch went out meanwhile
            self._empty_warned = True
        print(f"[engine] device empty {empty:.1f} s with {waiting} waiting "
              f"/ {live} live; dispatcher in {self._where}",
              file=sys.stderr, flush=True)

    def _hand_out(self, rows, admitted, pairs) -> None:
        """`rows`: a dispatch's tokens [chunk, B], of either program.  Every
        pair takes its column; for a request this dispatch admitted the
        column's first token is its prompt's first token, and its arrival
        is the request's TTFT."""
        now = time.time()
        for slot, req in admitted:
            req.ttft_s = now - req._t0
            admit = req._admit_t or now
            req.queue_s = max(admit - req._t0, 0.0)
            req.prefill_s = max(now - admit, 0.0)
            req.slot = slot
        # SLO windows (serve autoscaler): TTFT for this entry's
        # admissions; an inter-token-latency sample from the entry
        # cadence — each entry carries len(rows) decode steps, so
        # wall time between consecutive processed entries / chunk is
        # the per-token latency a streaming client observes.
        t_proc = time.time()
        with self._slo_lock:
            for _, req in admitted:
                self._ttft_win.append((t_proc, req.ttft_s))
            if pairs:
                if self._last_entry_t is not None:
                    self._itl_win.append(
                        (t_proc,
                         max(t_proc - self._last_entry_t, 0.0)
                         / max(len(rows), 1)))
                self._last_entry_t = t_proc
        # Column-major with one C-level tolist() + bulk extends:
        # per-token Python in this loop contends the GIL with the
        # dispatcher thread at chunk x B = 256 tokens per entry.
        # Slots are independent streams, so slot-by-slot processing is
        # equivalent to token-major order.
        cols = rows.T.tolist()                # [B][chunk]
        for slot, req in pairs:
            if req.done.is_set():
                continue                      # finished by an earlier entry
            cap = req._pos_cap
            col = cols[slot]
            take = min(len(col),
                       req.max_new - len(req.tokens),
                       cap - len(req.prompt) - len(req.tokens))
            seg = col[:max(take, 0)]
            if self.eos_id is not None and self.eos_id in seg:
                seg = seg[:seg.index(self.eos_id) + 1]
                req.finish_reason = "eos"
            req.tokens.extend(seg)
            if req.stream_q is not None:
                for t in seg:
                    req.stream_q.put(t)
            if req.finish_reason == "eos":
                self._retire(slot, req)
            elif len(req.tokens) >= req.max_new:
                req.finish_reason = "length"
                self._retire(slot, req)
            elif len(req.prompt) + len(req.tokens) >= cap:
                # Dispatch stops at the cap margin, so retire here too
                # or a capped slot would stall unretired.
                req.finish_reason = "cache"
                self._retire(slot, req)

    def _engine_loop(self) -> None:
        import jax.numpy as jnp
        t0 = time.time()
        try:
            self._warmup(jnp)
        except Exception as e:
            # A step that cannot compile or run will not start working
            # later: say so once, fail what is queued, refuse every
            # later submit with the cause, and stop — a replica that
            # stayed up would look healthy and answer nothing.
            import traceback
            traceback.print_exc()
            self._engine_error = e
            self._fail_all(e)
            return
        self.warmup_s = time.time() - t0
        self._warmed = True
        self._where = "loop"
        with self._dev_lock:
            self._empty_since = time.perf_counter()
        while not self._shutdown:
            try:
                # Acquire a pipeline slot, then dispatch; the processor
                # releases slots as it drains entries.
                t_a = time.perf_counter()
                with _Phase(self, SPAN_PERMIT_WAIT):
                    got = self._slots_sem.acquire(timeout=0.05)
                t_b = time.perf_counter()
                self.host_s["permit_wait"] += t_b - t_a
                if not got:
                    continue
                with _Phase(self, SPAN_DISPATCH,
                            seq=self.host_s["dispatches"]) as span:
                    launched = self._dispatch(jnp, span)
                if launched:
                    self.host_s["dispatch"] += time.perf_counter() - t_b
                else:
                    self._slots_sem.release()
                    # Requests a dispatch could not admit (no blocks, a
                    # swap that waits) are work the device is kept from.
                    blocked = bool(self._waiting)
                    with _Phase(self, SPAN_STARVED):
                        self._work.wait(timeout=0.05)
                        self._work.clear()
                    now = time.perf_counter()
                    self.host_s["starved"] += now - t_b
                    with self._dev_lock:
                        if self._empty_since is not None and not blocked:
                            self.host_s["device_unasked"] += (
                                now - self._empty_since)
                            self._empty_since = now
            except Exception as e:
                # An engine failure (e.g. device error) must surface to
                # every waiting caller, not die with the thread and
                # zombify the replica.
                self._slots_sem.release()
                self._fail_all(e)
                time.sleep(0.1)

    def _process_loop(self) -> None:
        while not self._shutdown:
            try:
                entry = self._inflight.popleft()
            except IndexError:
                # Idle: break the ITL cadence chain, or the first
                # entry after an idle gap would record (gap / chunk)
                # as an inter-token-latency sample and spuriously
                # trip the autoscaler's ITL SLO at light load.
                with self._slo_lock:
                    self._last_entry_t = None
                self._warn_device_empty()
                self._proc_wake.wait(timeout=0.05)
                self._proc_wake.clear()
                continue
            try:
                self._process_entry(entry)
            except Exception as e:
                self._fail_all(e)
                time.sleep(0.1)
            finally:
                # One permit per drained entry, whether it processed
                # cleanly or died — pipeline depth must never shrink.
                self._slots_sem.release()
                self._work.set()


class LLMDeployment:
    """Serve deployment wrapping a PagedBatcher.

    Constructor builds (or loads) model params in the replica process —
    on TPU each replica owns the chip its actor reserved.  With
    `adapters={model_id: adapter_spec}` one replica serves many LoRA
    variants: requests routed with
    `handle.options(multiplexed_model_id=...)` hot-swap the merged
    weights (specs may be ObjectRefs — fetched from the object store
    over the binary transfer plane at first use, LRU-resident after).
    """

    def __init__(self, cfg_kwargs: Dict[str, Any], num_slots: int = 8,
                 max_len: int = 256, prompt_pad: int = 64,
                 seed: int = 0, params: Any = None,
                 decode_chunk: int = 8,
                 pipeline_depth: int = 2,
                 kv_block_size: Optional[int] = None,
                 kv_num_blocks: Optional[int] = None,
                 prefix_cache: Optional[bool] = None,
                 adapters: Optional[Dict[str, Any]] = None,
                 max_resident_models: int = 3,
                 max_queue: int = 0,
                 num_states: Optional[int] = None) -> None:
        import jax
        from ray_tpu.models import transformer
        cfg = transformer.TransformerConfig(**cfg_kwargs)
        t0 = time.time()
        if params is None:
            params = jax.block_until_ready(transformer.init_params(
                cfg, jax.random.PRNGKey(seed)))
        self._params_s = time.time() - t0
        self.batcher = PagedBatcher(
            params, cfg, num_slots=num_slots, max_len=max_len,
            prompt_pad=prompt_pad, decode_chunk=decode_chunk,
            pipeline_depth=pipeline_depth,
            kv_block_size=kv_block_size,
            kv_num_blocks=kv_num_blocks,
            prefix_cache=prefix_cache, adapters=adapters,
            max_resident_models=max_resident_models,
            max_queue=max_queue, num_states=num_states)
        # Router probe hook: multiplex-aware pow-2 prefers replicas
        # whose engine already holds the requested adapter merged.
        self.__rtpu_resident_models__ = self.batcher.resident_models
        # Controller hooks: the autoscaler reads real engine SLO
        # signals (queue depth / TTFT p95 / inter-token p95) instead
        # of whole-request latency, and the health sweep caches the
        # engine's per-instance gauge tags so an unclean replica
        # death can zero its ray_tpu_kv_blocks series.
        self.__rtpu_slo_stats__ = self.batcher.slo_snapshot
        self.__rtpu_kv_engine_tags__ = self._kv_engine_tags

    def _kv_engine_tags(self) -> List[str]:
        return [self.batcher._engine_tag]

    @staticmethod
    def _request_model_id() -> str:
        try:
            from ray_tpu.serve.multiplex import get_multiplexed_model_id
            return get_multiplexed_model_id()
        except Exception:
            return ""

    async def generate(self, prompt: List[int],
                       max_new: int = 32) -> Dict[str, Any]:
        """Generate up to `max_new` tokens.  Returns the tokens plus a
        TTFT decomposition; the breakdown also carries
        `cache_hit`/`cached_tokens` (prefix-cache reuse: a hit
        skips device prefill for the cached prefix, so hit TTFT is
        route + queue + suffix prefill only)."""
        import asyncio
        import time as _time
        route_t0 = _time.time()
        req = self.batcher.submit(prompt, max_new,
                                  model_id=self._request_model_id())
        # The engine wakes this coroutine itself.  (Parking a thread of the
        # loop's default executor on req.done.wait let only min(32, CPUs +
        # 4) replies be watched at a time: with more requests in flight a
        # finished reply waited behind older ones, and a closed loop of
        # short dispatches ran its slots dry.)
        loop = asyncio.get_running_loop()
        woken = loop.create_future()

        def wake() -> None:
            loop.call_soon_threadsafe(
                lambda: woken.done() or woken.set_result(True))
        req._on_done = wake
        if req.done.is_set():       # finished before the hook was there
            wake()
        try:
            await asyncio.wait_for(woken, 300.0)
        except asyncio.TimeoutError:
            raise TimeoutError("generation timed out after 300s") from None
        if req.error is not None:
            raise req.error
        # TTFT decomposition spans: route (replica hop -> engine
        # submit), queue (slot wait), prefill (device prefill +
        # transfer to first token) — recorded into the request's trace
        # so timeline() shows where Serve TTFT milliseconds go.
        try:
            from ray_tpu.util import profiling
            admit = req._admit_t or req._t0
            first_tok = req._t0 + req.ttft_s
            profiling.record_span("llm.route", route_t0, req._t0)
            profiling.record_span("llm.queue", req._t0, admit)
            profiling.record_span("llm.prefill", admit, first_tok)
        except Exception:
            pass
        return {"tokens": req.tokens, "ttft_s": req.ttft_s,
                "finish_reason": req.finish_reason,
                "cache_hit": req.cache_hit,
                "cached_tokens": req.cached_tokens,
                "ttft_breakdown": {
                    "route_s": max(req._t0 - route_t0, 0.0),
                    "queue_s": req.queue_s,
                    "prefill_s": req.prefill_s,
                    "cache_hit": req.cache_hit,
                }}

    def generate_stream(self, prompt: List[int],
                        max_new: int = 32) -> Iterator[int]:
        """Streaming generator method: serve routes this through the
        streaming-generator task plane, the proxy turns it into SSE.
        Honors `multiplexed_model_id` like generate()."""
        yield from self.batcher.generate_stream(
            prompt, max_new, model_id=self._request_model_id())

    def __call__(self, prompt: List[int]) -> Dict[str, Any]:
        return self.batcher.generate(
            prompt, model_id=self._request_model_id())

    def stats(self) -> Dict[str, Any]:
        """Engine counters plus where this replica runs: the jax
        backend and device it computes on, its process and the chips
        it leased, and whether warm-up finished (or how it failed)."""
        import jax
        import ray_tpu
        b = self.batcher
        dev = jax.devices()[0]
        return {"steps": b.steps, "warmed": b._warmed,
                "params_s": self._params_s, "warmup_s": b.warmup_s,
                "engine_error": (repr(b._engine_error)
                                 if b._engine_error is not None else None),
                "backend": jax.default_backend(),
                "device_kind": dev.device_kind,
                "device_count": jax.device_count(),
                # None where the backend keeps no statistics (CPU).
                "peak_bytes": (dev.memory_stats() or {}).get(
                    "peak_bytes_in_use"),
                "pid": os.getpid(), "chips": ray_tpu.get_tpu_ids(),
                "host": b.host_stats(), "program": b.program_stats(),
                **b.kv_stats()}
