"""Serve streaming data plane: engine token streams, streaming handles
(ObjectRefGenerator through the router), and SSE over the HTTP proxy.

Reference analogs: serve/_private/proxy.py:779 (HTTPProxy streaming
replica calls), serve/handle.py DeploymentResponseGenerator,
serve/_private/long_poll.py (config push, exercised implicitly by the
router's long-poll thread)."""

import http.client
import json

import numpy as np
import pytest

import ray_tpu
from ray_tpu import serve


@pytest.fixture
def serve_session(ray_start):
    yield ray_tpu
    serve.shutdown()


def test_streaming_handle(serve_session):
    @serve.deployment
    class Counter:
        def counts(self, n):
            for i in range(n):
                yield {"i": i}

    h = serve.run(Counter)
    gen = h.counts.options(stream=True).remote(4)
    items = [ray_tpu.get(ref, timeout=30) for ref in gen]
    assert items == [{"i": i} for i in range(4)]


def test_streaming_handle_error_propagates(serve_session):
    @serve.deployment
    class Bad:
        def boom(self, n):
            yield 1
            raise ValueError("stream-kaboom")

    h = serve.run(Bad)
    gen = h.boom.options(stream=True).remote(1)
    it = iter(gen)
    assert ray_tpu.get(next(it), timeout=30) == 1
    with pytest.raises(Exception, match="stream-kaboom"):
        for ref in it:
            ray_tpu.get(ref, timeout=30)


def _read_sse(host, port, path):
    conn = http.client.HTTPConnection(host, port, timeout=60)
    conn.request("GET", path, headers={"Accept": "text/event-stream"})
    resp = conn.getresponse()
    assert resp.status == 200
    assert resp.headers["Content-Type"] == "text/event-stream"
    events = []
    buf = b""
    while True:
        chunk = resp.read(1)
        if not chunk:
            break
        buf += chunk
    conn.close()
    for block in buf.decode().split("\n\n"):
        if not block.strip():
            continue
        ev = {"event": "message"}
        for line in block.splitlines():
            k, _, v = line.partition(": ")
            ev[k if k in ("event", "data") else "event"] = v
        events.append(ev)
    return events


def test_http_sse_streaming(serve_session):
    @serve.deployment
    class Ticker:
        def tick(self, arg):
            for i in range(3):
                yield i * 10

    serve.run(Ticker)
    srv = serve.start_http_proxy(port=0)
    host, port = srv.server_address
    events = _read_sse(host, port, "/Ticker/tick?stream=1")
    datas = [json.loads(e["data"]) for e in events
             if e["event"] == "message"]
    assert datas == [0, 10, 20]
    assert events[-1]["event"] == "end"


@pytest.mark.parametrize("arch", ["llama", "gpt2"])
def test_llm_engine_stream_matches_generate(serve_session, arch,
                                            lm_params):
    from ray_tpu.models import transformer
    from ray_tpu.serve.llm import PagedBatcher
    cfg = transformer.TransformerConfig(
        vocab_size=128, d_model=64, n_layers=2, n_heads=2, max_seq=64,
        arch=arch, remat=False, xent_chunk=None,
        attn_impl="reference")
    bat = PagedBatcher(lm_params(cfg, 0), cfg, num_slots=2, max_len=48,
                       prompt_pad=8, kv_block_size=4)
    try:
        ref_out = bat.generate([1, 2, 3], max_new=6)
        streamed = list(bat.generate_stream([1, 2, 3], max_new=6))
        assert streamed == ref_out["tokens"]
    finally:
        bat.stop()


@pytest.mark.parametrize("arch", ["llama", "gpt2"])
def test_llm_deployment_streams_tokens(serve_session, arch):
    from ray_tpu.serve.llm import LLMDeployment
    dep = serve.deployment(LLMDeployment).bind(
        cfg_kwargs=dict(vocab_size=128, d_model=64, n_layers=2,
                        n_heads=2, max_seq=64, arch=arch,
                        remat=False, attn_impl="reference"),
        num_slots=2, max_len=48, prompt_pad=8, kv_block_size=4)
    h = serve.run(dep, name="llm")
    # Generous timeouts: under a full parallel suite on the 1-vCPU
    # host, engine warmup compiles contend with every other test.
    whole = ray_tpu.get(h.generate.remote([5, 6], max_new=5),
                        timeout=300)
    gen = h.generate_stream.options(stream=True).remote([5, 6], 5)
    toks = [ray_tpu.get(r, timeout=300) for r in gen]
    assert toks == whole["tokens"]
    assert len(toks) == 5


def test_engine_eos_retirement(serve_session):
    """With an eos_id the drained-slot pre-admission is disabled (the
    finish point is unpredictable) and generation stops AT the eos
    token; slots still recycle for later requests."""
    import jax
    from ray_tpu.models import transformer
    from ray_tpu.serve.llm import PagedBatcher
    cfg = transformer.TransformerConfig(
        vocab_size=64, d_model=32, n_layers=1, n_heads=2, max_seq=64,
        arch="llama", remat=False, attn_impl="reference")
    params = transformer.init_params(cfg, jax.random.PRNGKey(3))
    bat = PagedBatcher(params, cfg, num_slots=2, max_len=48,
                       prompt_pad=8, decode_chunk=4, kv_block_size=4)
    try:
        # Find what the greedy model emits, then declare one of the
        # early tokens as EOS for a second batcher run.
        probe = bat.generate([1, 2], max_new=8)["tokens"]
    finally:
        bat.stop()
    eos = probe[2]
    first = probe.index(eos)             # stops at the FIRST occurrence
    bat = PagedBatcher(params, cfg, num_slots=2, max_len=48,
                       prompt_pad=8, decode_chunk=4, kv_block_size=4,
                       eos_id=eos)
    try:
        out = bat.generate([1, 2], max_new=8)
        assert out["finish_reason"] == "eos"
        assert out["tokens"] == probe[:first + 1]
        assert out["tokens"][-1] == eos
        # Slots recycle after eos retirement.
        out2 = bat.generate([3, 4], max_new=3)
        assert len(out2["tokens"]) <= 3 and out2["tokens"]
    finally:
        bat.stop()
