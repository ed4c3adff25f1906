"""Serve tests: deployments, routing, batching, replica recovery, and
the continuous-batching LLM engine vs a full-forward oracle.

Reference analogs: serve/_private/controller.py:84 (controller),
pow_2_scheduler.py:52 (router), serve/batching.py:468 (@serve.batch).
"""

import os
import time

import numpy as np
import pytest

import ray_tpu
from ray_tpu import serve


@pytest.fixture
def serve_session(ray_start):
    yield ray_tpu
    serve.shutdown()


def test_deploy_and_call(serve_session):
    @serve.deployment(num_replicas=1)
    class Doubler:
        def __call__(self, x):
            return x * 2

    h = serve.run(Doubler)
    assert ray_tpu.get(h.remote(21), timeout=60) == 42


def test_slow_sync_method_does_not_hold_back_other_replies(serve_session):
    """A synchronous method runs on a thread of the replica, not on its
    event loop: while one call sleeps for seconds, a coroutine of the same
    replica still answers (on the loop it waited for the sleeper: a
    profiler stopping for 20 s held back every reply of an LLM replica)."""
    @serve.deployment(num_replicas=1)
    class Mixed:
        def slow(self, seconds):
            time.sleep(seconds)
            return "slept"

        async def quick(self):
            return "quick"

    h = serve.run(Mixed)
    assert ray_tpu.get(h.quick.remote(), timeout=60) == "quick"
    sleeper = h.slow.remote(6.0)
    time.sleep(0.5)                     # the sleeper is running
    t0 = time.time()
    assert ray_tpu.get(h.quick.remote(), timeout=60) == "quick"
    assert time.time() - t0 < 3.0
    assert ray_tpu.get(sleeper, timeout=60) == "slept"


def test_multi_replica_routing(serve_session):
    @serve.deployment(num_replicas=2)
    class Who:
        def pid(self):
            return os.getpid()

    h = serve.run(Who)
    pids = {ray_tpu.get(h.method("pid").remote(), timeout=60)
            for _ in range(12)}
    assert len(pids) == 2           # pow-2 spreads over both replicas


def test_redeploy_scales(serve_session):
    """Scale-up must be visible to an EXISTING handle (router refresh)."""
    @serve.deployment(num_replicas=1)
    class S:
        def pid(self):
            return os.getpid()

    h = serve.run(S)
    p1 = ray_tpu.get(h.method("pid").remote(), timeout=60)
    assert p1 > 0
    serve.run(S.options(num_replicas=3))
    st = serve.status()["S"]
    assert st["target_replicas"] == 3
    deadline = time.time() + 15
    pids = set()
    while time.time() < deadline and len(pids) < 2:
        time.sleep(0.5)   # past the router's refresh interval
        pids.add(ray_tpu.get(h.method("pid").remote(), timeout=60))
    assert len(pids) >= 2


def test_redeploy_replaces_code(serve_session):
    """A redeploy with different init args must replace running
    replicas (version-driven rollout), not keep serving old state."""
    @serve.deployment(num_replicas=1)
    class V:
        def __init__(self, tag):
            self.tag = tag

        def read(self):
            return self.tag

    h = serve.run(V.bind("v1"))
    assert ray_tpu.get(h.method("read").remote(), timeout=60) == "v1"
    serve.run(V.bind("v2"))
    deadline = time.time() + 15
    got = None
    while time.time() < deadline:
        time.sleep(0.5)
        try:
            got = ray_tpu.get(h.method("read").remote(), timeout=60)
            if got == "v2":
                break
        except Exception:
            pass    # old replica torn down mid-call
    assert got == "v2"


def test_serve_batch_accumulates(serve_session):
    @serve.deployment(num_replicas=1, max_concurrent_queries=32)
    class Batched:
        def __init__(self):
            self.batch_sizes = []

        @serve.batch(max_batch_size=8, batch_wait_timeout_s=0.05)
        async def __call__(self, xs):
            self.batch_sizes.append(len(xs))
            return [x + 1 for x in xs]

        def sizes(self):
            return self.batch_sizes

    h = serve.run(Batched)
    refs = [h.remote(i) for i in range(16)]
    assert ray_tpu.get(refs, timeout=60) == [i + 1 for i in range(16)]
    sizes = ray_tpu.get(h.method("sizes").remote(), timeout=60)
    assert max(sizes) > 1           # batching actually happened
    assert sum(sizes) == 16


def test_replica_failure_recovery(serve_session):
    @serve.deployment(num_replicas=2)
    class P:
        def pid(self):
            return os.getpid()

    h = serve.run(P)
    victim_pid = ray_tpu.get(h.method("pid").remote(), timeout=60)
    os.kill(victim_pid, 9)
    deadline = time.time() + 30
    ok = 0
    while time.time() < deadline and ok < 6:
        try:
            assert ray_tpu.get(h.method("pid").remote(), timeout=30) > 0
            ok += 1
        except Exception:
            time.sleep(0.2)
    assert ok >= 6                  # service keeps answering


def _tiny_cfg(arch):
    from ray_tpu.models.transformer import TransformerConfig
    import jax.numpy as jnp
    return TransformerConfig(vocab_size=97, d_model=32, n_heads=4,
                             n_kv_heads=2, n_layers=2, d_ff=64,
                             max_seq=128, dtype=jnp.float32,
                             remat=False, arch=arch)


def _make_batcher(params, cfg, num_slots, max_len, prompt_pad=16):
    from ray_tpu.serve.llm import PagedBatcher
    return PagedBatcher(params, cfg, num_slots=num_slots,
                        max_len=max_len, prompt_pad=prompt_pad,
                        kv_block_size=4)


@pytest.mark.parametrize("arch", ["llama", "gpt2"])
def test_continuous_batcher_matches_full_forward(arch, lm_params):
    """Greedy decode through the KV-cache engine == greedy decode via
    repeated full forward passes (the no-cache oracle), for both dense
    architectures (gpt2: learned positions and biases)."""
    from ray_tpu.models import transformer

    cfg = _tiny_cfg(arch)
    params = lm_params(cfg, 0)
    bat = _make_batcher(params, cfg, num_slots=4, max_len=64)
    prompts = [[5, 9, 11], [3], [60, 2, 8, 40, 7]]
    outs = [bat.generate(p, max_new=8) for p in prompts]
    bat.stop()

    for prompt, out in zip(prompts, outs):
        seq = list(prompt)
        want = []
        for _ in range(8):
            logits = transformer.forward(
                params, np.asarray([seq], np.int32), cfg)
            nxt = int(np.argmax(np.asarray(logits[0, -1])))
            want.append(nxt)
            seq.append(nxt)
        assert out["tokens"] == want, (prompt, out["tokens"], want)


@pytest.mark.parametrize("arch", ["llama", "gpt2"])
def test_continuous_batcher_concurrent_slots(arch, lm_params):
    """Interleaved requests (continuous batching) decode correctly."""
    from ray_tpu.models import transformer

    cfg = _tiny_cfg(arch)
    params = lm_params(cfg, 1)
    bat = _make_batcher(params, cfg, num_slots=2, max_len=64)
    # 5 concurrent requests through 2 slots forces queueing + slot reuse.
    reqs = [bat.submit([i + 1, i + 2], max_new=6) for i in range(5)]
    for r in reqs:
        assert r.done.wait(120)
    bat.stop()
    for i, r in enumerate(reqs):
        seq = [i + 1, i + 2]
        want = []
        for _ in range(6):
            logits = transformer.forward(
                params, np.asarray([seq], np.int32), cfg)
            nxt = int(np.argmax(np.asarray(logits[0, -1])))
            want.append(nxt)
            seq.append(nxt)
        assert r.tokens == want


def test_model_multiplexing(serve_session):
    """LRU model multiplexing + model-aware routing (reference:
    serve/multiplex.py, multiplex-aware pow-2 scheduling)."""
    import time as _time

    @serve.deployment(num_replicas=2)
    class Multi:
        def __init__(self):
            self.loads = []

        @serve.multiplexed(max_num_models_per_replica=2)
        async def get_model(self, model_id: str):
            self.loads.append(model_id)
            return {"id": model_id, "scale": int(model_id[1:])}

        async def __call__(self, x):
            model = await self.get_model(
                serve.get_multiplexed_model_id())
            return {"y": x * model["scale"], "model": model["id"],
                    "loads": list(self.loads)}

    h = serve.run(Multi)
    out = ray_tpu.get(h.method("__call__").options(
        multiplexed_model_id="m3").remote(7), timeout=60)
    assert out == {"y": 21, "model": "m3", "loads": ["m3"]}
    # Same model again: served from cache somewhere (loads don't grow
    # beyond one per replica that ever saw it).
    outs = [ray_tpu.get(h.method("__call__").options(
        multiplexed_model_id="m3").remote(1), timeout=60)
        for _ in range(4)]
    assert all(o["y"] == 3 for o in outs)
    assert all(o["loads"].count("m3") == 1 for o in outs)
    # LRU eviction: 3 models through a 2-model cache reloads the first
    # on a third pass ONLY if it was evicted; just assert correctness.
    for mid, scale in (("m5", 5), ("m8", 8), ("m5", 5)):
        o = ray_tpu.get(h.method("__call__").options(
            multiplexed_model_id=mid).remote(2), timeout=60)
        assert o["y"] == 2 * scale


def test_app_graph_build_plan():
    """serve.build resolves nested .bind() graphs bottom-up with handle
    injection, diamond sharing, and name-collision suffixing
    (reference: _private/deployment_graph_build.py:17)."""
    @serve.deployment
    class Leaf:
        def __init__(self, tag):
            self.tag = tag

    @serve.deployment
    class Mid:
        def __init__(self, left, right):
            pass

    shared = Leaf.bind("shared")
    other = Leaf.bind("other")           # distinct Leaf -> name suffix
    mid_a = Mid.bind(shared, other)
    mid_b = Mid.bind(shared, {"nested": [shared]})

    @serve.deployment
    class Root:
        def __init__(self, a, b):
            pass

    plan = serve.build(Root.bind(mid_a, mid_b))
    names = [n for n, *_ in plan]
    # Dependencies come before their parents; shared Leaf appears once.
    assert names.index("Leaf") < names.index("Mid")
    assert names.count("Leaf") == 1 and "Leaf_1" in names
    assert names[-1] == "Root"
    assert len(plan) == 5                # 2 leaves + 2 mids + root
    # Injected args are handles, including inside containers.
    root_args = plan[-1][2]
    assert all(isinstance(a, serve.DeploymentHandle) for a in root_args)
    mid_b_args = [e for e in plan if e[0] == "Mid_1"][0][2]
    assert isinstance(mid_b_args[1]["nested"][0], serve.DeploymentHandle)
    assert mid_b_args[0].deployment_name == "Leaf"

    # Forced root name wins over a colliding child name.
    plan2 = serve.build(Root.bind(Leaf.bind("x")), name="Leaf")
    assert plan2[-1][0] == "Leaf" and plan2[0][0] == "Leaf_1"

    # namedtuple init args survive injection.
    import collections
    Pair = collections.namedtuple("Pair", ["m", "tag"])
    plan3 = serve.build(Root.bind(Pair(m=Leaf.bind("y"), tag=7), None))
    pair = plan3[-1][2][0]
    assert isinstance(pair, Pair) and pair.tag == 7
    assert isinstance(pair.m, serve.DeploymentHandle)


def test_app_graph_deploys_in_one_run(serve_session):
    """A 3-deployment pipeline (ingress -> two models) deploys with ONE
    serve.run(app); nested Deployments arrive as live handles."""
    @serve.deployment(num_replicas=1)
    class Scaler:
        def __init__(self, scale):
            self.scale = scale

        def __call__(self, x):
            return x * self.scale

    @serve.deployment(num_replicas=1)
    class Ingress:
        def __init__(self, doubler, tripler):
            self.doubler = doubler
            self.tripler = tripler

        def __call__(self, x):
            a = ray_tpu.get(self.doubler.remote(x), timeout=60)
            b = ray_tpu.get(self.tripler.remote(x), timeout=60)
            return a + b

    app = Ingress.bind(Scaler.options(name="Doubler").bind(2),
                       Scaler.options(name="Tripler").bind(3))
    h = serve.run(app)
    assert ray_tpu.get(h.remote(7), timeout=120) == 7 * 2 + 7 * 3
    assert {"Ingress", "Doubler", "Tripler"} <= set(serve.status())


def test_declarative_yaml_apply(serve_session, tmp_path):
    """serve/schema.py: YAML-shaped config reconciliation (reference:
    serve deploy + serve/schema.py) — deploys listed deployments,
    reaps ones dropped from a later config."""
    import sys
    mod = tmp_path / "served_mod.py"
    mod.write_text(
        "class Doubler:\n"
        "    def __init__(self, scale=2):\n"
        "        self.scale = scale\n"
        "    def __call__(self, x):\n"
        "        return x * self.scale\n"
        "class Echo:\n"
        "    def __call__(self, x):\n"
        "        return x\n")
    sys.path.insert(0, str(tmp_path))
    try:
        from ray_tpu.serve.schema import serve_apply
        cfg = {"applications": [{"name": "app", "deployments": [
            {"name": "Doubler", "import_path": "served_mod:Doubler",
             "num_replicas": 1, "init_kwargs": {"scale": 5}},
            {"name": "Echo", "import_path": "served_mod:Echo"},
        ]}]}
        assert serve_apply(cfg) == ["Doubler", "Echo"]
        h = serve.get_deployment_handle("Doubler")
        assert ray_tpu.get(h.remote(3), timeout=60) == 15
        assert set(serve.status()) == {"Doubler", "Echo"}
        # Drop Echo from the config: reconciliation reaps it.
        cfg["applications"][0]["deployments"].pop()
        serve_apply(cfg)
        assert set(serve.status()) == {"Doubler"}
    finally:
        sys.path.remove(str(tmp_path))


def test_declarative_yaml_app_graph(serve_session, tmp_path):
    """Form A: app-level import_path resolving to a bound graph, with
    per-deployment option overrides (reference: ServeApplicationSchema
    import_path apps)."""
    import sys
    mod = tmp_path / "served_graph_mod.py"
    mod.write_text(
        "import ray_tpu\n"
        "from ray_tpu import serve\n"
        "@serve.deployment\n"
        "class M:\n"
        "    def __init__(self, k):\n"
        "        self.k = k\n"
        "    def __call__(self, x):\n"
        "        return x * self.k\n"
        "@serve.deployment\n"
        "class Gate:\n"
        "    def __init__(self, m):\n"
        "        self.m = m\n"
        "    def __call__(self, x):\n"
        "        return ray_tpu.get(self.m.remote(x), timeout=60) + 1\n"
        "app = Gate.bind(M.bind(10))\n")
    sys.path.insert(0, str(tmp_path))
    try:
        from ray_tpu.serve.schema import serve_apply
        cfg = {"applications": [
            {"import_path": "served_graph_mod:app",
             "deployments": [{"name": "M", "num_replicas": 2}]}]}
        assert serve_apply(cfg) == ["M", "Gate"]
        h = serve.get_deployment_handle("Gate")
        assert ray_tpu.get(h.remote(4), timeout=120) == 41
        assert serve.status()["M"]["target_replicas"] == 2
    finally:
        sys.path.remove(str(tmp_path))


def test_active_health_check_replaces_replica(serve_session):
    """Controller-driven health probing: a replica whose check_health
    turns false is killed and backfilled (reference:
    deployment_state.py active health checks)."""
    import time

    @serve.deployment(num_replicas=1, health_check_period_s=0.2,
                      health_check_timeout_s=5.0)
    class Flaky:
        def __init__(self):
            self.poisoned = False

        def poison(self):
            self.poisoned = True
            return "poisoned"

        def check_health(self):
            return not self.poisoned

        def who(self):
            return id(self)

    handle = serve.run(Flaky.bind(), name="flaky")
    first = ray_tpu.get(handle.who.remote())
    assert ray_tpu.get(handle.poison.remote()) == "poisoned"
    deadline = time.time() + 30
    while time.time() < deadline:
        try:
            cur = ray_tpu.get(handle.who.remote())
            if cur != first:
                break
        except Exception:
            pass          # mid-replacement window
        time.sleep(0.2)
    else:
        raise AssertionError("unhealthy replica never replaced")
    # The replacement is healthy and stays.
    assert ray_tpu.get(handle.who.remote()) != first


def test_user_config_reconfigure_without_restart(serve_session):
    """A user_config-only redeploy pushes reconfigure() to live
    replicas with NO restart; code changes still roll replicas
    (reference: user_config, serve/_private/replica.py)."""
    import time

    @serve.deployment(user_config={"threshold": 1})
    class Tunable:
        def __init__(self):
            self.threshold = None
            self.birth = time.time()

        def reconfigure(self, cfg):
            self.threshold = cfg["threshold"]

        def __call__(self, x):
            return {"over": x > self.threshold, "birth": self.birth}

    handle = serve.run(Tunable.bind(), name="tun")
    first = ray_tpu.get(handle.remote(5))
    assert first["over"] is True
    birth = first["birth"]

    # user_config-only update: SYNCHRONOUS — the config is live when
    # serve.run returns; same instance, new threshold.
    serve.run(Tunable.options(user_config={"threshold": 10}).bind(),
              name="tun")
    out = ray_tpu.get(handle.remote(5))
    assert out["over"] is False, out
    assert out["birth"] == birth      # replica was NOT restarted

    # A user_config on a class without reconfigure() fails at deploy
    # time, client-side, before anything lands.
    @serve.deployment(user_config={"x": 1})
    class NoReconf:
        def __call__(self, v):
            return v

    with __import__("pytest").raises(ValueError):
        serve.run(NoReconf.bind(), name="noreconf")


def test_router_failover_unstarted_requests(serve_session):
    """Requests assigned to a replica that dies before running them
    fail over (retry on another replica / after backfill) with zero
    user-visible errors — only the poison call itself (which STARTED)
    may surface an error."""
    from ray_tpu import exceptions as exc

    @serve.deployment(num_replicas=2)
    class S:
        def pid(self):
            return os.getpid()

        def boom(self):
            os._exit(1)

    h = serve.run(S)
    assert ray_tpu.get(h.method("pid").remote(), timeout=60) > 0
    # Kill one replica OUT FROM UNDER the router (no_restart): requests
    # routed to it before the refresh land on a dead actor.
    import ray_tpu as rt
    controller = rt.get_actor("SERVE_CONTROLLER")
    replicas = rt.get(controller.get_replicas.remote("S"),
                      timeout=30)["replicas"]
    rt.kill(replicas[0], no_restart=True)
    refs = [h.method("pid").remote() for _ in range(8)]
    pids = [ray_tpu.get(r, timeout=60) for r in refs]
    assert all(p > 0 for p in pids)


def test_router_circuit_breaker_sidelines_replica():
    """Unit: consecutive failures sideline a replica from pick() until
    a successful probe; an all-sidelined pool still serves."""
    import time as _time
    import types

    from ray_tpu.serve import _router

    r = _router.Router("unit")
    a = types.SimpleNamespace(_actor_id=b"a")
    b = types.SimpleNamespace(_actor_id=b"b")
    r._replicas = [a, b]
    r._last_refresh = _time.time()     # fresh: no controller round-trip
    r._last_probe = _time.time()       # suppress the probe thread
    for _ in range(_router._CB_THRESHOLD):
        r._record_failure(b"a")
    assert b"a" in r._sidelined
    picked = {r.pick()._actor_id for _ in range(20)}
    for _ in range(20):
        r.done(b)
    assert picked == {b"b"}
    # Successful probe resurrects it.
    r._record_success(b"a")
    assert b"a" not in r._sidelined
    # Whole pool sidelined -> fall back to serving everything.
    for _ in range(_router._CB_THRESHOLD):
        r._record_failure(b"a")
        r._record_failure(b"b")
    assert {r.pick()._actor_id for _ in range(20)} <= {b"a", b"b"}


def test_actor_unavailable_counts_as_transient():
    """The router's shared failure classifier: ActorUnavailableError
    from a restarting replica circuit-breaks locally but must NOT
    report the replica dead to the controller (no kill+backfill for a
    transient); true death errors do both."""
    import types

    from ray_tpu import exceptions as exc
    from ray_tpu.serve import _router

    r = _router.Router("unit2")
    calls = []
    r.report_failure = lambda replica: calls.append(replica._actor_id)
    rep = types.SimpleNamespace(_actor_id=b"x")

    r._note_replica_failure(rep, exc.ActorUnavailableError(
        "x", "restarting", task_started=True))
    assert calls == []                      # transient: no report
    assert r._failures.get(b"x") == 1       # but circuit-break counted

    r._note_replica_failure(rep, exc.ActorDiedError("x", "gone"))
    assert calls == [b"x"]                  # death: reported
    assert r._failures.get(b"x") == 2
