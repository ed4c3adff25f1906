"""Training telemetry & goodput plane (train/telemetry.py).

Covers the ISSUE-14 acceptance surface: per-step decomposition sums
to wall clock, ingest-vs-compute bound classification, a goodput
ledger that survives a checkpoint-restore + worker-kill restart and
charges the dead time to restart_recovery, straggler detection in a
CPU gang, monotonic report stamping across restarts, per-run gauge
lifecycle under the leak ledger, and the `/api/train` +
`ray_tpu train status` faces.
"""

import json
import os
import signal
import time
import urllib.request

import pytest

import ray_tpu
from ray_tpu.train import (Checkpoint, FailureConfig, RunConfig,
                           ScalingConfig, TpuTrainer)
from ray_tpu.train.telemetry import (LEDGER_CLASSES, PHASES,
                                     TrainTelemetry)
from ray_tpu.util import state as state_api


# ---------------------------------------------------------------------------
# offline sessions (no runtime)
# ---------------------------------------------------------------------------
def test_offline_decomposition_sums_to_wall():
    """Phase seconds + implicit idle must account for (nearly) all of
    the loop's wall clock."""
    tel = TrainTelemetry("tt_offline", client=None, publish=False,
                         tokens_per_step=128)
    t0 = time.perf_counter()
    for _ in range(5):
        with tel.data_wait():
            time.sleep(0.02)
        with tel.device_step():
            time.sleep(0.03)
        with tel.checkpoint():
            time.sleep(0.01)
        tel.end_step()
    wall = time.perf_counter() - t0
    tel.stop()
    s = tel.summary()
    assert s["step_index"] == 5
    ph = {p: s["phases"][p]["seconds"] for p in PHASES}
    assert ph["data_wait"] >= 0.5 * 5 * 0.02
    assert ph["step"] >= 0.5 * 5 * 0.03
    assert ph["checkpoint"] >= 0.5 * 5 * 0.01
    attributed = sum(ph.values())
    assert attributed <= wall * 1.05
    # Decomposition + idle covers >= 90% of wall (acceptance floor).
    assert s["coverage"] >= 0.9, s
    assert set(s["ledger"]) == set(LEDGER_CLASSES)
    # data_wait is 1/3 of attributed time -> input-bound verdict.
    assert s["bound"] == "input-bound"
    assert "data_wait" in s["verdict"]


def test_offline_compute_bound_and_rates():
    tel = TrainTelemetry("tt_offline2", client=None, publish=False,
                         tokens_per_step=1000, flops_per_token=2.0,
                         peak_flops=1e6)
    for _ in range(4):
        with tel.data_wait():
            time.sleep(0.002)
        with tel.device_step():
            time.sleep(0.05)
        tel.end_step()
    tel.stop()
    s = tel.summary()
    assert s["bound"] == "compute-bound"
    # ~1000 tokens / ~0.052s -> ~19k tokens/s; just sanity-band it.
    assert 5_000 < s["tokens_per_s"] < 500_000
    assert s["mfu"] == pytest.approx(
        s["tokens_per_s"] * 2.0 / 1e6, rel=1e-6)


def test_peak_flops_unknown_device_raises():
    """A utilization against a made-up peak is not a measurement."""
    from types import SimpleNamespace

    from ray_tpu.train.telemetry import peak_flops_for
    assert peak_flops_for(
        SimpleNamespace(device_kind="TPU v5 lite")) == 197e12
    assert peak_flops_for(SimpleNamespace(device_kind="TPU v5")) == 459e12
    with pytest.raises(ValueError, match="no published peak"):
        peak_flops_for(SimpleNamespace(device_kind="cpu"))


def test_compile_detected_via_jit_cache_miss():
    """A step whose jitted fn traced (cache grew) lands in `compile`,
    a cache-hit step lands in `step`."""
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: x * 2.0)
    tel = TrainTelemetry("tt_jit", client=None, publish=False,
                         jit_fns=[f])
    with tel.device_step():
        jax.block_until_ready(f(jnp.ones((4,))))
    first = tel.end_step()
    with tel.device_step():
        jax.block_until_ready(f(jnp.ones((4,))))
    second = tel.end_step()
    with tel.device_step():
        jax.block_until_ready(f(jnp.ones((8,))))   # new shape: retrace
    third = tel.end_step()
    tel.stop()
    assert "compile" in first["phases"] and \
        "step" not in first["phases"]
    assert "step" in second["phases"] and \
        "compile" not in second["phases"]
    assert "compile" in third["phases"]


def test_per_run_gauges_tracked_and_removed(monkeypatch):
    """Per-run gauge series register with the leak ledger on first
    set and discharge on stop() — the RT015 contract, observed live."""
    from ray_tpu.devtools import leaksan

    leaksan.enable_for_testing()
    try:
        run = f"tt_gauges_{os.getpid()}_{int(time.time() * 1000)}"
        tel = TrainTelemetry(run, client=None, publish=False,
                             tokens_per_step=10, flops_per_token=1.0,
                             peak_flops=1e9)
        with tel.device_step():
            time.sleep(0.005)
        tel.end_step()
        live = leaksan.live_counts().get("metric_series", 0)
        # mfu + tokens/s + 7 ledger-class fractions.
        assert live >= 9
        tel.stop()
        assert leaksan.live_counts().get("metric_series", 0) == 0
        report = leaksan.report()
        assert report["anomalies"] == []
    finally:
        leaksan.disable_for_testing()


def test_straggler_reducer_two_worker_gang():
    """Regression: with two workers the gang median must be the FAST
    worker's p95 (lower-middle), otherwise the slow worker is its own
    yardstick and can never be flagged."""
    from ray_tpu.train.telemetry import straggler_verdicts

    def snap(rank, step_s):
        return {"rank": rank,
                "window": [{"phases": {"step": step_s}}
                           for _ in range(10)]}

    verdicts = straggler_verdicts({0: snap(0, 0.02), 1: snap(1, 0.2)},
                                  multiple=1.5, min_steps=5)
    assert verdicts[1]["straggler"] is True, verdicts
    assert verdicts[0]["straggler"] is False
    # A balanced pair flags nobody.
    even = straggler_verdicts({0: snap(0, 0.02), 1: snap(1, 0.021)},
                              multiple=1.5, min_steps=5)
    assert not any(v["straggler"] for v in even.values())
    # One worker alone never self-flags.
    solo = straggler_verdicts({0: snap(0, 0.2)}, multiple=1.5,
                              min_steps=5)
    assert solo[0]["straggler"] is False


# ---------------------------------------------------------------------------
# cluster runs (TpuTrainer end to end)
# ---------------------------------------------------------------------------
def _telemetry_loop(data_s, step_s, steps):
    def loop(config=None):
        import time as _t
        from ray_tpu.train import session
        ctx = session.get_context()
        tel = ctx.telemetry(tokens_per_step=512)
        for i in range(steps):
            with tel.data_wait():
                _t.sleep(data_s)
            with tel.device_step():
                _t.sleep(step_s)
            tel.end_step()
            session.report({"step": i})
    return loop


def test_train_summary_bound_classification(ray_start, tmp_path,
                                            monkeypatch):
    """A slow-ingest run is classified input-bound; a compute-heavy
    run is not (the ROADMAP item-2 measurement)."""
    monkeypatch.setenv("RAY_TPU_TRAIN_TELEMETRY_PUBLISH_S", "0.2")
    for name, loop in [
            ("tt_ingest", _telemetry_loop(0.06, 0.02, 8)),
            ("tt_compute", _telemetry_loop(0.005, 0.06, 8))]:
        result = TpuTrainer(
            loop, scaling_config=ScalingConfig(num_workers=1),
            run_config=RunConfig(name=name,
                                 storage_path=str(tmp_path))).fit()
        assert result.error is None
    summary = state_api.train_summary()
    ingest = summary["runs"]["tt_ingest"]
    compute = summary["runs"]["tt_compute"]
    assert ingest["bound"] == "input-bound", ingest
    assert "data_wait" in ingest["verdict"]
    assert compute["bound"] == "compute-bound", compute
    assert ingest["coverage"] >= 0.9
    assert ingest["state"] == "finished"
    assert ingest["step_index"] == 8
    # Reports were stamped with monotonic step indexes + timestamps.
    # (result drained above; re-check on the compute run's history)
    one = state_api.train_summary(run="tt_ingest")
    assert one["bound"] == "input-bound"
    with pytest.raises(KeyError):
        state_api.train_summary(run="no_such_run")


def test_run_name_reuse_resets_state(ray_start, tmp_path,
                                     monkeypatch):
    """Regression: a SECOND fit() reusing a run name must start a
    fresh telemetry record — not restore the first fit's ledger and
    charge the whole between-fits gap to restart_recovery."""
    monkeypatch.setenv("RAY_TPU_TRAIN_TELEMETRY_PUBLISH_S", "0.1")
    loop = _telemetry_loop(0.01, 0.02, 4)
    result = None
    for i in range(2):
        result = TpuTrainer(
            loop, scaling_config=ScalingConfig(num_workers=1),
            run_config=RunConfig(
                name="tt_reuse",
                storage_path=str(tmp_path / str(i)))).fit()
        assert result.error is None
    s = state_api.train_summary(run="tt_reuse")
    assert s["restarts"] == 0, s
    assert s["ledger"]["restart_recovery"] == 0.0, s["ledger"]
    assert s["step_index"] == 4
    # The report _step stamp restarted in agreement.
    assert [m["_step"] for m in result.metrics_dataframe] == \
        [0, 1, 2, 3]


@pytest.fixture
def dash(ray_start):
    import ray_tpu.dashboard as dashboard
    httpd = dashboard.serve(port=0)
    host, port = httpd.server_address[:2]
    yield f"http://{host}:{port}"
    httpd.shutdown()


def test_goodput_ledger_survives_worker_kill(ray_start, tmp_path,
                                             dash, monkeypatch,
                                             capsys):
    """The flagship acceptance drill: an ingest-throttled run with an
    injected worker SIGKILL mid-run resumes from its checkpoint, the
    goodput ledger persists (dead time charged to restart_recovery),
    the decomposition covers >= 90% of wall, the run reads
    input-bound — and `ray_tpu train status --json` shows the same
    numbers."""
    monkeypatch.setenv("RAY_TPU_TRAIN_TELEMETRY_PUBLISH_S", "0.1")
    marker = str(tmp_path / "killed_once")

    def loop(config=None):
        import json as _json
        import time as _t
        from ray_tpu.train import session
        from ray_tpu.train.checkpoint import Checkpoint as _Ckpt
        ctx = session.get_context()
        tel = ctx.telemetry(tokens_per_step=256)
        start = 0
        ckpt = ctx.get_checkpoint()
        if ckpt is not None:
            with open(os.path.join(ckpt.path, "state.json")) as f:
                start = _json.load(f)["step"] + 1
        for step in range(start, 6):
            with tel.data_wait():
                _t.sleep(0.05)
            with tel.device_step():
                _t.sleep(0.01)
            with tel.checkpoint():
                ckpt_dir = os.path.join(ctx.get_trial_dir(),
                                        f"c{step}")
                os.makedirs(ckpt_dir, exist_ok=True)
                with open(os.path.join(ckpt_dir, "state.json"),
                          "w") as f:
                    _json.dump({"step": step}, f)
            tel.end_step()
            session.report({"step": step, "resumed": start > 0},
                           checkpoint=_Ckpt(ckpt_dir))
            if step == 2 and not os.path.exists(marker):
                open(marker, "w").close()
                _t.sleep(0.3)       # let the publisher push a snapshot
                os.kill(os.getpid(), signal.SIGKILL)
    result = TpuTrainer(
        loop,
        scaling_config=ScalingConfig(num_workers=1),
        run_config=RunConfig(
            name="tt_killed", storage_path=str(tmp_path),
            failure_config=FailureConfig(max_failures=2))).fit()
    assert result.error is None, result.error
    assert result.metrics["step"] == 5
    assert result.metrics["resumed"] is True

    summary = state_api.train_summary(run="tt_killed")
    # The injected kill is charged to restart_recovery.
    assert summary["restarts"] == 1
    assert summary["ledger"]["restart_recovery"] > 0.0, summary
    # Decomposition accounts for >= 90% of wall clock.
    assert summary["coverage"] >= 0.9, summary
    # Ingest-throttled: data_wait dominates -> input-bound.
    assert summary["bound"] == "input-bound", summary
    assert summary["ledger"]["input_wait"] > \
        summary["ledger"]["productive"]
    # Reports carry a monotonic step index that did NOT reset on the
    # resume-from-checkpoint restart.
    steps = [m["_step"] for m in result.metrics_dataframe]
    assert steps == sorted(steps)
    assert len(set(steps)) == len(steps)
    assert all("_ts" in m for m in result.metrics_dataframe)

    # Same numbers through the CLI (--json) and the raw endpoint.
    from ray_tpu.scripts import cli
    assert cli.main(["train", "status", "--dashboard-url", dash,
                     "--json"]) == 0
    payload = json.loads(capsys.readouterr().out)
    cli_run = payload["runs"]["tt_killed"]
    assert cli_run["ledger"]["restart_recovery"] == pytest.approx(
        summary["ledger"]["restart_recovery"])
    assert cli_run["bound"] == "input-bound"
    assert cli_run["step_index"] == summary["step_index"]
    assert cli.main(["train", "status", "--dashboard-url", dash]) == 0
    text = capsys.readouterr().out
    assert "verdict: input-bound" in text
    assert "restart_recovery" in text
    with urllib.request.urlopen(f"{dash}/api/train?run=tt_killed",
                                timeout=30) as r:
        api_run = json.loads(r.read())
    assert api_run["bound"] == "input-bound"


def test_straggler_flagged_in_cpu_gang(ray_start, tmp_path,
                                       monkeypatch):
    """One rank in a 3-worker gang runs slow steps; the reducer flags
    it against the gang median and the driver takes one targeted
    stack capture via the stall-sentinel dump path."""
    monkeypatch.setenv("RAY_TPU_TRAIN_TELEMETRY_PUBLISH_S", "0.15")
    monkeypatch.setenv("RAY_TPU_TRAIN_STRAGGLER_CHECK_S", "0.5")

    def loop(config=None):
        import time as _t
        from ray_tpu.train import session
        ctx = session.get_context()
        tel = ctx.telemetry(tokens_per_step=64)
        slow = ctx.get_world_rank() == 2
        for i in range(20):
            with tel.data_wait():
                _t.sleep(0.002)
            with tel.device_step():
                _t.sleep(0.15 if slow else 0.02)
            tel.end_step()
            session.report({"step": i})

    result = TpuTrainer(
        loop, scaling_config=ScalingConfig(num_workers=3),
        run_config=RunConfig(name="tt_gang",
                             storage_path=str(tmp_path))).fit()
    assert result.error is None
    summary = state_api.train_summary(run="tt_gang")
    verdicts = summary["stragglers"]
    assert verdicts["2"]["straggler"] is True, verdicts
    assert not verdicts.get("0", {}).get("straggler")
    assert not verdicts.get("1", {}).get("straggler")
    # One targeted capture fired for the flagged rank (the capture
    # runs on a driver-side daemon thread — poll briefly).
    deadline = time.time() + 15.0
    while time.time() < deadline and \
            "2" not in (summary.get("straggler_captures") or {}):
        time.sleep(0.25)
        summary = state_api.train_summary(run="tt_gang")
    assert "2" in (summary.get("straggler_captures") or {}), summary
    from ray_tpu.util import metrics
    counts = {(s["name"], (s.get("tags") or {}).get("run")):
              s["value"] for s in metrics.scrape()}
    assert counts.get(("ray_tpu_train_stragglers_total",
                       "tt_gang"), 0) >= 1
    # The capture also landed on the run's shared-trace timeline.
    events = ray_tpu._ensure_connected().timeline_events()
    names = [e.get("name") for e in events]
    assert any(n == "train.straggler[tt_gang]" for n in names), \
        [n for n in names if n and "train" in n]
    assert any(n == "train.step[tt_gang]" for n in names)


def test_cli_train_status_empty(ray_start, dash, capsys):
    from ray_tpu.scripts import cli
    assert cli.main(["train", "status",
                     "--dashboard-url", dash]) == 0
    assert "no train runs" in capsys.readouterr().out
