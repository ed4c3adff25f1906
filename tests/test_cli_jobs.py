"""CLI (`python -m ray_tpu ...`) + job submission end-to-end.

Reference analogs: scripts/scripts.py (ray start/stop/status),
dashboard job SDK (sdk.py), state CLI."""

import json
import os
import subprocess
import sys
import urllib.request

import pytest


REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def cli_env(tmp_path):
    env = dict(os.environ)
    env["HOME"] = str(tmp_path)          # isolate ~/.ray_tpu_cli.json
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    yield env
    subprocess.run([sys.executable, "-m", "ray_tpu", "stop"],
                   env=env, capture_output=True, timeout=60)


def _cli(env, *args, timeout=120):
    return subprocess.run([sys.executable, "-m", "ray_tpu", *args],
                          env=env, capture_output=True, text=True,
                          timeout=timeout)


def test_cli_cluster_lifecycle(cli_env):
    r = _cli(cli_env, "start", "--head", "--num-cpus", "2",
             "--dashboard-port", "0")
    assert r.returncode == 0, r.stderr
    assert "head started" in r.stdout

    state = json.loads(open(os.path.join(cli_env["HOME"],
                                         ".ray_tpu_cli.json")).read())
    assert state["gcs_address"] and state["dashboard_url"]

    r = _cli(cli_env, "status")
    assert r.returncode == 0, r.stderr
    assert "1 node(s)" in r.stdout
    assert "CPU" in r.stdout

    # dashboard endpoints serve
    with urllib.request.urlopen(state["dashboard_url"] + "/api/summary",
                                timeout=10) as resp:
        summary = json.loads(resp.read())
    assert len(summary["nodes"]) == 1
    with urllib.request.urlopen(state["dashboard_url"] + "/metrics",
                                timeout=10) as resp:
        assert b"ray_tpu_workers" in resp.read()
    with urllib.request.urlopen(state["dashboard_url"] + "/graphs",
                                timeout=10) as resp:
        assert b"canvas" in resp.read()
    with urllib.request.urlopen(
            state["dashboard_url"] + "/api/metrics.json",
            timeout=10) as resp:
        series = json.loads(resp.read())
    assert any(s["name"].startswith("ray_tpu") for s in series)

    # join a second node, then status shows 2
    r = _cli(cli_env, "start", "--resources", '{"extra": 1}')
    assert r.returncode == 0, r.stderr
    r = _cli(cli_env, "status")
    assert "2 node(s)" in r.stdout
    assert "extra" in r.stdout

    # jobs: success path — the entrypoint joins the cluster itself
    script = ("import ray_tpu; ray_tpu.init();"
              "print('resources', ray_tpu.cluster_resources());"
              "print('job-ran-ok')")
    r = _cli(cli_env, "job", "submit", "--wait", "--",
             sys.executable, "-c", script, timeout=180)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "job-ran-ok" in r.stdout
    assert "SUCCEEDED" in r.stdout

    # jobs: failure path
    r = _cli(cli_env, "job", "submit", "--wait", "--",
             sys.executable, "-c", "import sys; sys.exit(3)",
             timeout=180)
    assert r.returncode == 1
    assert "FAILED" in r.stdout

    r = _cli(cli_env, "job", "list")
    assert r.stdout.count("job-") >= 2

    # state CLI over the dashboard
    r = _cli(cli_env, "list", "actors")
    assert r.returncode == 0, r.stderr
    assert "_JobSupervisor" in r.stdout

    r = _cli(cli_env, "memory")
    assert "cluster objects:" in r.stdout
    assert "by node:" in r.stdout

    r = _cli(cli_env, "memory", "--group-by", "owner",
             "--leak-suspects")
    assert "by owner:" in r.stdout
    assert "leak suspects" in r.stdout

    r = _cli(cli_env, "stop")
    assert "stopped" in r.stdout
