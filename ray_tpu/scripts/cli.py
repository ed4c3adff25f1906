"""`python -m ray_tpu <command>`: the cluster CLI.

Reference surface: python/ray/scripts/scripts.py (`ray start/stop/
status`) + `ray list/summary` (util/state CLI) + `ray job` (job CLI).

    start --head [...]        start GCS + head node + dashboard, detached
    start --address H:P       join an existing cluster as a worker node
    stop                      stop every process this CLI started
    drain <node_id> [--grace S]
                              gracefully drain a node (planned departure)
    status [--address H:P]    cluster nodes + resources
    list {tasks,actors,workers,objects,nodes,pgs}
    summary                   task/actor/object rollups
    memory [--group-by node|owner] [--leak-suspects]
                              cluster memory accounting: object bytes
                              by reference kind/owner/node vs real shm
                              store usage, plus leak suspects
    stack [task_id] [--flame] cluster-wide worker stack dumps; target
                              one task, or sample into a flamegraph
    device-time <trace>       a jax.profiler trace's device time by
                              program and, inside one, by named scope
    metrics                   Prometheus text from the head
    job {submit,status,logs,list,stop}
    microbench                core-runtime perf harness
    lint <path>...            static analysis (RT001-RT020) for
                              remote/actor/sharding/concurrency/
                              lifecycle/XLA code (--lock-graph dumps
                              the lock-order graph; --changed lints
                              only git-modified files)
    locksan                   merged runtime lock-sanitizer report
                              from a RAY_TPU_LOCKSAN=1 run
    leaksan                   merged resource-leak ledger from a
                              RAY_TPU_LEAKSAN=1 run (exit 1 on leaks)
    xlasan                    merged XLA recompile/host-sync ledger
                              from a RAY_TPU_XLASAN=1 run (exit 1 on
                              recompile storms over budget)
    doctor                    cluster health triage: GCS liveness/WAL,
                              stalls, slow RPCs, leak suspects,
                              event-ring drops, serve shedding, train
                              goodput — prioritized findings with
                              stable codes; exit 1 on errors
    top [--interval S]        live terminal view over the metrics
                              history rings (runtime gauges + busiest
                              RPC handlers, sparklines)
    bench-diff NEW BASE       direction-aware bench-capture regression
                              gate (exit 1 when a throughput metric
                              drops / latency metric rises beyond
                              --tolerance)

State (started pids, head address) persists in ~/.ray_tpu_cli.json so
`stop`/`status` work from a fresh shell."""

from __future__ import annotations

import argparse
import json
import os
import signal
import subprocess
import sys
import time
import urllib.error
import urllib.request
from typing import Any, Dict, List, Optional

STATE_PATH = os.path.expanduser("~/.ray_tpu_cli.json")


# ---------------------------------------------------------------------------
# CLI state file
# ---------------------------------------------------------------------------
def _load_state() -> dict:
    try:
        with open(STATE_PATH) as f:
            return json.load(f)
    except (OSError, ValueError):
        return {"procs": []}


def _save_state(st: dict) -> None:
    with open(STATE_PATH, "w") as f:
        json.dump(st, f, indent=1)


def _daemon_log(role: str) -> str:
    d = os.path.expanduser("~/.ray_tpu_logs")
    os.makedirs(d, exist_ok=True)
    return os.path.join(d, f"{role}-{int(time.time())}.err")


def _parse_addr(addr: str) -> tuple:
    host, _, port = addr.rpartition(":")
    return (host or "127.0.0.1", int(port))


def _head_address(args) -> Optional[str]:
    if getattr(args, "address", None):
        return args.address
    st = _load_state()
    return st.get("gcs_address")


# ---------------------------------------------------------------------------
# start / stop / status
# ---------------------------------------------------------------------------
def cmd_start(args) -> int:
    st = _load_state()
    env = dict(os.environ)
    if args.head:
        cmd = [sys.executable, "-m", "ray_tpu.scripts.head",
               "--host", args.host, "--port", str(args.port),
               "--dashboard-port", str(args.dashboard_port),
               "--resources", args.resources]
        if args.num_cpus is not None:
            cmd += ["--num-cpus", str(args.num_cpus)]
        if args.num_tpus is not None:
            cmd += ["--num-tpus", str(args.num_tpus)]
        if args.object_store_memory:
            cmd += ["--object-store-memory",
                    str(args.object_store_memory)]
        if args.persist_dir:
            cmd += ["--persist-dir", args.persist_dir]
        err_f = open(_daemon_log("head"), "ab")
        try:
            # stderr to a log file, NOT inherited: a detached daemon
            # holding the caller's pipe would hang any capture of this
            # CLI's own output.
            proc = subprocess.Popen(cmd, env=env, stdout=subprocess.PIPE,
                                    stderr=err_f, text=True,
                                    start_new_session=True)
        finally:
            err_f.close()
        info = _await_line(proc, "HEAD_READY=", args.timeout)
        head = json.loads(info)
        st["gcs_address"] = head["gcs_address"]
        st["dashboard_url"] = head["dashboard_url"]
        st["client_address"] = head.get("client_address")
        st["procs"].append({"pid": proc.pid, "role": "head"})
        _save_state(st)
        print(f"head started: gcs={head['gcs_address']} "
              f"client={head.get('client_address')} "
              f"dashboard={head['dashboard_url']} pid={proc.pid}")
        print(f"join with: python -m ray_tpu start "
              f"--address {head['gcs_address']}")
        return 0
    addr = args.address or st.get("gcs_address")
    if not addr:
        print("error: --address required (no head on record)",
              file=sys.stderr)
        return 1
    host, port = _parse_addr(addr)
    cmd = [sys.executable, "-m", "ray_tpu._private.node_service",
           "--gcs-host", host, "--gcs-port", str(port),
           "--resources", args.resources]
    if args.object_store_memory:
        cmd += ["--store-capacity", str(args.object_store_memory)]
    err_f = open(_daemon_log("node"), "ab")
    try:
        proc = subprocess.Popen(cmd, env=env, stdout=subprocess.PIPE,
                                stderr=err_f, text=True,
                                start_new_session=True)
    finally:
        err_f.close()
    node_id = _await_line(proc, "NODE_READY=", args.timeout)
    st["procs"].append({"pid": proc.pid, "role": "node"})
    _save_state(st)
    print(f"node {node_id[:12]} joined {addr} (pid={proc.pid})")
    return 0


def _await_line(proc, prefix: str, timeout_s: float) -> str:
    deadline = time.time() + timeout_s
    while time.time() < deadline:
        line = proc.stdout.readline()
        if not line:
            raise RuntimeError(
                f"process exited early (rc={proc.poll()})")
        if line.startswith(prefix):
            # Leave the pipe to the OS; the daemon keeps running.
            import threading

            def drain(p=proc.stdout):
                try:
                    for _ in p:
                        pass
                except (OSError, ValueError):
                    pass
            threading.Thread(target=drain, daemon=True).start()
            return line.strip()[len(prefix):]
    proc.kill()
    raise TimeoutError(f"no {prefix} within {timeout_s}s")


def cmd_stop(args) -> int:
    st = _load_state()
    stopped = 0
    for rec in st.get("procs", []):
        try:
            os.killpg(os.getpgid(rec["pid"]), signal.SIGTERM)
            stopped += 1
        except (ProcessLookupError, PermissionError):
            pass
    _save_state({"procs": []})
    print(f"stopped {stopped} process group(s)")
    return 0


def cmd_status(args) -> int:
    addr = _head_address(args)
    if not addr:
        print("no cluster on record (start one with: "
              "python -m ray_tpu start --head)", file=sys.stderr)
        return 1
    from ray_tpu._private.gcs_service import GcsClient
    host, port = _parse_addr(addr)
    gcs = GcsClient(host, port)
    try:
        nodes = gcs.nodes()
    finally:
        gcs.close()
    print(f"cluster at {addr}: {len(nodes)} node(s)")
    total: Dict[str, float] = {}
    avail: Dict[str, float] = {}
    for n in nodes:
        for k, v in n["resources_total"].items():
            total[k] = total.get(k, 0.0) + v
        for k, v in n["resources_avail"].items():
            avail[k] = avail.get(k, 0.0) + v
        print(f"  node {n['node_id'].hex()[:12]} {n['host']} "
              f"state={n.get('state', 'alive')}")
    for k in sorted(total):
        print(f"  {avail.get(k, 0.0):g}/{total[k]:g} {k}")
    return 0


# ---------------------------------------------------------------------------
# state queries (served by the head's dashboard HTTP endpoints)
# ---------------------------------------------------------------------------
def _dashboard_url(args) -> str:
    st = _load_state()
    url = getattr(args, "dashboard_url", None) or st.get("dashboard_url")
    if not url:
        raise SystemExit("no dashboard on record; pass --dashboard-url")
    if "://" not in url:
        url = f"http://{url}"
    return url


def _fetch_json(path: str, args) -> Any:
    url = _dashboard_url(args)
    with urllib.request.urlopen(f"{url}{path}", timeout=30) as r:
        return json.loads(r.read())


def _print_table(rows: List[dict], cols: List[str]) -> None:
    if not rows:
        print("(empty)")
        return
    widths = {c: max(len(c), *(len(str(r.get(c, ""))) for r in rows))
              for c in cols}
    print("  ".join(c.ljust(widths[c]) for c in cols))
    for r in rows:
        print("  ".join(str(r.get(c, "")).ljust(widths[c])
                        for c in cols))


def cmd_list(args) -> int:
    dump = _fetch_json("/api/state", args)
    kind = args.kind
    key = {"tasks": "tasks", "actors": "actors", "workers": "workers",
           "objects": "objects", "pgs": "placement_groups",
           "nodes": "nodes"}[kind]
    rows = dump.get(key) or []
    cols = {
        "tasks": ["task_id", "name", "state", "pid", "retries_left"],
        "actors": ["actor_id", "class_name", "name", "state", "pid"],
        "workers": ["worker_id", "pid", "state", "tpu", "task"],
        "objects": ["object_id", "state", "loc", "size", "refcount"],
        "pgs": ["pg_id", "name", "strategy", "state"],
        "nodes": ["node_id", "host", "state"],
    }[kind]
    for r in rows:
        for c in cols:
            if isinstance(r.get(c), bytes):
                r[c] = r[c].hex()
        for c in ("task_id", "actor_id", "worker_id", "object_id",
                  "pg_id", "node_id"):
            if isinstance(r.get(c), str) and len(r[c]) > 16:
                r[c] = r[c][:16]
    _print_table(rows, cols)
    return 0


def cmd_summary(args) -> int:
    print(json.dumps(_fetch_json("/api/summary", args), indent=1,
                     default=str))
    return 0


def _fmt_bytes(n: float) -> str:
    n = float(n or 0)
    for unit in ("B", "KiB", "MiB", "GiB", "TiB"):
        if n < 1024 or unit == "TiB":
            return (f"{n:.0f}{unit}" if unit == "B"
                    else f"{n:.1f}{unit}")
        n /= 1024
    return f"{n:.1f}TiB"


def cmd_memory(args) -> int:
    """Cluster memory accounting (reference: `ray memory`): per-node
    object-store breakdown by reference kind (owned / borrowed /
    pinned_by_actor / spilled / drain_replica) and by owner, next to
    each node's real shm store usage; --leak-suspects flags old
    objects whose owner client is dead or whose borrow count is
    zero."""
    summary = _fetch_json(
        f"/api/memory?min_age_s={args.min_age_s:g}", args)
    print(f"cluster objects: {summary.get('object_count', 0)} ready, "
          f"{_fmt_bytes(summary.get('total_bytes', 0))}")
    for kind, cell in sorted((summary.get("by_kind") or {}).items()):
        print(f"  {kind}: {cell['count']} objects, "
              f"{_fmt_bytes(cell['bytes'])}")
    kv = summary.get("kv_blocks") or {}
    if kv:
        parts = " ".join(f"{s}={int(kv.get(s, 0))}"
                         for s in ("used", "cached", "free"))
        print(f"paged-KV blocks (serve LLM engines): {parts}")
    group = getattr(args, "group_by", "node")
    if group == "owner":
        rows = [{"owner": (o[:16] if isinstance(o, str) else o),
                 "objects": c["count"],
                 "bytes": _fmt_bytes(c["bytes"])}
                for o, c in sorted((summary.get("by_owner") or {})
                                   .items(),
                                   key=lambda kv: -kv[1]["bytes"])]
        print("\nby owner:")
        _print_table(rows, ["owner", "objects", "bytes"])
    else:
        rows = []
        for nid, c in sorted((summary.get("by_node") or {}).items()):
            rows.append({
                "node": nid[:12],
                "objects": c.get("count", 0),
                "bytes": _fmt_bytes(c.get("bytes", 0)),
                "store_used": _fmt_bytes(c.get("store_used_bytes", 0)),
                "store_capacity": _fmt_bytes(
                    c.get("store_capacity_bytes", 0)),
            })
        print("\nby node:")
        _print_table(rows, ["node", "objects", "bytes", "store_used",
                            "store_capacity"])
    if getattr(args, "leak_suspects", False):
        suspects = summary.get("leak_suspects") or []
        print(f"\nleak suspects ({len(suspects)}):")
        rows = [{"object_id": s.get("object_id", "")[:16],
                 "node": (s.get("node_id") or "")[:12],
                 "kind": s.get("reference_kind"),
                 "bytes": _fmt_bytes(s.get("size_bytes", 0)),
                 "age_s": s.get("age_s"),
                 "reason": s.get("leak_reason")}
                for s in suspects]
        _print_table(rows, ["object_id", "node", "kind", "bytes",
                            "age_s", "reason"])
    unreachable = summary.get("unreachable_nodes") or []
    if unreachable:
        print(f"\nWARNING: partial snapshot — unreachable nodes: "
              f"{', '.join(n[:12] for n in unreachable)}")
    return 0


def cmd_timeline(args) -> int:
    """Chrome-trace export of the runtime timeline (open the file in
    chrome://tracing or Perfetto; reference: `ray timeline`)."""
    events = _fetch_json("/api/timeline", args)
    if args.out:
        with open(args.out, "w") as f:
            json.dump(events, f)
        print(f"wrote {len(events)} events to {args.out}")
    else:
        print(json.dumps(events, indent=1, default=str))
    return 0


def cmd_device_time(args) -> int:
    """Where a traced window's device time went (`jax.profiler` left an
    `.xplane.pb` under <dir>/plugins/profile/<time>/): a line a program,
    under it its scopes (profiling.device_time; --json for the whole
    result)."""
    import glob

    from ray_tpu.util import profiling
    path = args.trace
    if os.path.isdir(path):
        found = sorted(glob.glob(os.path.join(
            path, "**", "*.xplane.pb"), recursive=True))
        if not found:
            raise SystemExit(f"no .xplane.pb under {path}")
        path = found[-1]
    result = profiling.device_time(path)
    print(json.dumps(result) if args.json
          else profiling.format_device_time(result, args.scopes))
    return 0


def cmd_metrics(args) -> int:
    url = _dashboard_url(args)
    with urllib.request.urlopen(f"{url}/metrics", timeout=10) as r:
        sys.stdout.write(r.read().decode())
    return 0


# ---------------------------------------------------------------------------
# jobs
# ---------------------------------------------------------------------------
def _job_client(args):
    from ray_tpu.util.job import JobSubmissionClient
    addr = _head_address(args)
    if not addr:
        raise SystemExit("no cluster on record")
    return JobSubmissionClient(addr)


def cmd_stack(args) -> int:
    """On-demand stack dump of every live worker in the cluster
    (reference: `ray stack` / the dashboard's py-spy role), served by
    the head's dashboard.  With a task_id hex prefix, dumps only the
    worker(s) executing that task; --flame switches to low-rate stack
    sampling merged into flamegraph.pl folded format."""
    if args.flame:
        url = _dashboard_url(args) + (
            f"/api/flamegraph?samples={args.samples}"
            f"&interval_s={args.interval:g}")
        if args.task_id:
            url += f"&task_id={args.task_id}"
        # The server blocks for the whole sampling window — scale the
        # HTTP timeout with it instead of racing a fixed constant.
        http_timeout = args.samples * args.interval + 60.0
        with urllib.request.urlopen(url, timeout=http_timeout) as r:
            folded = r.read().decode()
        if args.out:
            with open(args.out, "w") as f:
                f.write(folded + ("\n" if folded else ""))
            print(f"wrote folded stacks to {args.out} "
                  f"(render with flamegraph.pl or speedscope)")
        else:
            print(folded if folded else "(no samples collected)")
        return 0
    path = f"/api/stack?timeout={args.timeout:g}"
    if args.task_id:
        path += f"&task_id={args.task_id}"
    # Dashboard + node fanout wait up to args.timeout (+5s margin
    # each) before replying — outlast them.
    url = _dashboard_url(args)
    with urllib.request.urlopen(f"{url}{path}",
                                timeout=args.timeout + 30.0) as r:
        stacks = (json.loads(r.read()) or {}).get("stacks") or {}
    if not stacks:
        print("no matching live workers" if args.task_id
              else "no live workers")
        return 1 if args.task_id else 0
    for pid, text in sorted(stacks.items(), key=lambda kv: str(kv[0])):
        print(f"===== worker {pid} =====")
        print(text)
    return 0


def _serve_shed_counters() -> dict:
    """deployment -> {reason: count} from the merged metric plane."""
    out: dict = {}
    try:
        from ray_tpu.util import metrics
        for s in metrics.scrape():
            if s.get("name") != metrics.SERVE_REQUESTS_SHED_METRIC:
                continue
            tags = s.get("tags") or {}
            dep = tags.get("deployment", "?")
            out.setdefault(dep, {})[tags.get("reason", "?")] = \
                int(s.get("value") or 0)
    except Exception:
        pass
    return out


def _render_serve_status(data: dict, shed: dict) -> str:
    """Text face of `ray_tpu serve status` (pure: unit-testable).
    `data` is the controller's overload_status(); `shed` maps
    deployment -> {reason: count} from the metric plane."""
    lines = []
    for name, s in sorted(data.items()):
        lines.append(
            f"{name}: {s.get('running', 0)} running"
            f" / {s.get('draining', 0)} draining"
            f" (target {s.get('target_replicas', '?')},"
            f" v{s.get('version', '?')})")
        qd = s.get("queue_depth")
        ttft = s.get("ttft_p95_ms")
        itl = s.get("itl_p95_ms")
        lines.append(
            "  queue_depth "
            + (f"{qd:g}" if qd is not None else "n/a")
            + "  ttft_p95 "
            + (f"{ttft:.1f}ms" if ttft is not None else "n/a")
            + "  itl_p95 "
            + (f"{itl:.2f}ms" if itl is not None else "n/a"))
        counts = shed.get(name) or {}
        if counts:
            lines.append("  shed: " + ", ".join(
                f"{r}={n}" for r, n in sorted(counts.items())))
        adm = s.get("admission")
        if adm:
            lines.append("  admission: " + ", ".join(
                f"{k}={v}" for k, v in sorted(adm.items())))
        last = s.get("autoscale_last")
        if last:
            lines.append(
                f"  autoscale: {last.get('action')} "
                f"{last.get('current')} -> {last.get('desired')} "
                f"({last.get('reason')})")
        for ev in s.get("autoscale_events") or []:
            lines.append(
                f"    event: {ev.get('action')} {ev.get('current')}"
                f" -> {ev.get('desired')} ({ev.get('reason')})")
    return "\n".join(lines) if lines else "(no deployments)"


def _render_train_status(data: dict) -> str:
    """Text face of `ray_tpu train status` (pure: unit-testable).
    `data` is state.train_summary()'s {"runs": {...}} payload."""
    runs = data.get("runs") or {}
    if not runs:
        return "(no train runs recorded)"
    lines = []
    for name, r in sorted(runs.items()):
        lines.append(
            f"run {name} [{r.get('state', '?')}]: "
            f"step {r.get('step_index', 0)}, "
            f"{r.get('workers_reporting', 0)}"
            f"/{r.get('world_size', '?')} workers, "
            f"wall {float(r.get('wall_s') or 0):.1f}s, "
            f"restarts {r.get('restarts', 0)}"
            + (f", resizes {r.get('resize_count')}"
               if r.get("resize_count") else ""))
        lines.append(f"  verdict: {r.get('verdict', 'n/a')}")
        # Elastic resize history (train/elastic.py): direction,
        # world-size transition, the checkpoint step resharded from,
        # and the dead time the resize charged to resize_recovery.
        for ev in (r.get("resizes") or [])[-6:]:
            lines.append(
                f"  resize {ev.get('direction', '?')}: "
                f"{ev.get('from', '?')} -> {ev.get('to', '?')} workers"
                f" @ ckpt step {ev.get('step', '?')}"
                f" (+{float(ev.get('dead_s') or 0):.2f}s dead)")
        cr = r.get("ckpt_reads") or {}
        if any(int(v or 0) for v in cr.values()):
            lines.append(
                f"  ckpt restores: memory={int(cr.get('memory') or 0)}"
                f" disk={int(cr.get('disk') or 0)}")
        tok = float(r.get("tokens_per_s") or 0.0)
        mfu = r.get("mfu")
        line = f"  tokens/s {tok:,.0f}"
        if mfu is not None:
            line += f"  MFU {float(mfu):.3f}"
        sm = r.get("step_ms") or {}
        line += (f"  step p50 {float(sm.get('p50') or 0):.1f}ms"
                 f" p95 {float(sm.get('p95') or 0):.1f}ms")
        lines.append(line)
        phases = r.get("phases") or {}
        if phases:
            lines.append("  phases: " + "  ".join(
                f"{p}={c.get('seconds', 0):.2f}s"
                f"({float(c.get('fraction') or 0) * 100:.0f}%)"
                for p, c in phases.items()
                if float(c.get("seconds") or 0) > 0))
        ledger = r.get("ledger") or {}
        lines.append(
            "  goodput ledger: " + "  ".join(
                f"{c}={v:.2f}s" for c, v in ledger.items()
                if float(v or 0) > 0)
            + f"  (coverage {float(r.get('coverage') or 0) * 100:.0f}%"
              f", goodput "
              f"{float(r.get('goodput_fraction') or 0) * 100:.0f}%)")
        flagged = {rk: v for rk, v in
                   (r.get("stragglers") or {}).items()
                   if v.get("straggler")}
        for rk, v in sorted(flagged.items(),
                            key=lambda kv: int(kv[0])
                            if str(kv[0]).isdigit() else 0):
            p95 = float(v.get("p95_s") or 0.0)
            med = float(v.get("median_s") or 0.0)
            lines.append(
                f"  STRAGGLER rank {rk}: step p95 "
                f"{p95 * 1000:.1f}ms vs gang median "
                f"{med * 1000:.1f}ms"
                + (" (stack captured)"
                   if rk in (r.get("straggler_captures") or {})
                   else ""))
    return "\n".join(lines)


def cmd_train(args) -> int:
    """Training telemetry status (train/telemetry.py): per-run step
    decomposition, live MFU + tokens/s, goodput ledger, and
    straggler verdicts, served by the head's dashboard."""
    path = "/api/train"
    if getattr(args, "run", None):
        from urllib.parse import quote
        path += f"?run={quote(args.run, safe='')}"
    try:
        data = _fetch_json(path, args)
    except urllib.error.HTTPError as e:
        # An unknown --run surfaces as the dashboard's 500 payload;
        # show the server's error (it names the known runs) instead
        # of a urllib traceback.
        try:
            detail = json.loads(e.read()).get("error", str(e))
        except Exception:
            detail = str(e)
        print(f"error: {detail}", file=sys.stderr)
        return 1
    if getattr(args, "run", None):
        data = {"runs": {args.run: data}}
    if getattr(args, "json", False):
        print(json.dumps(data, indent=1, default=str))
    else:
        print(_render_train_status(data))
    return 0


def cmd_serve(args) -> int:
    """Declarative serve apply/status/shutdown (reference: `serve
    deploy` over the REST config, serve/schema.py)."""
    from ray_tpu.util import client as thin
    addr = getattr(args, "address", None) or _head_address(args)
    if not addr:
        raise SystemExit("no cluster on record; pass --address H:P")
    ctx = thin.connect(addr)
    try:
        from ray_tpu import serve
        if args.serve_cmd == "deploy":
            from ray_tpu.serve.schema import serve_apply
            names = serve_apply(args.config)
            print(json.dumps({"deployed": names}))
        elif args.serve_cmd == "status":
            import ray_tpu
            from ray_tpu.serve._controller import CONTROLLER_NAME
            try:
                controller = ray_tpu.get_actor(CONTROLLER_NAME)
                data = ray_tpu.get(
                    controller.overload_status.remote(), timeout=60)
            except ValueError:
                data = {}       # serve never started on this cluster
            shed = _serve_shed_counters()
            if getattr(args, "json", False):
                print(json.dumps({"deployments": data, "shed": shed},
                                 indent=1, default=str))
            else:
                print(_render_serve_status(data, shed))
        elif args.serve_cmd == "shutdown":
            serve.shutdown()
            print("serve shut down")
    finally:
        ctx.disconnect()
    return 0


def cmd_job(args) -> int:
    jc = _job_client(args)
    try:
        if args.job_cmd == "submit":
            import shlex
            argv = args.entrypoint
            if argv and argv[0] == "--":
                argv = argv[1:]
            entrypoint = shlex.join(argv)
            job_id = jc.submit_job(
                entrypoint=entrypoint,
                runtime_env=({"working_dir": args.working_dir}
                             if args.working_dir else None))
            print(f"submitted {job_id}")
            if args.wait:
                status = jc.wait(job_id)
                print(f"{job_id}: {status}")
                sys.stdout.write(jc.get_job_logs(job_id))
                return 0 if status == "SUCCEEDED" else 1
        elif args.job_cmd == "status":
            print(jc.get_job_status(args.job_id))
        elif args.job_cmd == "logs":
            sys.stdout.write(jc.get_job_logs(args.job_id))
        elif args.job_cmd == "list":
            _print_table(jc.list_jobs(),
                         ["job_id", "status", "entrypoint"])
        elif args.job_cmd == "stop":
            jc.stop_job(args.job_id)
            print(f"stopped {args.job_id}")
        return 0
    finally:
        jc.close()


def cmd_microbench(args) -> int:
    from ray_tpu.util.microbench import run_all
    run_all()
    return 0


def cmd_lint(args) -> int:
    from ray_tpu.devtools.lint import cli as lint_cli
    return lint_cli.run(args)


def cmd_locksan(args) -> int:
    """Merged runtime lock-sanitizer report (devtools/locksan.py).
    Run the workload with RAY_TPU_LOCKSAN=1 first; every process
    drops a <pid>.json report into the locksan dir.  Exit 1 when any
    lock-order inversion was witnessed, 0 on a clean run."""
    from ray_tpu.devtools import locksan
    rep = locksan.merged_report(args.dir)
    if args.json:
        print(json.dumps(rep, indent=1, default=str))
        return 1 if rep["inversions"] else 0
    print(f"locksan report ({rep['processes']} process(es), "
          f"{rep['acquires']} tracked acquires, dir "
          f"{args.dir or locksan.report_dir()})")
    if not rep["processes"]:
        print("no reports found — run the workload with "
              "RAY_TPU_LOCKSAN=1")
        return 0
    inv = rep["inversions"]
    print(f"\nlock-order inversions: {len(inv)}")
    for i in inv:
        print(f"  {i.get('order_here')}  (reverse order seen "
              f"earlier; thread {i.get('thread')}, pid "
              f"{i.get('pid')})")
        for ln in (i.get("stack_here") or [])[-4:]:
            print(f"    {ln}")
    holds = rep["long_holds"]
    print(f"\nlong holds (> lock_hold_warn_ms): {len(holds)}")
    for h in holds[:10]:
        print(f"  {h.get('held_s'):>8}s  {h.get('site')}  "
              f"(thread {h.get('thread')}, pid {h.get('pid')})")
    same = rep.get("same_site_nesting") or {}
    if same:
        print(f"\nsame-site lock nesting (direction not checkable "
              f"by site — verify instance ordering): {len(same)}")
        for site, cell in sorted(same.items(),
                                 key=lambda kv: -kv[1]["count"]):
            print(f"  x{cell['count']}  {site}")
    cont = sorted(rep["contention"].items(), key=lambda kv: -kv[1])
    print(f"\nmost contended lock sites:")
    for site, n in cont[:10]:
        print(f"  {n:>6}  {site}")
    if not cont:
        print("  (no contention observed)")
    return 1 if inv else 0


def cmd_leaksan(args) -> int:
    """Merged resource-leak ledger (devtools/leaksan.py).  Run the
    workload with RAY_TPU_LEAKSAN=1 first; every process drops a
    <pid>.json ledger into the leaksan dir at exit.  Anything still
    live in a ledger at dump time was never released — exit 1 on any
    leak or exactly-once anomaly, 0 on a clean run."""
    from ray_tpu.devtools import leaksan
    rep = leaksan.merged_report(args.dir)
    bad = bool(rep["leaks"] or rep["anomalies"])
    if args.json:
        print(json.dumps(rep, indent=1, default=str))
        return 1 if bad else 0
    print(f"leaksan report ({rep['processes']} process(es), "
          f"{rep['registrations']} tracked registrations, dir "
          f"{args.dir or leaksan.report_dir()})")
    if not rep["processes"]:
        print("no ledgers found — run the workload with "
              "RAY_TPU_LEAKSAN=1")
        return 0
    print("\nper-kind registered/discharged:")
    for kind in sorted(rep["registered"]):
        reg = rep["registered"][kind]
        dis = rep["discharged"].get(kind, 0)
        leaked = rep["leak_counts"].get(kind, 0)
        mark = f"  LEAKED {leaked}" if leaked else ""
        print(f"  {kind:<16} {reg:>8} / {dis:<8}{mark}")
    print(f"\nleaked resources: {len(rep['leaks'])}")
    for row in rep["leaks"][:20]:
        print(f"  [{row.get('kind')}] key={row.get('key')} "
              f"age={row.get('age_s')}s pid={row.get('pid')}")
        print(f"      born at {row.get('site')}")
    if len(rep["leaks"]) > 20:
        print(f"  ... and {len(rep['leaks']) - 20} more")
    anoms = rep["anomalies"]
    print(f"\nexactly-once anomalies (double discharge): {len(anoms)}")
    for a in anoms[:10]:
        print(f"  [{a.get('kind')}] key={a.get('key')} "
              f"pid={a.get('pid')} thread={a.get('thread')}")
    return 1 if bad else 0


def cmd_xlasan(args) -> int:
    """Merged XLA recompile/host-sync ledger (devtools/xlasan.py).
    Run the workload with RAY_TPU_XLASAN=1 first; every process drops
    a <pid>.json ledger into the xlasan dir at exit.  Exit 1 when any
    jit site recompiled past the budget (--budget overrides
    RAY_TPU_XLASAN_BUDGET), 0 on a clean run."""
    from ray_tpu.devtools import xlasan
    rep = xlasan.merged_report(args.dir)
    budget = args.budget if args.budget is not None \
        else rep.get("budget", xlasan.DEFAULT_BUDGET)
    storms = sorted(s for s, m in rep["sites"].items()
                    if m["recompiles"] > budget)
    rep["budget"], rep["storms"] = budget, storms
    if args.json:
        print(json.dumps(rep, indent=1, default=str))
        return 1 if storms else 0
    print(f"xlasan report ({rep['processes']} process(es), "
          f"{rep['compiles']} compile(s) / {rep['recompiles']} "
          f"recompile(s), budget {budget}, dir "
          f"{args.dir or xlasan.report_dir()})")
    if not rep["processes"]:
        print("no ledgers found — run the workload with "
              "RAY_TPU_XLASAN=1")
        return 0
    ordered = sorted(rep["sites"].items(),
                     key=lambda kv: (-kv[1]["recompiles"],
                                     -kv[1]["seconds"]))
    print("\njit sites (calls / compiles / recompiles / compile-s):")
    for site, m in ordered[:20]:
        mark = "  STORM" if site in storms else ""
        print(f"  {m['calls']:>7} {m['compiles']:>5} "
              f"{m['recompiles']:>5} {m['seconds']:>9.3f}  "
              f"{m['label']} @ {site}{mark}")
        if site in storms:
            for d in m["deltas"][-3:]:
                print(f"      {d}")
    syncs = sorted(rep["syncs"].items(),
                   key=lambda kv: -kv[1]["count"])
    print(f"\nhost-sync sites: {len(syncs)}")
    for site, m in syncs[:10]:
        print(f"  x{m['count']:<7} {m['seconds']:>9.3f}s  "
              f"{m['kind']} @ {site}")
    if storms:
        print(f"\nRECOMPILE STORMS ({len(storms)} site(s) over "
              f"budget {budget}) — fix the static/arg churn above")
    return 1 if storms else 0


def cmd_drain(args) -> int:
    """Gracefully drain one node (reference: `ray drain-node`): the
    GCS flips it alive -> draining and the node hands back queued
    work, migrates its actors, re-replicates sole object copies, then
    exits — a planned departure instead of a failure.  `node_id` is a
    hex prefix (from `ray_tpu status` / `ray_tpu list nodes`)."""
    addr = _head_address(args)
    if not addr:
        print("no cluster on record; pass --address H:P",
              file=sys.stderr)
        return 1
    from ray_tpu._private.gcs_service import GcsClient
    host, port = _parse_addr(addr)
    gcs = GcsClient(host, port)
    try:
        matches = [n for n in gcs.nodes()
                   if n["node_id"].hex().startswith(args.node_id)
                   and n.get("state") == "alive"]
        if not matches:
            print(f"no alive node matches {args.node_id!r}",
                  file=sys.stderr)
            return 1
        if len(matches) > 1:
            print(f"node id prefix {args.node_id!r} is ambiguous "
                  f"({len(matches)} matches)", file=sys.stderr)
            return 1
        nid = matches[0]["node_id"]
        ok = gcs.drain_node(nid, grace_s=args.grace,
                            reason="operator drain (CLI)")
    finally:
        gcs.close()
    if not ok:
        print("drain refused (node no longer alive?)", file=sys.stderr)
        return 1
    print(f"draining node {nid.hex()[:12]} (grace {args.grace:g}s)")
    return 0


def cmd_gcs(args) -> int:
    """Control-plane fault-tolerance card (reference: the HA-GCS face
    of `ray status`): recovery epoch, uptime, WAL size + ops since the
    last snapshot/compaction, last-snapshot age, and node membership
    counts including stale (recovered-but-not-yet-resynced) records."""
    addr = _head_address(args)
    if not addr:
        print("no cluster on record; pass --address H:P",
              file=sys.stderr)
        return 1
    from ray_tpu._private.gcs_service import GcsClient
    host, port = _parse_addr(addr)
    try:
        gcs = GcsClient(host, port)
    except OSError as e:
        print(f"GCS at {addr} unreachable: {e}", file=sys.stderr)
        return 1
    try:
        st = gcs.status()
    finally:
        gcs.close()
    if getattr(args, "json", False):
        print(json.dumps(st, indent=1, default=str))
        return 0
    print(f"GCS at {addr}")
    print(f"  epoch:         {st['epoch']}"
          + ("  (recovered from WAL/snapshot)" if st.get("recovered")
             else ""))
    print(f"  uptime:        {st['uptime_s']:.1f}s")
    print(f"  durable:       {'yes (WAL+snapshot)' if st['persistent'] else 'NO — head death loses the cluster'}")
    if st["persistent"]:
        print(f"  wal:           {_fmt_bytes(st['wal_bytes'])} "
              f"({st['wal_ops_since_snapshot']} ops since snapshot)")
        age = st.get("last_snapshot_age_s")
        print(f"  last snapshot: "
              f"{'never (no compaction yet)' if age is None else f'{age:.1f}s ago'}")
    counts = ", ".join(f"{k}={v}" for k, v in
                       sorted(st.get("nodes", {}).items())) or "none"
    print(f"  nodes:         {counts}"
          + (f"  ({st['stale_nodes']} stale, awaiting re-sync)"
             if st.get("stale_nodes") else ""))
    print(f"  named actors:  {st['named_actors']}  "
          f"actor directory: {st['actor_directory']}")
    print(f"  objects:       {st['objects_tracked']} tracked, "
          f"{st['small_objects']} inline/error payloads")
    return 0


def cmd_chaos(args) -> int:
    """Print/validate a chaos fault-injection spec (the schedule from
    --spec, or the ambient RAY_TPU_CHAOS_SPEC / config + legacy env
    specs).  Exit 0 on a valid schedule, 2 on a grammar error."""
    from ray_tpu._private.chaos import (FAULT_KINDS, chaos, parse_spec)
    from ray_tpu._private.config import config
    if args.spec is not None:
        try:
            entries = [s.to_dict() for s in parse_spec(args.spec)]
        except ValueError as e:
            print(f"invalid chaos spec: {e}", file=sys.stderr)
            return 2
        seed = config.chaos_seed
    else:
        entries = chaos.describe()
        seed = config.chaos_seed
    if args.json:
        print(json.dumps({"seed": seed, "entries": entries}, indent=1))
        return 0
    print(f"chaos seed: {seed} "
          f"(same seed + workload => identical fault trace)")
    if not entries:
        print("no faults armed (set RAY_TPU_CHAOS_SPEC or pass --spec)")
    else:
        cols = ["site", "kind", "p", "n"]
        if any(e.get("interval_s") for e in entries):
            cols.append("interval_s")   # storm spacing (preempt storms)
        _print_table(entries, cols)
    print(f"fault kinds: {', '.join(FAULT_KINDS)}")
    return 0


# ---------------------------------------------------------------------------
# doctor / top / bench-diff (control-plane observability)
# ---------------------------------------------------------------------------
def _render_doctor(rep: dict) -> str:
    """Text face of `ray_tpu doctor` (pure: unit-testable)."""
    lines = []
    findings = rep.get("findings") or []
    errors = [f for f in findings if f.get("severity") == "error"]
    warns = [f for f in findings if f.get("severity") != "error"]
    if not findings:
        lines.append("cluster is HEALTHY — no findings")
    elif errors:
        lines.append(f"cluster is UNHEALTHY — {len(errors)} error(s), "
                     f"{len(warns)} warning(s)")
    else:
        lines.append(f"cluster is healthy with {len(warns)} warning(s)")
    for f in findings:
        sev = (f.get("severity") or "?").upper()
        lines.append(f"  [{sev:<7}] {f.get('code')}: "
                     f"{f.get('summary')}")
        detail = f.get("detail") or {}
        for k in sorted(detail):
            v = detail[k]
            text = json.dumps(v, default=str)
            if len(text) > 160:
                text = text[:160] + "..."
            lines.append(f"             {k}: {text}")
    for pe in rep.get("probe_errors") or []:
        lines.append(f"  (probe {pe.get('probe')} failed: "
                     f"{pe.get('error')})")
    lines.append(f"probes run: {', '.join(rep.get('probes') or [])}")
    return "\n".join(lines)


def cmd_doctor(args) -> int:
    """Cluster health triage (state.doctor() via /api/doctor): fuses
    GCS liveness/WAL health, node reachability, stall + slow-RPC
    sentinel captures, object leak suspects, event-ring drops, lock
    inversions, serve shedding, and train goodput into prioritized
    findings with stable codes.  Exit 1 when any error-severity
    finding is present, 0 otherwise."""
    rep = _fetch_json(
        f"/api/doctor?gcs_stale_s={args.gcs_stale_s:g}"
        f"&leak_min_age_s={args.leak_min_age_s:g}", args)
    if args.json:
        print(json.dumps(rep, indent=1, default=str))
    else:
        print(_render_doctor(rep))
    return int(rep.get("exit_code") or 0)


_SPARK_CHARS = "▁▂▃▄▅▆▇█"


def _sparkline(vals: List[float], width: int = 32) -> str:
    vals = list(vals)[-width:]
    if not vals:
        return ""
    lo, hi = min(vals), max(vals)
    if hi <= lo:
        return _SPARK_CHARS[0] * len(vals)
    span = hi - lo
    return "".join(
        _SPARK_CHARS[int((v - lo) / span * (len(_SPARK_CHARS) - 1))]
        for v in vals)


# Runtime gauges `ray_tpu top` always shows (one row per node each).
_TOP_BUILTINS = (
    "ray_tpu_tasks_pending",
    "ray_tpu_tasks_total",
    "ray_tpu_workers",
    "ray_tpu_actors_alive",
    "ray_tpu_objects_local",
    "ray_tpu_object_store_bytes_used",
)


def _series_rate(samples: List[list]) -> float:
    """Events/s over a monotone count series' sampled window."""
    if len(samples) < 2:
        return 0.0
    (t0, v0), (t1, v1) = samples[0], samples[-1]
    if t1 <= t0:
        return 0.0
    return max(v1 - v0, 0.0) / (t1 - t0)


def _render_top(series: List[dict], width: int = 32) -> str:
    """Text face of `ray_tpu top` (pure: unit-testable): sparkline
    per builtin gauge per node, plus the busiest RPC methods by
    handled rate with live in-flight counts."""
    lines = []
    by_name: Dict[str, List[dict]] = {}
    for row in series:
        by_name.setdefault(row.get("name", ""), []).append(row)
    lines.append("runtime (per node):")
    for name in _TOP_BUILTINS:
        for row in sorted(by_name.get(name, ()),
                          key=lambda r: r.get("node_id") or ""):
            samples = row.get("samples") or []
            vals = [s[1] for s in samples]
            last = vals[-1] if vals else 0.0
            nid = (row.get("node_id") or "?")[:8]
            shown = (_fmt_bytes(last) if name.endswith("bytes_used")
                     else f"{last:g}")
            lines.append(f"  {name:<34} {nid:<8} {shown:>10}  "
                         f"{_sparkline(vals, width)}")
    rpc_rows = []
    for row in by_name.get("ray_tpu_rpc_server_seconds", ()):
        method = (row.get("tags") or {}).get("method", "?")
        rate = _series_rate(row.get("samples") or [])
        rpc_rows.append((rate, method, row))
    inflight = {}
    for row in by_name.get("ray_tpu_rpc_inflight", ()):
        method = (row.get("tags") or {}).get("method", "?")
        samples = row.get("samples") or []
        if samples:
            inflight[method] = inflight.get(method, 0.0) + \
                samples[-1][1]
    if rpc_rows:
        lines.append("busiest RPC handlers (by handled/s):")
        rpc_rows.sort(key=lambda r: -r[0])
        for rate, method, row in rpc_rows[:10]:
            vals = [s[1] for s in row.get("samples") or []]
            lines.append(
                f"  {method:<26} {rate:>8.1f}/s  inflight "
                f"{inflight.get(method, 0):g}  "
                f"{_sparkline(vals, width)}")
    if not series:
        lines.append("  (no history samples yet — the ring fills at "
                     "metrics_history_resolution_s cadence)")
    return "\n".join(lines)


def cmd_top(args) -> int:
    """Live terminal view over the metrics history rings
    (/api/metrics/history): runtime gauges + busiest RPC handlers,
    refreshed every --interval seconds.  --iterations N renders N
    frames then exits (0 = until Ctrl-C)."""
    frames = 0
    try:
        while True:
            data = _fetch_json("/api/metrics/history", args)
            frame = _render_top(data.get("series") or [],
                                width=args.width)
            if frames and not args.no_clear:
                print("\x1b[2J\x1b[H", end="")
            print(frame)
            unreachable = data.get("unreachable_nodes") or []
            if unreachable:
                print("WARNING: partial view — unreachable nodes: "
                      + ", ".join(n[:12] for n in unreachable))
            frames += 1
            if args.iterations and frames >= args.iterations:
                return 0
            time.sleep(args.interval)
    except KeyboardInterrupt:
        return 0


# Direction markers for bench-diff: a dotted metric path matching a
# higher-better marker regresses when it DROPS; lower-better (latency-
# shaped) paths regress when they RISE.  Higher-better wins ties
# ("speedup_p50" is a speedup, not a latency).
_BENCH_HIGHER = ("per_s", "_mb_s", "mbps", "throughput", "speedup",
                 "goodput", "goodput_fraction", "mfu", "tokens_s",
                 "qps")
_BENCH_LOWER = ("_us", "_ms", "_ns", "p50", "p95", "p99", "latency",
                "seconds", "_s_", "overhead", "stall")


def _bench_direction(path: str) -> Optional[str]:
    low = path.lower()
    if any(m in low for m in _BENCH_HIGHER):
        return "higher"
    if any(m in low for m in _BENCH_LOWER):
        return "lower"
    return None


def _bench_flatten(obj: Any, prefix: str = "") -> Dict[str, float]:
    out: Dict[str, float] = {}
    if isinstance(obj, dict):
        for k, v in obj.items():
            out.update(_bench_flatten(v, f"{prefix}{k}."))
    elif isinstance(obj, (int, float)) and not isinstance(obj, bool):
        out[prefix[:-1]] = float(obj)
    return out


def _bench_diff(fresh: dict, baseline: dict,
                tolerance: float = 0.10) -> List[dict]:
    """Compare two bench-capture dicts metric by metric (pure:
    unit-testable).  Returns one row per baseline metric: {path,
    base, new, delta_pct, direction, regressed}; metrics with no
    direction marker (counts, config echoes) are informational and
    never regress, as are metrics absent from the fresh capture
    (legs not re-run)."""
    fflat = _bench_flatten(fresh)
    bflat = _bench_flatten(baseline)
    rows = []
    for path in sorted(bflat):
        base = bflat[path]
        new = fflat.get(path)
        direction = _bench_direction(path)
        row = {"path": path, "base": base, "new": new,
               "direction": direction, "delta_pct": None,
               "regressed": False}
        if new is not None and base:
            row["delta_pct"] = round(100.0 * (new - base) / abs(base),
                                     2)
        if new is not None and direction == "higher":
            row["regressed"] = new < base * (1.0 - tolerance)
        elif new is not None and direction == "lower":
            row["regressed"] = new > base * (1.0 + tolerance)
        rows.append(row)
    return rows


def cmd_bench_diff(args) -> int:
    """Regression gate over bench captures: compare a fresh result
    JSON against a last-good one, direction-aware per metric
    (throughput-shaped metrics must not drop, latency-shaped must not
    rise, beyond --tolerance).  Exit 1 on any regression, 0 otherwise."""
    with open(args.fresh) as f:
        fresh = json.load(f)
    with open(args.baseline) as f:
        baseline = json.load(f)
    rows = _bench_diff(fresh, baseline, tolerance=args.tolerance)
    regressions = [r for r in rows if r["regressed"]]
    if args.json:
        print(json.dumps({"rows": rows,
                          "regressions": len(regressions),
                          "tolerance": args.tolerance},
                         indent=1))
        return 1 if regressions else 0
    shown = [r for r in rows
             if r["regressed"] or (
                 r["direction"] and r["delta_pct"] is not None
                 and abs(r["delta_pct"]) >= 1.0)]
    print(f"bench-diff {args.fresh} vs {args.baseline} "
          f"(tolerance {args.tolerance:.0%}): "
          f"{len(rows)} metrics, {len(regressions)} regression(s)")
    table = [{
        "metric": r["path"],
        "base": f"{r['base']:g}",
        "new": "missing" if r["new"] is None else f"{r['new']:g}",
        "delta": ("" if r["delta_pct"] is None
                  else f"{r['delta_pct']:+.1f}%"),
        "want": r["direction"] or "-",
        "verdict": "REGRESSED" if r["regressed"] else "ok",
    } for r in shown]
    if table:
        _print_table(table, ["metric", "base", "new", "delta",
                             "want", "verdict"])
    else:
        print("(no directional metric moved by 1% or more)")
    return 1 if regressions else 0


# ---------------------------------------------------------------------------
def main(argv: Optional[List[str]] = None) -> int:
    raw = sys.argv[1:] if argv is None else list(argv)
    ap = argparse.ArgumentParser(prog="ray_tpu")
    sub = ap.add_subparsers(dest="cmd", required=True)

    p = sub.add_parser("start", help="start head or join a cluster")
    p.add_argument("--head", action="store_true")
    p.add_argument("--address", default=None, help="H:P of existing GCS")
    p.add_argument("--host", default="127.0.0.1")
    p.add_argument("--port", type=int, default=0)
    p.add_argument("--num-cpus", type=float, default=None)
    p.add_argument("--num-tpus", type=float, default=None)
    p.add_argument("--resources", default="{}")
    p.add_argument("--object-store-memory", type=int, default=0)
    p.add_argument("--dashboard-port", type=int, default=8265)
    p.add_argument("--persist-dir", default="",
                   help="durable GCS state dir (survives head restarts)")
    p.add_argument("--timeout", type=float, default=60.0)
    p.set_defaults(fn=cmd_start)

    p = sub.add_parser("stop", help="stop CLI-started processes")
    p.set_defaults(fn=cmd_stop)

    p = sub.add_parser("status", help="cluster nodes + resources")
    p.add_argument("--address", default=None)
    p.set_defaults(fn=cmd_status)

    p = sub.add_parser("list", help="list runtime entities")
    p.add_argument("kind", choices=["tasks", "actors", "workers",
                                    "objects", "nodes", "pgs"])
    p.add_argument("--dashboard-url", default=None)
    p.set_defaults(fn=cmd_list)

    p = sub.add_parser("summary", help="state rollups")
    p.add_argument("--dashboard-url", default=None)
    p.set_defaults(fn=cmd_summary)

    p = sub.add_parser(
        "memory", help="cluster memory accounting (by kind/owner/node)")
    p.add_argument("--dashboard-url", default=None)
    p.add_argument("--group-by", choices=["node", "owner"],
                   default="node", dest="group_by")
    p.add_argument("--leak-suspects", action="store_true",
                   dest="leak_suspects",
                   help="flag old objects whose owner is dead or "
                        "whose borrow count is zero")
    p.add_argument("--min-age-s", type=float, default=60.0,
                   dest="min_age_s",
                   help="minimum age before an object can be a leak "
                        "suspect")
    p.set_defaults(fn=cmd_memory)

    p = sub.add_parser(
        "device-time",
        help="a profiler trace's device time by program and scope")
    p.add_argument("trace", help="an .xplane.pb, or a directory that "
                                 "holds one (the newest is read)")
    p.add_argument("--scopes", type=int, default=12,
                   help="rows to print under each program")
    p.add_argument("--json", action="store_true",
                   help="print profiling.device_time's whole result")
    p.set_defaults(fn=cmd_device_time)

    p = sub.add_parser("metrics", help="Prometheus metrics dump")
    p.add_argument("--dashboard-url", default=None)
    p.set_defaults(fn=cmd_metrics)

    p = sub.add_parser("timeline",
                       help="chrome-trace export of the task timeline")
    p.add_argument("--dashboard-url", default=None)
    p.add_argument("--out", default=None,
                   help="write the trace JSON to this file")
    p.set_defaults(fn=cmd_timeline)

    p = sub.add_parser("job", help="job submission")
    jsub = p.add_subparsers(dest="job_cmd", required=True)
    j = jsub.add_parser("submit")
    j.add_argument("--address", default=None)
    j.add_argument("--working-dir", default=None)
    j.add_argument("--wait", action="store_true")
    j.add_argument("entrypoint", nargs=argparse.REMAINDER)
    for name in ("status", "logs", "stop"):
        j = jsub.add_parser(name)
        j.add_argument("job_id")
        j.add_argument("--address", default=None)
    j = jsub.add_parser("list")
    j.add_argument("--address", default=None)
    p.set_defaults(fn=cmd_job)

    p = sub.add_parser(
        "stack",
        help="dump live worker stack traces (cluster-wide; optional "
             "task targeting and flamegraph sampling)")
    p.add_argument("task_id", nargs="?", default=None,
                   help="task id hex prefix: dump only the worker(s) "
                        "executing that task")
    p.add_argument("--dashboard-url", default=None)
    p.add_argument("--timeout", type=float, default=10.0)
    p.add_argument("--flame", action="store_true",
                   help="sample stacks and emit flamegraph.pl folded "
                        "format instead of one-shot dumps")
    p.add_argument("--samples", type=int, default=40,
                   help="samples per worker in --flame mode")
    p.add_argument("--interval", type=float, default=0.02,
                   help="seconds between samples in --flame mode")
    p.add_argument("--out", default=None,
                   help="write --flame output to this file")
    p.set_defaults(fn=cmd_stack)

    p = sub.add_parser("train", help="training telemetry")
    tsub = p.add_subparsers(dest="train_cmd", required=True)
    tp = tsub.add_parser(
        "status",
        help="per-run step decomposition (data_wait/compile/step/"
             "checkpoint/sync), live MFU, goodput ledger, and "
             "straggler verdicts")
    tp.add_argument("--dashboard-url", default=None)
    tp.add_argument("--run", default=None,
                    help="narrow to one run (default: all runs)")
    tp.add_argument("--json", action="store_true",
                    help="machine-readable dump")
    p.set_defaults(fn=cmd_train)

    p = sub.add_parser("serve", help="declarative serve config")
    ssub = p.add_subparsers(dest="serve_cmd", required=True)
    sp = ssub.add_parser("deploy", help="apply a YAML app config")
    sp.add_argument("config")
    sp.add_argument("--address", default=None,
                    help="cluster client address host:port")
    sp2 = ssub.add_parser(
        "status", help="deployments: replicas by state, queue depth, "
                       "shed counters, autoscale decision")
    sp2.add_argument("--address", default=None)
    sp2.add_argument("--json", action="store_true",
                     help="machine-readable dump")
    sp3 = ssub.add_parser("shutdown")
    sp3.add_argument("--address", default=None)
    p.set_defaults(fn=cmd_serve)

    p = sub.add_parser("microbench", help="core perf harness")
    p.set_defaults(fn=cmd_microbench)

    p = sub.add_parser(
        "drain", help="gracefully drain a node (planned departure)")
    p.add_argument("node_id", help="node id hex prefix")
    p.add_argument("--grace", type=float, default=30.0,
                   help="seconds the node gets to hand off its work")
    p.add_argument("--address", default=None, help="GCS address H:P")
    p.set_defaults(fn=cmd_drain)

    p = sub.add_parser(
        "gcs", help="control-plane status: epoch / uptime / WAL / "
                    "last snapshot")
    p.add_argument("--address", default=None, help="GCS address H:P")
    p.add_argument("--json", action="store_true")
    p.set_defaults(fn=cmd_gcs)

    p = sub.add_parser(
        "chaos", help="print/validate a chaos fault-injection spec")
    p.add_argument("--spec", default=None,
                   help="spec to validate (default: the ambient "
                        "config/env schedule)")
    p.add_argument("--json", action="store_true")
    p.set_defaults(fn=cmd_chaos)

    p = sub.add_parser(
        "doctor",
        help="cluster health triage: prioritized findings with "
             "stable codes (exit 1 on error-severity findings)")
    p.add_argument("--dashboard-url", default=None)
    p.add_argument("--gcs-stale-s", type=float, default=15.0,
                   dest="gcs_stale_s",
                   help="flag GCS_UNREACHABLE when a node's last "
                        "successful GCS heartbeat is older than this")
    p.add_argument("--leak-min-age-s", type=float, default=60.0,
                   dest="leak_min_age_s",
                   help="minimum object age before it can be a "
                        "LEAK_SUSPECT")
    p.add_argument("--json", action="store_true")
    p.set_defaults(fn=cmd_doctor)

    p = sub.add_parser(
        "top",
        help="live terminal view over the metrics history rings "
             "(runtime gauges + busiest RPC handlers)")
    p.add_argument("--dashboard-url", default=None)
    p.add_argument("--interval", type=float, default=2.0,
                   help="seconds between frames")
    p.add_argument("--iterations", type=int, default=0,
                   help="render N frames then exit (0 = until Ctrl-C)")
    p.add_argument("--width", type=int, default=32,
                   help="sparkline width in samples")
    p.add_argument("--no-clear", action="store_true", dest="no_clear",
                   help="append frames instead of clearing the screen")
    p.set_defaults(fn=cmd_top)

    p = sub.add_parser(
        "bench-diff",
        help="compare a fresh bench capture against a last-good one "
             "(direction-aware; exit 1 on regression)")
    p.add_argument("fresh", help="fresh capture JSON")
    p.add_argument("baseline", help="last-good capture JSON")
    p.add_argument("--tolerance", type=float, default=0.10,
                   help="allowed fractional change before a "
                        "directional metric counts as regressed")
    p.add_argument("--json", action="store_true")
    p.set_defaults(fn=cmd_bench_diff)

    p = sub.add_parser(
        "locksan",
        help="merged lock-sanitizer report (inversions / long holds "
             "/ contention) from a RAY_TPU_LOCKSAN=1 run")
    p.add_argument("--dir", default=None,
                   help="report directory (default: the ambient "
                        "locksan dir)")
    p.add_argument("--json", action="store_true")
    p.set_defaults(fn=cmd_locksan)

    p = sub.add_parser(
        "leaksan",
        help="merged resource-leak ledger (leaked blocks/slots/fds/"
             "threads/series) from a RAY_TPU_LEAKSAN=1 run")
    p.add_argument("--dir", default=None,
                   help="ledger directory (default: the ambient "
                        "leaksan dir)")
    p.add_argument("--json", action="store_true")
    p.set_defaults(fn=cmd_leaksan)

    p = sub.add_parser(
        "xlasan",
        help="merged XLA recompile/host-sync ledger (per-jit-site "
             "compile counts, arg-shape deltas, storm verdicts) from "
             "a RAY_TPU_XLASAN=1 run")
    p.add_argument("--dir", default=None,
                   help="ledger directory (default: the ambient "
                        "xlasan dir)")
    p.add_argument("--budget", type=int, default=None,
                   help="recompiles allowed per jit site before it "
                        "counts as a storm (default: "
                        "RAY_TPU_XLASAN_BUDGET or 2)")
    p.add_argument("--json", action="store_true")
    p.set_defaults(fn=cmd_xlasan)

    # The rule-table epilog imports + registers the whole lint rule
    # set; only `ray_tpu lint -h` ever renders a subparser epilog, so
    # build it only on the lint path — every other command stays lean.
    epilog = None
    if raw and raw[0] == "lint":
        from ray_tpu.devtools.lint import cli as lint_cli
        epilog = lint_cli.rule_table_text()
    from ray_tpu.devtools.lint.cli import add_arguments
    p = sub.add_parser(
        "lint", help="static analysis for remote/actor/sharding code",
        epilog=epilog,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    add_arguments(p)
    p.set_defaults(fn=cmd_lint)

    args = ap.parse_args(argv)
    return args.fn(args)


if __name__ == "__main__":
    sys.exit(main())
