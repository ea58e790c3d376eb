"""`ray-tpu` CLI (reference: `python/ray/scripts/scripts.py` —
start:676 / stop / status, memory, timeline, microbenchmark; `ray job`
CLI in `dashboard/modules/job/cli.py`).

Cluster lifecycle: ``ray-tpu start --head`` stands up a head + node
daemons as persistent OS processes (daemons survive driver disconnects);
any driver joins with ``ray_tpu.init(address="host:port")``; ``ray-tpu
stop`` tears the cluster down. The address of the last locally started
cluster is recorded in ``/tmp/ray_tpu/current_cluster.json`` so
``stop``/``status`` work without arguments.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

CLUSTER_FILE = "/tmp/ray_tpu/current_cluster.json"


def _init_runtime(args):
    import ray_tpu
    if not ray_tpu.is_initialized():
        ray_tpu.init(num_nodes=args.num_nodes)
    return ray_tpu


def cmd_status(args) -> int:
    ray_tpu = _init_runtime(args)
    from ray_tpu.util import state as st
    print(json.dumps({
        "nodes": st.list_nodes(),
        "cluster_resources": ray_tpu.cluster_resources(),
        "available_resources": ray_tpu.available_resources(),
    }, indent=2, default=str))
    return 0


def cmd_summary(args) -> int:
    _init_runtime(args)
    from ray_tpu.util import state as st
    print(json.dumps({"tasks": st.summarize_tasks(),
                      "actors": len(st.list_actors()),
                      "placement_groups": len(st.list_placement_groups())},
                     indent=2))
    return 0


def cmd_memory(args) -> int:
    ray_tpu = _init_runtime(args)
    from ray_tpu._private import worker as _worker
    rt = _worker.global_runtime()
    rows = []
    for node in rt.nodes():
        rows.append({"node_id": node.node_id.hex()[:16],
                     "used_bytes": node.store.used_bytes(),
                     "num_objects": len(node.store.object_ids()),
                     "stats": dict(node.store.stats)})
    print(json.dumps(rows, indent=2))
    return 0


def cmd_timeline(args) -> int:
    _init_runtime(args)
    from ray_tpu.util import state as st
    # merged cluster trace: one lane per process (driver / daemon /
    # worker), clock-corrected spans from the head's task-event store
    path = st.cluster_timeline(args.output)
    print(f"wrote chrome trace to {path}")
    return 0


def cmd_profile(args) -> int:
    """Cluster-wide stack profile: on-demand burst fan-out to every
    process (driver / daemons / workers) merged with the head's
    federated continuous aggregates, written as speedscope JSON (one
    lane per process — the profiling counterpart of `ray-tpu
    timeline`)."""
    _init_runtime(args)
    from ray_tpu.util import state as st
    node = args.node if not args.all else None
    out = st.cluster_profile(duration_s=args.duration, node=node,
                             path=args.output, fmt=args.format)
    for rec in out["records"]:
        print(f"  {rec['proc']:<24} {rec.get('mode', '?'):<10} "
              f"{rec.get('samples', 0):>6} samples")
    print(f"wrote {args.format} profile ({len(out['records'])} "
          f"processes) to {args.output}")
    return 0


def cmd_microbenchmark(args) -> int:
    from ray_tpu._private.perf import run_microbenchmarks
    for row in run_microbenchmarks(duration_s=args.duration):
        print(json.dumps(row))
    return 0


def cmd_dashboard(args) -> int:
    _init_runtime(args)
    from ray_tpu.dashboard import start_dashboard
    host, port = start_dashboard(port=args.port)
    print(f"dashboard at http://{host}:{port}")
    try:
        import time
        while True:
            time.sleep(3600)
    except KeyboardInterrupt:
        return 0


def _resolve_address(args) -> str:
    addr = getattr(args, "address", None)
    if addr:
        return addr
    try:
        with open(CLUSTER_FILE) as f:
            return json.load(f)["address"]
    except (OSError, KeyError, ValueError):
        raise SystemExit(
            "no --address given and no local cluster recorded "
            f"({CLUSTER_FILE}); start one with `ray-tpu start --head`")


def cmd_start(args) -> int:
    """Stand up a persistent head + N node daemons (scripts.py:676)."""
    if not args.head:
        raise SystemExit("only --head mode is supported: pass --head "
                         "(joining remote workers use the daemon module "
                         "with --head host:port directly)")
    from ray_tpu._private.cluster import _spawn
    from ray_tpu._private.ids import NodeID

    session = os.path.join("/tmp", "ray_tpu",
                           f"cluster_{os.getpid()}")
    os.makedirs(session, exist_ok=True)
    head_args = ["--state-path", os.path.join(session, "head_state.db")]
    if args.port:
        head_args += ["--port", str(args.port)]
    head_proc, head_port = _spawn(
        "ray_tpu._private.head", head_args,
        output_path=os.path.join(session, "head.log"))
    address = f"127.0.0.1:{head_port}"

    resources = args.resources or json.dumps(
        {"CPU": float(os.cpu_count() or 4)})
    daemon_pids = []
    for _ in range(args.num_daemons):
        proc, _port = _spawn("ray_tpu._private.daemon", [
            "--head", address,
            "--node-id", NodeID.from_random().hex(),
            "--resources", resources,
            "--object-store-bytes", str(args.object_store_bytes),
            "--persist",
        ], output_path=os.path.join(session, "daemon.log"))
        daemon_pids.append(proc.pid)

    os.makedirs(os.path.dirname(CLUSTER_FILE), exist_ok=True)
    with open(CLUSTER_FILE, "w") as f:
        json.dump({"address": address, "head_pid": head_proc.pid,
                   "daemon_pids": daemon_pids, "session": session}, f)
    print(f"ray_tpu cluster started at {address} "
          f"({args.num_daemons} daemons)")
    print(f'connect with: ray_tpu.init(address="{address}")')
    if not args.block:
        return 0
    # --block: stay up and respawn a crashed head on the same port
    import time
    try:
        while True:
            time.sleep(0.5)
            if head_proc.poll() is not None:
                try:
                    head_proc, _ = _spawn(
                        "ray_tpu._private.head",
                        ["--state-path",
                         os.path.join(session, "head_state.db"),
                         "--port", str(head_port)],
                        output_path=os.path.join(session, "head.log"))
                except (RuntimeError, OSError):
                    continue
                try:   # keep stop's pid fallback pointing at the LIVE head
                    with open(CLUSTER_FILE) as f:
                        rec = json.load(f)
                    rec["head_pid"] = head_proc.pid
                    with open(CLUSTER_FILE, "w") as f:
                        json.dump(rec, f)
                except (OSError, ValueError):
                    pass
    except KeyboardInterrupt:
        return cmd_stop(args)


def cmd_stop(args) -> int:
    """Tear down the cluster recorded in the cluster file (or at
    --address): stop every registered daemon, then the head."""
    import signal

    address = _resolve_address(args)
    host, port = address.rsplit(":", 1)
    from ray_tpu._private import rpc
    from ray_tpu._private.head import HeadClient

    stopped = 0
    try:
        head = HeadClient((host, int(port)))
        for info in head.list_nodes():
            if not info["alive"]:
                continue
            try:
                rpc.connect(tuple(info["addr"]), timeout=5.0).call(
                    "daemon_stop", timeout=2.0)
                stopped += 1
            except (rpc.RpcError, OSError):
                pass
        head.stop_head()
        head.close()
    except (OSError, rpc.RpcError):
        # head already gone: fall back to recorded pids
        try:
            with open(CLUSTER_FILE) as f:
                rec = json.load(f)
            for pid in [rec.get("head_pid"), *rec.get("daemon_pids", [])]:
                if pid:
                    try:
                        os.kill(pid, signal.SIGKILL)
                    except (OSError, ProcessLookupError):
                        pass
        except (OSError, ValueError):
            pass
    try:
        os.unlink(CLUSTER_FILE)
    except OSError:
        pass
    print(f"stopped cluster at {address} ({stopped} daemons)")
    return 0


def cmd_cluster_status(args) -> int:
    """Membership of a running cluster (no runtime init)."""
    address = _resolve_address(args)
    host, port = address.rsplit(":", 1)
    from ray_tpu._private.head import HeadClient

    head = HeadClient((host, int(port)))
    nodes = head.list_nodes()
    head.close()
    print(json.dumps({"address": address, "nodes": nodes}, indent=2,
                     default=str))
    return 0


def cmd_drain(args) -> int:
    """Gracefully drain a node of a running cluster (no runtime init):
    the head fences new placements, connected drivers migrate work off,
    and the deadline escalates to the death path."""
    address = _resolve_address(args)
    host, port = address.rsplit(":", 1)
    from ray_tpu._private.head import HeadClient

    head = HeadClient((host, int(port)))
    try:
        out = head.drain_node(args.node_id, args.deadline_s, args.reason)
    finally:
        head.close()
    out.pop("i", None)      # rpc correlation id, not user-facing
    print(json.dumps({"address": address, "node_id": args.node_id,
                      **out}, indent=2, default=str))
    return 0 if out.get("ok") else 1


def cmd_serve_deploy(args) -> int:
    """Deploy Serve applications from a YAML/JSON config (the
    `serve deploy` role)."""
    _init_runtime(args)
    from ray_tpu import serve

    handles = serve.run_config(args.config_file)
    print(f"deployed {len(handles)} application(s): "
          f"{sorted(handles)}")
    if args.http_port >= 0:
        port = serve.start_http_proxy(port=args.http_port)
        print(f"http proxy on :{port}")
        import time
        try:
            while True:
                time.sleep(3600)
        except KeyboardInterrupt:
            return 0
    return 0


def cmd_job_submit(args) -> int:
    _init_runtime(args)
    from ray_tpu.job import JobSubmissionClient
    client = JobSubmissionClient()
    job_id = client.submit_job(entrypoint=args.entrypoint)
    status = client.wait_until_finished(job_id, timeout=args.timeout)
    print(client.get_job_logs(job_id), end="")
    print(f"job {job_id}: {status}")
    return 0 if status == "SUCCEEDED" else 1


def cmd_loadgen(args) -> int:
    # reached only when a global flag precedes the subcommand
    # (`ray-tpu --num-nodes 2 loadgen ...`); the bare form short-circuits
    # before argparse in main()
    from ray_tpu.loadgen.__main__ import main as loadgen_main
    return loadgen_main(args.rest)


def cmd_attach(args) -> int:
    """Open a shell (or run a command) wired to the running cluster
    (reference: `ray attach` opens a shell on the head; the local
    equivalent exports RAY_TPU_ADDRESS so `ray_tpu.init()` with no
    arguments joins the cluster)."""
    import subprocess

    address = getattr(args, "cluster", "") or _try_cluster_address()
    if not address:
        raise SystemExit("no running cluster (start one with "
                         "`ray-tpu start --head` or `ray-tpu up`)")
    env = dict(os.environ)
    env["RAY_TPU_ADDRESS"] = address
    cmd = args.cmd or [os.environ.get("SHELL", "/bin/bash")]
    print(f"attached to {address} (RAY_TPU_ADDRESS set)")
    return subprocess.call(cmd, env=env)


def cmd_up(args) -> int:
    """Create the cluster described by a YAML config (reference:
    `ray up`, scripts.py:1419 over autoscaler commands.py)."""
    from ray_tpu.cluster_launcher import up
    state = up(args.config_file)
    workers = sum(1 for n in state["nodes"] if n["kind"] == "worker")
    print(f"cluster {state['cluster_name']!r} up at {state['address']} "
          f"({workers} workers)")
    print(f'connect with: ray_tpu.init(address="{state["address"]}")')
    return 0


def cmd_down(args) -> int:
    from ray_tpu.cluster_launcher import down
    n = down(args.config_file)
    print(f"terminated {n} nodes")
    return 0


def cmd_debug(args) -> int:
    """List active remote-debugger sessions or attach to one
    (reference: the `ray debug` CLI over ray.util.rpdb). Listing reads
    the RUNNING cluster's head KV (cluster file or --cluster), never a
    fresh isolated runtime."""
    from ray_tpu.util import rpdb
    if args.session:
        host, _, port = args.session.rpartition(":")
        token = getattr(args, "token", None)
        if not token:
            # externally-bound sessions require their KV-advertised
            # token; resolve it from the running cluster when possible
            cluster = getattr(args, "cluster", "") or _try_cluster_address()
            if cluster:
                from ray_tpu._private.head import HeadClient
                chost, cport = cluster.rsplit(":", 1)
                head = HeadClient((chost, int(cport)))
                try:
                    for s in rpdb.sessions_from_kv(head):
                        if (str(s.get("port")) == port
                                and s.get("host") == (host
                                                      or "127.0.0.1")
                                and s.get("token")):
                            token = s["token"]
                            break
                finally:
                    head.close()
        rpdb.connect(host or "127.0.0.1", int(port), token=token)
        return 0
    sessions = []
    cluster = getattr(args, "cluster", "") or _try_cluster_address()
    if cluster:
        from ray_tpu._private.head import HeadClient
        host, port = cluster.rsplit(":", 1)
        head = HeadClient((host, int(port)))
        try:
            sessions = rpdb.sessions_from_kv(head)
        finally:
            head.close()
    else:
        # same-process fallback (tests / embedded drivers)
        import ray_tpu
        if ray_tpu.is_initialized():
            sessions = rpdb.active_sessions()
    if not sessions:
        print("no active debugger sessions")
        return 0
    for s in sessions:
        print(f"{s['host']}:{s['port']}  pid={s['pid']} "
              f"task={s.get('task_id')}  {s.get('banner', '')}")
    return 0


def _try_cluster_address() -> str:
    try:
        with open(CLUSTER_FILE) as f:
            return json.load(f)["address"]
    except Exception:
        return ""


def main(argv=None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    if argv and argv[0] == "loadgen":
        # pass-through BEFORE argparse: the loadgen CLI owns its whole
        # flag surface (argparse.REMAINDER drops a leading `--help`)
        from ray_tpu.loadgen.__main__ import main as loadgen_main
        return loadgen_main(argv[1:])
    parser = argparse.ArgumentParser(
        prog="ray-tpu", description="ray_tpu cluster CLI")
    parser.add_argument("--num-nodes", type=int, default=1)
    sub = parser.add_subparsers(dest="command", required=True)

    p = sub.add_parser("start")
    p.add_argument("--head", action="store_true")
    p.add_argument("--port", type=int, default=0)
    p.add_argument("--num-daemons", type=int, default=2)
    p.add_argument("--resources", default="",
                   help="JSON resource map per daemon")
    p.add_argument("--object-store-bytes", type=int,
                   default=256 * 1024 * 1024)
    p.add_argument("--block", action="store_true",
                   help="stay attached; supervise the head")
    p = sub.add_parser("stop")
    p.add_argument("--address", default="")
    p = sub.add_parser("cluster-status")
    p.add_argument("--address", default="")
    p = sub.add_parser("drain")
    p.add_argument("node_id", help="node id (hex) to drain gracefully")
    p.add_argument("--address", default="")
    p.add_argument("--deadline-s", type=float, default=30.0,
                   dest="deadline_s",
                   help="drain window before escalating to node death")
    p.add_argument("--reason", default="manual drain")
    sub.add_parser("status")
    sub.add_parser("summary")
    sub.add_parser("memory")
    p = sub.add_parser("timeline")
    p.add_argument("--output", default="/tmp/ray_tpu_timeline.json")
    p = sub.add_parser("profile")
    p.add_argument("--node", default="",
                   help="profile only the daemon whose node id (hex) "
                        "starts with this prefix")
    p.add_argument("--all", action="store_true",
                   help="whole cluster: driver + every daemon/worker + "
                        "head aggregates (the default when --node is "
                        "not given)")
    p.add_argument("--duration", type=float, default=2.0,
                   help="burst sampling window in seconds")
    p.add_argument("--output", default="/tmp/ray_tpu_profile.json")
    p.add_argument("--format", choices=["speedscope", "collapsed"],
                   default="speedscope")
    p = sub.add_parser("microbenchmark")
    p.add_argument("--duration", type=float, default=2.0)
    p = sub.add_parser("dashboard")
    p.add_argument("--port", type=int, default=8265)
    p = sub.add_parser("serve-deploy")
    p.add_argument("config_file")
    p.add_argument("--http-port", type=int, default=-1,
                   help=">=0: start the HTTP proxy and block")
    p = sub.add_parser("job-submit")
    p.add_argument("entrypoint")
    p.add_argument("--timeout", type=float, default=300.0)
    p = sub.add_parser(
        "loadgen", add_help=False,
        help="open-loop serving load generator "
             "(see `ray-tpu loadgen --help`)")
    p.add_argument("rest", nargs=argparse.REMAINDER)
    p = sub.add_parser("up")
    p.add_argument("config_file", help="cluster YAML (see "
                                       "ray_tpu/cluster_launcher.py)")
    p = sub.add_parser("down")
    p.add_argument("config_file")
    p = sub.add_parser("attach")
    p.add_argument("cmd", nargs="*",
                   help="command to run attached (default: $SHELL)")
    p.add_argument("--cluster", default="",
                   help="head host:port (default: the cluster file)")
    p = sub.add_parser("debug")
    p.add_argument("session", nargs="?", default="",
                   help="host:port of a session to attach; empty = list")
    p.add_argument("--cluster", default="",
                   help="head host:port (default: the cluster file)")
    p.add_argument("--token", default="",
                   help="session token for externally-bound sessions "
                        "(default: resolved from the cluster KV)")

    args, extra = parser.parse_known_args(argv)
    if args.command == "loadgen":
        # global-flag-prefixed form (`ray-tpu --num-nodes 2 loadgen …`):
        # REMAINDER cannot capture leading option-like tokens
        # (bpo-17050), so hand loadgen everything after its own name.
        # Safe slice: the only global flag takes an int value, so the
        # first "loadgen" token IS the subcommand.
        args.rest = argv[argv.index("loadgen") + 1:]
    elif extra:
        parser.error("unrecognized arguments: " + " ".join(extra))
    handler = {
        "start": cmd_start, "stop": cmd_stop,
        "cluster-status": cmd_cluster_status, "drain": cmd_drain,
        "status": cmd_status, "summary": cmd_summary,
        "memory": cmd_memory, "timeline": cmd_timeline,
        "profile": cmd_profile,
        "microbenchmark": cmd_microbenchmark, "dashboard": cmd_dashboard,
        "serve-deploy": cmd_serve_deploy, "job-submit": cmd_job_submit,
        "up": cmd_up, "down": cmd_down, "attach": cmd_attach,
        "debug": cmd_debug, "loadgen": cmd_loadgen,
    }[args.command]
    return handler(args)


if __name__ == "__main__":
    sys.exit(main())
