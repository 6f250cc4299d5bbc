"""CLI — `python -m minio_tpu server ...` process bootstrap.

The reference's L0 (main.go + cmd/server-main.go): parse args, boot the
node, print the startup banner, block on signals.

Single node:
    python -m minio_tpu server /data/d{1...16} --address :9000

Distributed (run once per node, same node list everywhere):
    python -m minio_tpu server \
        --node 10.0.0.1:9000=/data/d{1...8} \
        --node 10.0.0.2:9000=/data/d{1...8} \
        --this 0
"""

from __future__ import annotations

import argparse
import os
import signal
import sys

from .cluster import NodeSpec, parse_node_arg, start_node, start_single
from .object.codec import data_path_line
from .s3.credentials import Credentials, global_credentials


def _parse(argv: list[str]) -> argparse.Namespace:
    p = argparse.ArgumentParser(prog="minio_tpu")
    sub = p.add_subparsers(dest="cmd", required=True)
    s = sub.add_parser("server", help="start an object-store node")
    s.add_argument("drives", nargs="*",
                   help="local drive paths (ellipses {1...N} supported)")
    s.add_argument("--address", default=":9000",
                   help="listen address host:port (default :9000)")
    s.add_argument("--node", action="append", default=[],
                   help="host:port=/drive{1...N} — one per cluster node")
    s.add_argument("--this", type=int, default=-1,
                   help="index of this node in the --node list")
    s.add_argument("--parity", type=int, default=None,
                   help="parity shards per set (default N/2)")
    s.add_argument("--set-drive-count", type=int, default=0,
                   help="drives per erasure set (default: auto 4..16)")
    s.add_argument("--region", default=os.environ.get(
        "MINIO_REGION", "us-east-1"))
    s.add_argument("--cert", default="", help="TLS certificate file")
    s.add_argument("--key", default="", help="TLS private key file")
    s.add_argument("--pool", action="append", default=[],
                   help="extra drive pool /data2/d{1...N} appended "
                   "after boot (single-node topology expansion); "
                   "repeatable")

    d = sub.add_parser("decommission",
                       help="drain a pool's objects into the active "
                       "pools (admin rebalance surface)")
    d.add_argument("--url", default="127.0.0.1:9000",
                   help="server admin endpoint host:port")
    d.add_argument("--pool", type=int, default=None,
                   help="pool index to decommission")
    d.add_argument("--status", action="store_true",
                   help="print rebalance/topology status and exit")
    d.add_argument("--cancel", action="store_true",
                   help="cancel the running drain (pool returns to "
                   "active)")
    d.add_argument("--region", default=os.environ.get(
        "MINIO_REGION", "us-east-1"))

    t = sub.add_parser("tier", help="manage remote tiers for ILM "
                       "transitions (mc admin tier surface)")
    t.add_argument("action", choices=("add", "ls", "rm", "stats"))
    t.add_argument("--url", default="127.0.0.1:9000",
                   help="server admin endpoint host:port")
    t.add_argument("--name", default="",
                   help="tier name (add/rm)")
    t.add_argument("--type", default="fs", dest="tier_type",
                   choices=("fs", "s3", "azure", "gcs", "hdfs"),
                   help="tier backend type (add)")
    t.add_argument("--param", action="append", default=[],
                   help="backend param key=value (repeatable): fs needs "
                   "path=...; s3 needs host=, bucket= (+port/access_key/"
                   "secret_key/prefix/region)")
    t.add_argument("--force", action="store_true",
                   help="add: update an existing tier in place; "
                   "rm: remove even when lifecycle rules reference it")
    t.add_argument("--region", default=os.environ.get(
        "MINIO_REGION", "us-east-1"))

    q = sub.add_parser("qos", help="manage per-tenant/per-tier QoS "
                       "budgets (admission shares, request/byte rates)")
    q.add_argument("action", choices=("get", "set", "rm"))
    q.add_argument("--url", default="127.0.0.1:9000",
                   help="server admin endpoint host:port")
    q.add_argument("--scope", default="tenant",
                   choices=("tenant", "tier"),
                   help="budget scope (set/rm)")
    q.add_argument("--name", default="",
                   help="tenant account or tier name (set/rm)")
    q.add_argument("--share", type=float, default=0.0,
                   help="admission-share weight (0 = default)")
    q.add_argument("--rps", type=float, default=0.0,
                   help="request-rate budget, req/s (0 = unlimited)")
    q.add_argument("--rx-bps", type=float, default=0.0,
                   help="request-body byte budget, bytes/s "
                   "(0 = unlimited)")
    q.add_argument("--tx-bps", type=float, default=0.0,
                   help="response/push byte budget, bytes/s "
                   "(0 = unlimited)")
    q.add_argument("--region", default=os.environ.get(
        "MINIO_REGION", "us-east-1"))

    n = sub.add_parser("notify", help="manage bucket event "
                       "notification targets (webhook/queue/log)")
    n.add_argument("action", choices=("status", "add", "rm"))
    n.add_argument("--url", default="127.0.0.1:9000",
                   help="server admin endpoint host:port")
    n.add_argument("--type", default="webhook",
                   choices=("webhook", "queue", "log"),
                   help="target type (add)")
    n.add_argument("--name", default="",
                   help="ARN id segment (add; random when empty)")
    n.add_argument("--arn", default="",
                   help="target ARN (rm, or add --force to update)")
    n.add_argument("--endpoint", default="",
                   help="webhook POST URL (add --type webhook)")
    n.add_argument("--auth-token", default="",
                   help="webhook bearer token (add --type webhook)")
    n.add_argument("--timeout", type=float, default=0.0,
                   help="webhook send timeout, seconds (0 = default)")
    n.add_argument("--path", default="",
                   help="event log file (add --type log)")
    n.add_argument("--force", action="store_true",
                   help="add: update an existing target in place "
                   "(needs --arn)")
    n.add_argument("--region", default=os.environ.get(
        "MINIO_REGION", "us-east-1"))

    f = sub.add_parser("fsck", help="run the crash-consistency "
                       "auditor against a running node")
    f.add_argument("--url", default="127.0.0.1:9000",
                   help="server admin endpoint host:port")
    f.add_argument("--repair", action="store_true",
                   help="repair repairable findings (POST mode)")
    f.add_argument("--bucket", default="",
                   help="narrow the audit to one bucket")
    f.add_argument("--tmp-age", type=float, default=None,
                   help="staged tmp older than this (seconds) counts "
                   "as a crash leftover; 0 = reap all (quiesced only)")
    f.add_argument("--region", default=os.environ.get(
        "MINIO_REGION", "us-east-1"))

    i = sub.add_parser("incidents", help="list black-box capture "
                       "bundles from a running node (or fetch one "
                       "with --id)")
    i.add_argument("--url", default="127.0.0.1:9000",
                   help="server admin endpoint host:port")
    i.add_argument("--id", default="",
                   help="fetch one full bundle by incident id")
    i.add_argument("--cluster", action="store_true",
                   help="merge every peer's bundle list")
    i.add_argument("--region", default=os.environ.get(
        "MINIO_REGION", "us-east-1"))

    g = sub.add_parser("gateway", help="serve the S3 API over a "
                       "foreign backend (cmd/gateway-main.go)")
    g.add_argument("kind", choices=("nas", "s3", "azure", "gcs",
                                    "hdfs"))
    g.add_argument("target", nargs="?", default="",
                   help="nas: /mount/path; s3: host:port; "
                   "azure: blob endpoint host:port; gcs: endpoint "
                   "host:port (default storage.googleapis.com); "
                   "hdfs: namenode host:port")
    g.add_argument("--address", default=":9000")
    g.add_argument("--region", default=os.environ.get(
        "MINIO_REGION", "us-east-1"))
    return p.parse_args(argv)


def _creds() -> Credentials:
    ak = os.environ.get("MINIO_ACCESS_KEY") or \
        os.environ.get("MINIO_ROOT_USER")
    sk = os.environ.get("MINIO_SECRET_KEY") or \
        os.environ.get("MINIO_ROOT_PASSWORD")
    if ak and sk:
        return Credentials(access_key=ak, secret_key=sk)
    return global_credentials()


def _run_gateway(args, creds: Credentials) -> int:
    """`minio_tpu gateway <kind> <target>` — serve the full S3 surface
    over a foreign backend (reference cmd/gateway-main.go). Backend
    credentials come from MINIO_GATEWAY_{ACCESS,SECRET}_KEY (s3) or
    MINIO_AZURE_{ACCOUNT,KEY} (azure)."""
    from .gateway import new_gateway
    from .s3.server import S3Server
    from .utils import host_port

    if args.kind == "nas":
        if not args.target:
            print("gateway nas needs a mount path", file=sys.stderr)
            return 2
        layer = new_gateway("nas", path=args.target)
    elif args.kind == "s3":
        if not args.target:
            # no silent default: 127.0.0.1:9000 would be the gateway's
            # own listen address — a self-proxying loop
            print("gateway s3 needs an upstream host:port",
                  file=sys.stderr)
            return 2
        h, p = host_port(args.target, 9000)
        layer = new_gateway(
            "s3", host=h, port=p,
            access_key=os.environ.get("MINIO_GATEWAY_ACCESS_KEY",
                                      creds.access_key),
            secret_key=os.environ.get("MINIO_GATEWAY_SECRET_KEY",
                                      creds.secret_key),
            region=args.region)
    elif args.kind == "azure":
        account = os.environ.get("MINIO_AZURE_ACCOUNT", "")
        key = os.environ.get("MINIO_AZURE_KEY", "")
        if not account or not key:
            print("gateway azure needs MINIO_AZURE_ACCOUNT and "
                  "MINIO_AZURE_KEY", file=sys.stderr)
            return 2
        h, p = host_port(args.target or f"{account}.blob.core."
                         "windows.net:443", 443)
        layer = new_gateway("azure", account=account, key_b64=key,
                            host=h, port=p, secure=(p == 443))
    elif args.kind == "gcs":
        # JSON API (the reference's mode): a service-account key file
        # via GOOGLE_APPLICATION_CREDENTIALS / MINIO_GCS_CREDENTIALS.
        # XML interop fallback: HMAC keys.
        sa = os.environ.get("MINIO_GCS_CREDENTIALS", "") or \
            os.environ.get("GOOGLE_APPLICATION_CREDENTIALS", "")
        ak = os.environ.get("MINIO_GCS_ACCESS_KEY", "")
        sk = os.environ.get("MINIO_GCS_SECRET_KEY", "")
        h, p = host_port(args.target or "storage.googleapis.com:443",
                         443)
        if sa:
            layer = new_gateway(
                "gcs", credentials_json=sa,
                project=os.environ.get("MINIO_GCS_PROJECT", ""),
                host=h, port=p, secure=(p == 443))
        elif ak and sk:
            layer = new_gateway("gcs", access_key=ak, secret_key=sk,
                                host=h, port=p, secure=(p == 443))
        else:
            print("gateway gcs needs GOOGLE_APPLICATION_CREDENTIALS/"
                  "MINIO_GCS_CREDENTIALS (JSON API) or "
                  "MINIO_GCS_ACCESS_KEY + MINIO_GCS_SECRET_KEY "
                  "(HMAC interop)", file=sys.stderr)
            return 2
    else:
        if not args.target:
            print("gateway hdfs needs a namenode host:port",
                  file=sys.stderr)
            return 2
        h, p = host_port(args.target, 9870)
        layer = new_gateway("hdfs", host=h, port=p)

    lh, lp = host_port(args.address, 9000)
    srv = S3Server(layer, creds=creds, region=args.region,
                   address=lh or "0.0.0.0", port=lp).start()
    print(f"MinIO-TPU {args.kind} gateway up at "
          f"http://{lh or '127.0.0.1'}:{srv.port} "
          f"(access key {creds.access_key})")

    def cleanup():
        srv.stop()
        layer.close()

    return _serve_until_signal(cleanup)


def _serve_until_signal(cleanup) -> int:
    """Block until SIGTERM/SIGINT, then run cleanup (Event.wait is
    signal-safe: no lost-wakeup window)."""
    import threading
    stop = threading.Event()
    signal.signal(signal.SIGTERM, lambda *a: stop.set())
    signal.signal(signal.SIGINT, lambda *a: stop.set())
    try:
        stop.wait()
    finally:
        cleanup()
    return 0


def _run_decommission(args, creds: Credentials) -> int:
    """`minio_tpu decommission` — drive the admin rebalance surface
    (start / --status / --cancel) against a running node."""
    import json as _json
    from .madmin import AdminClient, AdminClientError
    from .utils import host_port
    h, p = host_port(args.url, 9000)
    cli = AdminClient(h, p, creds.access_key, creds.secret_key,
                      region=args.region)
    try:
        if args.status:
            out = cli.rebalance_status()
        elif args.cancel:
            out = cli.cancel_rebalance()
        elif args.pool is None:
            print("decommission needs --pool N (or --status/--cancel)",
                  file=sys.stderr)
            return 2
        else:
            out = cli.start_rebalance(args.pool)
    except AdminClientError as e:
        print(f"decommission failed: {e}", file=sys.stderr)
        return 1
    print(_json.dumps(out, indent=2, sort_keys=True))
    return 0


def _run_tier(args, creds: Credentials) -> int:
    """`minio_tpu tier add|ls|rm|stats` — drive the admin tier registry
    against a running node."""
    import json as _json
    from .madmin import AdminClient, AdminClientError
    from .utils import host_port
    h, p = host_port(args.url, 9000)
    cli = AdminClient(h, p, creds.access_key, creds.secret_key,
                      region=args.region)
    try:
        if args.action == "ls":
            out = cli.list_tiers()
        elif args.action == "stats":
            out = cli.tier_stats()
        elif args.action == "rm":
            if not args.name:
                print("tier rm needs --name", file=sys.stderr)
                return 2
            out = cli.remove_tier(args.name, force=args.force)
        else:
            if not args.name:
                print("tier add needs --name", file=sys.stderr)
                return 2
            params = {}
            for kv in args.param:
                k, sep, v = kv.partition("=")
                if not sep:
                    print(f"bad --param {kv!r}: need key=value",
                          file=sys.stderr)
                    return 2
                params[k] = v
            out = cli.add_tier(args.name, args.tier_type,
                               update=args.force, **params)
    except AdminClientError as e:
        print(f"tier {args.action} failed: {e}", file=sys.stderr)
        return 1
    print(_json.dumps(out, indent=2, sort_keys=True))
    return 0


def _run_qos(args, creds: Credentials) -> int:
    """`minio_tpu qos get|set|rm` — drive the admin QoS budget
    registry against a running node."""
    import json as _json
    from .madmin import AdminClient, AdminClientError
    from .utils import host_port
    h, p = host_port(args.url, 9000)
    cli = AdminClient(h, p, creds.access_key, creds.secret_key,
                      region=args.region)
    try:
        if args.action == "get":
            out = cli.qos_get()
        elif args.action == "rm":
            if not args.name:
                print("qos rm needs --name", file=sys.stderr)
                return 2
            out = cli.qos_remove(args.name, scope=args.scope)
        else:
            if not args.name:
                print("qos set needs --name", file=sys.stderr)
                return 2
            out = cli.qos_set(args.name, scope=args.scope,
                              share=args.share, rps=args.rps,
                              rx_bps=args.rx_bps, tx_bps=args.tx_bps)
    except AdminClientError as e:
        print(f"qos {args.action} failed: {e}", file=sys.stderr)
        return 1
    print(_json.dumps(out, indent=2, sort_keys=True))
    return 0


def _run_notify(args, creds: Credentials) -> int:
    """`minio_tpu notify status|add|rm` — drive the admin
    notification-target registry against a running node."""
    import json as _json
    from .madmin import AdminClient, AdminClientError
    from .utils import host_port
    h, p = host_port(args.url, 9000)
    cli = AdminClient(h, p, creds.access_key, creds.secret_key,
                      region=args.region)
    try:
        if args.action == "status":
            out = cli.notify_status()
        elif args.action == "rm":
            if not args.arn:
                print("notify rm needs --arn", file=sys.stderr)
                return 2
            cli.remove_notify_target(args.arn)
            out = {"removed": args.arn}
        else:
            params = {}
            if args.endpoint:
                params["endpoint"] = args.endpoint
            if args.auth_token:
                params["auth_token"] = args.auth_token
            if args.timeout:
                params["timeout"] = args.timeout
            if args.path:
                params["path"] = args.path
            arn = cli.add_notify_target(
                type=args.type, name=args.name, arn=args.arn,
                update=args.force, **params)
            out = {"arn": arn}
    except AdminClientError as e:
        print(f"notify {args.action} failed: {e}", file=sys.stderr)
        return 1
    print(_json.dumps(out, indent=2, sort_keys=True))
    return 0


def _run_fsck(args, creds: Credentials) -> int:
    """`minio_tpu fsck` — drive the admin consistency auditor. Exit 0
    when the tree is clean (or everything repairable was repaired),
    1 when unrepaired findings remain."""
    import json as _json
    from .madmin import AdminClient, AdminClientError
    from .utils import host_port
    h, p = host_port(args.url, 9000)
    cli = AdminClient(h, p, creds.access_key, creds.secret_key,
                      region=args.region)
    try:
        out = cli.fsck(repair=args.repair, bucket=args.bucket,
                       tmp_age_s=args.tmp_age)
    except AdminClientError as e:
        print(f"fsck failed: {e}", file=sys.stderr)
        return 1
    print(_json.dumps(out, indent=2, sort_keys=True))
    return 0 if out.get("unrepaired", 0) == 0 else 1


def _run_incidents(args, creds: Credentials) -> int:
    """`minio_tpu incidents` — list capture bundles (or fetch one
    with --id); the black box's readback."""
    import json as _json
    from .madmin import AdminClient, AdminClientError
    from .utils import host_port
    h, p = host_port(args.url, 9000)
    cli = AdminClient(h, p, creds.access_key, creds.secret_key,
                      region=args.region)
    try:
        out = cli.incident(args.id) if args.id \
            else {"incidents": cli.incidents(cluster=args.cluster)}
    except AdminClientError as e:
        print(f"incidents failed: {e}", file=sys.stderr)
        return 1
    print(_json.dumps(out, indent=2, sort_keys=True))
    return 0


def main(argv: list[str] | None = None) -> int:
    args = _parse(argv if argv is not None else sys.argv[1:])
    creds = _creds()
    if args.cmd == "gateway":
        return _run_gateway(args, creds)
    if args.cmd == "fsck":
        return _run_fsck(args, creds)
    if args.cmd == "incidents":
        return _run_incidents(args, creds)
    if args.cmd == "decommission":
        return _run_decommission(args, creds)
    if args.cmd == "tier":
        return _run_tier(args, creds)
    if args.cmd == "qos":
        return _run_qos(args, creds)
    if args.cmd == "notify":
        return _run_notify(args, creds)
    kw = dict(parity=args.parity, set_drive_count=args.set_drive_count,
              region=args.region,
              certfile=args.cert or None, keyfile=args.key or None)

    if args.node:
        if args.this < 0 or args.this >= len(args.node):
            print("--this must index the --node list", file=sys.stderr)
            return 2
        nodes = [parse_node_arg(n) for n in args.node]
        node = start_node(nodes, args.this, creds, **kw)
    else:
        if not args.drives:
            print("no drives given", file=sys.stderr)
            return 2
        host, sep, port = args.address.rpartition(":")
        if not sep:
            host, port = args.address, ""
        try:
            port_n = int(port) if port else 9000
        except ValueError:
            print(f"bad --address {args.address!r}: port must be a "
                  "number (host:port)", file=sys.stderr)
            return 2
        from .utils import ellipses as _ell
        expanded = _ell.expand_args(args.drives)
        if len(expanded) == 1:
            # one path: FS backend, no erasure (reference newObjectLayer)
            if args.pool:
                print("--pool needs an erasure backend; the FS "
                      "backend has no pool topology", file=sys.stderr)
                return 2
            from .cluster import start_fs
            node = start_fs(expanded[0], host or "0.0.0.0", port_n,
                            creds, region=args.region)
            print(f"MinIO-TPU FS node up at {node.url} "
                  f"(access key {creds.access_key})")
            return _serve_until_signal(node.shutdown)
        node = start_single(args.drives, host or "0.0.0.0", port_n,
                            creds, **kw)

    for pool_arg in getattr(args, "pool", []) or []:
        if args.node:
            print("--pool expansion is single-node only; distributed "
                  "pools join via their own --node lists",
                  file=sys.stderr)
            node.shutdown()
            return 2
        node.add_pool([pool_arg])

    info = node.object_layer.storage_info()
    print(f"MinIO-TPU node {node.spec.addr} up: "
          f"{node.set_count} set(s) x {node.set_drive_count} drives, "
          f"EC:{node.parity}; {info['online_disks']} online / "
          f"{info['offline_disks']} offline drives")
    print(f"S3 endpoint: {node.url}  (access key {creds.access_key})")
    print(data_path_line(), flush=True)
    return _serve_until_signal(node.shutdown)


if __name__ == "__main__":
    sys.exit(main())
