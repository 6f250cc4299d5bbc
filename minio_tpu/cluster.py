"""Cluster assembly — boot a node into a runnable (multi-)node system.

The reference's serverMain (cmd/server-main.go:371-533): parse endpoints,
mount the internode RPC routers (storage/lock/peer/bootstrap) on the same
HTTP server that serves S3, verify cluster config against peers, assemble
the ObjectLayer from local + remote drives (waitForFormatErasure), swap
the namespace lock for dsync when distributed, and start the S3 API.

A node's own drives are local XLStorage objects (also exported over
storage RPC for peers); every other node's drives are RemoteStorage
clients. The drive order is the endpoint order, identical on every node,
so each drive occupies the same erasure-set slot cluster-wide.
"""

from __future__ import annotations

import dataclasses
import os
import threading
import time
from typing import Optional

from .distributed import membership
from .distributed.local_locker import LocalLocker
from .distributed.lock_rpc import LockRPCClient, LockRPCServer
from .distributed.peer_rpc import (BootstrapRPCServer, NotificationSys,
                                   PeerRPCClient, PeerRPCServer,
                                   verify_server_system_config)
from .distributed.storage_rpc import RemoteStorage, StorageRPCServer
from .distributed.dsync import DistNSLockMap
from .object.nslock import NSLockMap
from .object.sets import ErasureSets
from .object.server_sets import ErasureServerSets
from .s3.credentials import Credentials
from .s3.server import S3Server
from .storage import errors as serr
from .storage.xl_storage import XLStorage
from .parallel import ladder
from .utils import device, ellipses, knobs, telemetry


@dataclasses.dataclass
class NodeSpec:
    """One node: where it listens and which drive paths it owns."""
    host: str
    port: int
    drives: list[str]

    @property
    def addr(self) -> str:
        return f"{self.host}:{self.port}"


def parse_node_arg(arg: str) -> NodeSpec:
    """"host:port=/d{1...4}" or "host:port=/a,/b" -> NodeSpec."""
    addr, _, paths = arg.partition("=")
    if not paths:
        raise ValueError(f"node arg needs host:port=drives, got {arg!r}")
    host, _, port = addr.rpartition(":")
    drives = []
    for p in paths.split(","):
        drives.extend(ellipses.expand_arg(p))
    return NodeSpec(host or "127.0.0.1", int(port), drives)


class ClusterNode:
    """One running node: S3 endpoint + internode RPC + object layer."""

    def __init__(self, nodes: list[NodeSpec], this: int,
                 creds: Credentials, parity: Optional[int] = None,
                 set_drive_count: int = 0, block_size: int = 1 << 22,
                 region: str = "us-east-1", iam=None,
                 bootstrap_timeout: float = 30.0,
                 format_timeout: float = 30.0,
                 certfile: Optional[str] = None,
                 keyfile: Optional[str] = None):
        self._tls = (certfile, keyfile)
        self.nodes = nodes
        self.this = this
        self.creds = creds
        self.spec = nodes[this]
        self.distributed = len(nodes) > 1
        # partition-tolerance plane identity: this process speaks as
        # spec.addr; every RPC carries it + the boot generation so
        # peers can fence stale per-peer state after a restart.
        # (In-process multi-node tests boot several ClusterNodes per
        # process — their handlers/clients carry explicit node_ids
        # below, which win over this process-level fallback.)
        membership.set_local_node(self.spec.addr)

        all_drives = [(ni, path) for ni, n in enumerate(nodes)
                      for path in n.drives]
        total = len(all_drives)
        node_counts = [len(n.drives) for n in nodes]
        if set_drive_count:
            if total % set_drive_count:
                raise ValueError("drives not divisible into sets")
            set_count = total // set_drive_count
        else:
            set_count, set_drive_count = ellipses.divide_into_sets(
                total, node_counts)
        if parity is None:
            parity = set_drive_count // 2   # reference default EC:N/2
        self.set_count, self.set_drive_count = set_count, set_drive_count
        self.parity = parity

        # -- local drives + RPC servers on this node's listener ------------
        self.local_drives: dict[str, XLStorage] = {}
        for path in self.spec.drives:
            try:
                self.local_drives[path] = XLStorage(path)
            except serr.StorageError:
                pass
        self.locker = LocalLocker()
        ak, sk = creds.access_key, creds.secret_key
        self._storage_rpc = StorageRPCServer(self.local_drives, ak, sk)
        self._storage_rpc.handler.node_id = self.spec.addr
        self._lock_rpc = LockRPCServer(self.locker, ak, sk)
        self._lock_rpc.handler.node_id = self.spec.addr
        self._peer_rpc = PeerRPCServer(ak, sk, node_id=self.spec.addr)
        endpoints = [f"{n.addr}{p}" for n in nodes for p in n.drives]
        self._bootstrap_rpc = BootstrapRPCServer(ak, sk, endpoints)
        self._bootstrap_rpc.handler.node_id = self.spec.addr

        # the S3 server carries every router (reference configureServerHandler)
        self.s3: Optional[S3Server] = None
        self.sets = None
        self._remote_clients: list[RemoteStorage] = []
        self._lock_clients: list[LockRPCClient] = []
        self._peer_clients: list[PeerRPCClient] = []
        self._start_server(region, iam)
        try:
            with telemetry.trace("node.boot", node=self.spec.addr):
                self._finish_boot(nodes, this, all_drives, endpoints,
                                  ak, sk, set_count, set_drive_count,
                                  parity, block_size, bootstrap_timeout,
                                  format_timeout)
        except BaseException:
            # a failed boot must not leak the already-listening server /
            # RPC clients into the process (shutdown is idempotent and
            # tolerant of the partially-built state)
            self.shutdown()
            raise

    def _finish_boot(self, nodes, this, all_drives, endpoints, ak, sk,
                     set_count, set_drive_count, parity, block_size,
                     bootstrap_timeout, format_timeout) -> None:
        # -- bootstrap verify against peers --------------------------------
        peers = [(n.host, n.port) for i, n in enumerate(nodes)
                 if i != this]
        if peers:
            verify_server_system_config(
                peers, endpoints, ak, sk,
                retries=max(int(bootstrap_timeout), 1))

        # -- assemble the drive list in global endpoint order --------------
        drives: list = []
        for ni, path in all_drives:
            if ni == this:
                drives.append(self.local_drives.get(path))
            else:
                rc = RemoteStorage(nodes[ni].host, nodes[ni].port, path,
                                   ak, sk)
                rc.rc.node_id = self.spec.addr
                self._remote_clients.append(rc)
                drives.append(rc)

        # -- namespace lock: dsync across every node when distributed ------
        if self.distributed:
            lockers: list = []
            for i, n in enumerate(nodes):
                if i == this:
                    lockers.append(self.locker)
                else:
                    lc = LockRPCClient(n.host, n.port, ak, sk)
                    lc.rc.node_id = self.spec.addr
                    self._lock_clients.append(lc)
                    lockers.append(lc)
            ns_lock = DistNSLockMap(lockers, owner=self.spec.addr)
        else:
            ns_lock = NSLockMap()

        # -- cross-request device batch former + RAM-budgeted admission ----
        # the data path is decided HERE, once, before the first jit
        # (and the compile cache placed with it): every later routing
        # and kernel-flavour choice reads this probe
        device.probe()
        from .parallel import pipeline as _pipeline
        from .parallel.scheduler import BatchScheduler, requests_budget
        self.scheduler = BatchScheduler()
        budget = requests_budget(block_size, set_drive_count)
        self.s3.api.set_max_clients(budget)
        # staging rings sized from the SAME admission budget (each
        # admitted stream keeps ~2 batches in flight), not a flat
        # 2×cores guess
        _pipeline.configure_pool_buffers(budget)

        # -- format bootstrap (waitForFormatErasure) -----------------------
        deadline = time.monotonic() + format_timeout
        while True:
            try:
                sets = ErasureSets.from_storage(
                    drives, set_count, set_drive_count, parity,
                    block_size=block_size, ns_lock=ns_lock,
                    create_format=(this == 0),
                    scheduler=self.scheduler)
                break
            except serr.StorageError:
                if time.monotonic() >= deadline:
                    raise
                time.sleep(0.5)
        self.sets = sets
        # the geometry is declared: on a TPU, load every encode rung of
        # the launch ladder now — the object layer is set (and the
        # listener answers its first request) only when they are in.
        # A CPU host loads nothing. Decode rungs wait for a drive to
        # be found missing (ROADMAP A5).
        eng = sets.sets[0]
        ladder.load_encode(
            eng.codec(eng.data_shards, eng.parity_shards),
            eng.bitrot_algo, self.scheduler.max_batch)
        # distributed clusters are single-pool (expansion/decommission
        # are the single-node surface today): skip the boot-time
        # cluster-wide topology read — during a concurrent multi-node
        # boot it races peers still formatting and can trip a remote
        # drive's offline backoff for nothing (the default map is
        # all-active, which is exactly a 1-pool cluster's only state)
        self.object_layer = ErasureServerSets(
            [sets], load_topology=not self.distributed)
        self.s3.api.set_object_layer(self.object_layer)
        self._block_size = block_size
        # a drain interrupted by a restart resumes from its persisted
        # checkpoint (the pool is still marked draining in the topology
        # epoch doc) instead of starting over
        try:
            self.object_layer.resume_rebalance_if_pending()
        except Exception:  # noqa: BLE001 — boot must proceed; the
            # admin rebalance endpoint can restart the drain manually
            pass

        # -- IAM over the object layer (erasure-coded identity store) ------
        if self.s3.api.iam is None:
            from .iam import IAMSys
            self.s3.api.iam = IAMSys(self.object_layer,
                                     root_cred=self.creds)
        self.iam = self.s3.api.iam
        self.iam.bucket_policy_lookup = \
            lambda b: self.s3.api.bucket_meta.get(b).policy_json

        # -- peer control plane hooks --------------------------------------
        self._peer_clients = [PeerRPCClient(n.host, n.port, ak, sk,
                                            node_id=self.spec.addr)
                              for i, n in enumerate(nodes) if i != this]
        self.notification = NotificationSys(self._peer_clients)
        # generation fencing, cluster edition: a peer that restarted
        # (new boot generation) invalidated every grant/subscription it
        # held for us — transport already clears its healthtrack windows
        # and offline marker (import-time listener); here the cluster
        # drops cached replication wire clients so the next replication
        # op reconnects instead of riding a dead session
        def _on_peer_restart(peer: str, _old: int, _new: int) -> None:
            if self.s3 is None:        # shut down: stale listener, no-op
                return
            targets = getattr(self, "repl_targets", None)
            if targets is not None:
                with targets._mu:
                    targets._clients.clear()
            try:
                self.console.log_line(
                    "INFO", f"peer {peer} restarted (new generation); "
                    "stale per-peer state reset")
            except Exception:  # noqa: BLE001 — console not up yet
                pass

        membership.TRACKER.add_listener(_on_peer_restart)
        self._peer_rpc.get_locks = self.locker.dump
        self._peer_rpc.get_server_info = lambda: {
            "addr": self.spec.addr,
            "sets": self.set_count,
            "drives_per_set": self.set_drive_count,
        }
        self._peer_rpc.reload_bucket_metadata = \
            lambda b: self.s3.api.bucket_meta.reload(b)
        self.s3.api.bucket_meta.on_change = \
            lambda b: self.notification.reload_bucket_metadata(b)
        self._peer_rpc.reload_iam = self.iam.load
        self._peer_rpc.apply_iam_delta = self.iam.apply_delta
        self.iam.on_change = self.notification.reload_iam
        self.iam.on_delta = self.notification.iam_delta
        # bounded staleness: a delta lost to a transient partition (the
        # sender's per-peer reload fallback failing too) must not
        # diverge this node forever — refresh the whole cache on an
        # interval like the reference's IAM refresh loop
        refresh_s = knobs.get_float("MINIO_TPU_IAM_REFRESH_S")
        self._iam_refresh_stop = threading.Event()

        def _iam_refresh_loop():
            while not self._iam_refresh_stop.wait(refresh_s):
                try:
                    self.iam.load()
                except Exception:  # noqa: BLE001 — retry next tick
                    pass

        threading.Thread(target=_iam_refresh_loop, daemon=True).start()
        self._peer_rpc.get_storage_info = self.object_layer.storage_info
        self._peer_rpc.get_trace = \
            lambda: list(self.s3.api.trace.recent)
        self._peer_rpc.get_bucket_usage = \
            lambda: (self.crawler.usage
                     if getattr(self, "crawler", None) is not None
                     else {})
        self._peer_rpc.obd_drive_paths = list(self.spec.drives)
        self._peer_rpc.get_bandwidth = \
            lambda: self.s3.api.bandwidth.report()
        # console-log ring: name this node's singleton so merged
        # cluster logs attribute lines to their origin
        from .utils.console import get_console
        self.console = get_console()
        self.console.node = self.spec.addr
        self.console.log_line("INFO", f"node {self.spec.addr} online")

        # -- admin / health / metrics routers ------------------------------
        from .s3.admin import mount_admin
        self.admin = mount_admin(self.s3, self)
        # cluster observability plane: trace records carry this node's
        # name, peers pull the full Prometheus exposition for the
        # federated ?cluster=1 scrape, and follow-mode trace streams
        # subscribe to this node's live hub over the trace-stream verb
        self.s3.api.trace.node = self.spec.addr
        self._peer_rpc.get_metrics_text = self.admin.metrics.local_text
        self._peer_rpc.trace_hub = self.s3.api.trace.hub

        # -- incident plane: event journal, SLO engine, flight recorder ----
        # the journal persists under the first local drive (like the
        # event-notifier backlog) so transitions survive a restart;
        # the flight recorder subscribes to it and snapshots
        # postmortem state on trigger events
        from .distributed import membership as _membership
        from .utils import eventlog, healthtrack, incidents, slo
        if self.spec.drives:
            eventlog.JOURNAL.attach(
                os.path.join(self.spec.drives[0], ".minio.sys",
                             "eventlog"),
                node=self.spec.addr)
            incidents.RECORDER.attach(
                os.path.join(self.spec.drives[0], ".minio.sys",
                             "incidents"))
        if device.probe().reason:
            # a CPU-only host is a supported deployment — it just says
            # so, once, in the journal as on the boot banner
            eventlog.emit_once("device.decline", stage="boot",
                               reason="no-device",
                               detail=device.probe().reason)
        if knobs.get_bool("MINIO_TPU_SLO"):
            slo.ENGINE.ensure_started()
        incidents.RECORDER.add_provider(
            "healthtrack", lambda: {
                "drives": healthtrack.TRACKER.snapshot("drive"),
                "peers": healthtrack.TRACKER.snapshot("peer")})
        incidents.RECORDER.add_provider(
            "membership", _membership.TRACKER.snapshot)
        incidents.RECORDER.add_provider("slo", slo.ENGINE.status)
        incidents.RECORDER.add_provider(
            "topology",
            lambda: self.object_layer.topology.to_dict()
            if getattr(self.object_layer, "topology", None) is not None
            else {})
        self._peer_rpc.event_hub = eventlog.JOURNAL.hub
        self._peer_rpc.get_events = \
            lambda: eventlog.JOURNAL.recent(500)
        self._peer_rpc.list_incidents = incidents.RECORDER.list
        self._peer_rpc.get_incident = incidents.RECORDER.get

        # -- web JSON-RPC control surface (cmd/web-router.go) --------------
        from .s3.web import mount as mount_web
        self.web = mount_web(self.s3)

        # -- config KV (newAllSubsystems ConfigSys + lookupConfigs) --------
        from .config import ConfigSys
        self.config = ConfigSys(self.object_layer, secret=sk)
        self.s3.api.config = self.config

        # -- bucket federation over etcd DNS (cmd/etcd.go) -----------------
        etcd_ep = self.config.get("etcd", "endpoints")
        fed_domain = self.config.get("etcd", "domain")
        if etcd_ep and fed_domain:
            from .distributed.etcd import EtcdClient
            from .features.federation import BucketFederation
            try:
                etcd_client = EtcdClient(etcd_ep.split(",")[0].strip())
                fed = BucketFederation(
                    etcd_client,
                    fed_domain, self.spec.host, self.spec.port,
                    cluster_addrs=[(n.host, n.port)
                                   for n in self.nodes])
                self.s3.api.federation = fed
                # reference initFederatorBackend: buckets that predate
                # federation (or an etcd restore) get re-registered
                fed.register_existing(self.object_layer)
                # etcd configured => IAM moves to the etcd store
                # (cmd/iam-etcd-store.go): users/policies/service
                # accounts created on ANY federated cluster are
                # visible to all of them; identities that predate etcd
                # are seeded into it on first switch
                from .iam.store import EtcdIAMStore
                self.iam.migrate_to_store(EtcdIAMStore(etcd_client))
            except ValueError:
                pass              # bad endpoint: federation stays off

        # -- live bucket features (events, replication, lifecycle) ---------
        from .features import EventNotifier
        from .features.lifecycle import (crawler_action, mpu_abort_action,
                                         noncurrent_sweep_action)
        # durable event backlog lives under the node's first local
        # drive (queuestore.go semantics: pending events survive a
        # process restart)
        _evq = os.path.join(self.spec.drives[0], ".minio.sys", "events") \
            if self.spec.drives else None
        self.events = EventNotifier(self.s3.api.bucket_meta,
                                    queue_dir=_evq)
        self.s3.api.events = self.events
        # active-active replication plane (minio_tpu/replicate/): the
        # epoch-versioned target registry recovers from every pool
        # (highest epoch wins — targets survive decommission), the
        # plane rides the engine namespace-change feed so EVERY
        # mutation verb reaches the replication queue
        from .replicate import ReplicationPlane, TargetRegistry
        self.repl_targets = TargetRegistry(self.object_layer)
        try:
            if not self.repl_targets.load():
                # first boot: persist the minted site id so replicas
                # pushed before and after a restart carry ONE origin
                self.repl_targets.save()
        except Exception:  # noqa: BLE001 — boot proceeds; admin re-adds
            pass
        self.replication = ReplicationPlane(self.object_layer,
                                            self.repl_targets,
                                            bucket_meta=self.s3.api.
                                            bucket_meta)
        self.replication.bandwidth = self.s3.api.bandwidth
        self.object_layer.attach_replication(self.replication)
        try:
            buckets = [v.name for v in self.object_layer.list_buckets()]
        except Exception as e:  # noqa: BLE001 — boot must proceed, but
            # an unlistable namespace leaves targets unmounted: say so
            self.console.log_line(
                "ERROR", f"replication target mount skipped: {e}")
            buckets = []
        # legacy bucket-metadata remote targets mount into the registry
        for b in buckets:
            try:
                for entry in self.s3.api.bucket_meta.get(
                        b).replication_targets:
                    entry = dict(entry, source_bucket=b)
                    self.replication.mount_target_entry(entry)
            except Exception:  # noqa: BLE001 — per-bucket best effort
                continue
        # service restart/stop: peers run the same local action the
        # admin endpoint runs — DEFERRED so the RPC reply reaches the
        # broadcaster before this process exec-restarts
        import threading as _threading
        self._peer_rpc.signal_service = \
            lambda sig: _threading.Timer(
                0.2, self.admin.service_action, (sig,)).start()
        self.s3.api.replication = self.replication
        # apply stored/env config to the live subsystems
        self.config.apply(self.s3.api, events=self.events,
                          trace=self.s3.api.trace)

        # -- bucket event notification plane (minio_tpu/notify/) -----------
        # same epoch-versioned every-pool registry rule as replication
        # targets; the plane rides the SAME namespace feed, so every
        # mutation verb reaches the delivery queue. Durable per-target
        # backlog lives beside the legacy event queue on the first
        # local drive (pending events survive a restart).
        from .notify import NotificationPlane, NotifyTargetRegistry
        self.notify_targets = NotifyTargetRegistry(self.object_layer)
        try:
            self.notify_targets.load()
        except Exception:  # noqa: BLE001 — boot proceeds; admin re-adds
            pass
        _nq = os.path.join(self.spec.drives[0], ".minio.sys", "notify",
                           "queue") if self.spec.drives else None
        self.notify_plane = NotificationPlane(
            self.object_layer, self.notify_targets,
            bucket_meta=self.s3.api.bucket_meta,
            queue_dir=_nq, node=self.spec.addr,
            nodes=[n.addr for n in nodes],
            site_id=self.repl_targets.site_id)
        # owner-node delivery: non-owners hand the event to the
        # bucket's owner over the peer control plane (no double-fire
        # on multi-node clusters); peers' registries reload on admin
        # target mutations so a target added at any node serves on all
        _npeers = {p.addr: p for p in self._peer_clients}
        self.notify_plane.forward_fn = \
            lambda addr, b, k: (addr in _npeers
                                and _npeers[addr].notify_event(b, k))
        self._peer_rpc.notify_event = self.notify_plane.ingest
        self._peer_rpc.notify_reload = self.notify_targets.load
        self.notify_plane.reload_peers = self.notification.notify_reload
        self.object_layer.attach_notifications(self.notify_plane)
        self.s3.api.notify = self.notify_plane

        # -- tiering plane (remote tiers + ILM transitions) ----------------
        from .tier.config import TierManager
        self.tiers = TierManager(self.object_layer)
        try:
            self.tiers.load()
        except Exception:  # noqa: BLE001 — boot proceeds; admin re-adds
            pass
        self.s3.api.tiers = self.tiers

        # -- QoS budget registry (s3/qos.py) -------------------------------
        # same every-pool persistence rule as tiers: recover the newest
        # budget doc; a missing/torn doc just means default budgets
        self.s3.api.qos.registry.obj = self.object_layer
        try:
            self.s3.api.qos.registry.load()
        except Exception:  # noqa: BLE001 — boot proceeds on defaults
            pass

        # -- boot-time crash-consistency audit (object/fsck.py) ------------
        # MINIO_TPU_FSCK_BOOT=on: audit every pool and repair what the
        # last crash left behind (tmp garbage, orphan data dirs, torn
        # registry copies) BEFORE the scanners/index start trusting the
        # tree; repairable findings run the same heal/delete verbs the
        # admin fsck endpoint uses
        if this == 0 and knobs.get_bool("MINIO_TPU_FSCK_BOOT"):
            from .object.fsck import run_fsck
            try:
                rep = run_fsck(self.object_layer, repair=True,
                               tiers=self.tiers)
                if not rep.clean:
                    self.console.log_line(
                        "INFO", f"boot fsck: found {rep.counts()}, "
                        f"repaired {rep.repaired_counts()}, "
                        f"unrepaired {len(rep.unrepaired)}")
            except Exception as e:  # noqa: BLE001 — boot must proceed;
                # the admin endpoint can rerun the audit on demand
                self.console.log_line("ERROR", f"boot fsck failed: {e}")

        # -- bucket metacache (persisted listing index + scanner feed) -----
        from .object.metacache import MetacacheManager
        from .object import metacache as _mc
        self.metacache = None
        if _mc.enabled() and not self.distributed:
            # single-node clusters only today: deltas are engine-local,
            # so writes through a PEER's S3 endpoint would never feed
            # this node's journal — distributed nodes keep the
            # merge-walk (README "Listing and the bucket metacache")
            self.metacache = MetacacheManager(self.object_layer).start()
            self.object_layer.attach_metacache(self.metacache)

        # -- device scan plane (TPU-offloaded S3 Select) -------------------
        # wire the handler's ScanEngine onto the shared batch former:
        # concurrent SelectObjectContent requests coalesce their pages
        # into single device launches (fourth verb of the scheduler);
        # same instance, so its serve/fallback stats stay continuous
        self.s3.api.scan.scheduler = self.scheduler

        # -- hot-object read cache in front of the erasure path ------------
        from .object import cache as _cache
        self.read_cache = None
        if _cache.enabled() and self.spec.drives:
            default_dir = os.path.join(self.spec.drives[0],
                                       ".minio.sys", "cache")
            self.read_cache = _cache.CacheObjects.from_env(
                self.object_layer, default_dir)
            # invalidation rides the namespace feed; the S3 surface
            # serves THROUGH the wrapper (GET/Select hits skip the
            # erasure decode path entirely); background planes keep
            # the raw layer — they must never populate the cache
            self.object_layer.attach_read_cache(self.read_cache)
            self.s3.api.set_object_layer(self.read_cache)

        # -- background plane (initAutoHeal + initDataCrawler) -------------
        from .object.background import (DataUsageCrawler, DiskMonitor,
                                        HealScanner)
        from .object.update_tracker import DataUpdateTracker
        self.disk_monitor = DiskMonitor(sets).start()
        # data-update tracker: every mutation marks the bloom; the heal
        # scanner prunes unchanged work (cmd/data-update-tracker.go)
        _tpath = os.path.join(self.spec.drives[0], ".minio.sys",
                              "tracker", "update-tracker.bin") \
            if self.spec.drives else ""
        self.update_tracker = DataUpdateTracker(_tpath)
        self.s3.api.update_tracker = self.update_tracker
        self._peer_rpc.get_update_tracker = \
            self.update_tracker.rotate_snapshot
        self.heal_scanner = None
        self.crawler = None
        self.transition_worker = None
        if this == 0:
            self.heal_scanner = HealScanner(
                self.object_layer, self.update_tracker,
                peer_snapshots=self.notification.tracker_rotate_all
            ).start()
            # one transition worker per cluster, riding the same
            # crawler cadence lifecycle expiry does: Transition rules
            # enqueue moves, the worker drains them throttled off
            # foreground pressure
            from .tier.transition import (TransitionWorker,
                                          noncurrent_transition_action,
                                          restore_reclaim_action,
                                          transition_action)
            self.transition_worker = TransitionWorker(
                self.object_layer, self.tiers)
            # per-tier push budgets come from the QoS registry's
            # "tier" scope (same doc shape the tenant budgets use)
            self.transition_worker.budget_lookup = \
                lambda name: self.s3.api.qos.registry.get("tier", name)
            self.transition_worker.start()
            # async RestoreObject (202 + background pull) rides the
            # same worker, throttled with the transitions
            self.s3.api.restore_worker = self.transition_worker
            # one crawler per cluster (first node), like the reference's
            # leader-ish crawler cadence; usage cache feeds quota and the
            # crawler enforces lifecycle expiry + ILM transitions
            self.crawler = DataUsageCrawler(
                self.object_layer,
                actions=[crawler_action(self.s3.api.bucket_meta,
                                        self.object_layer,
                                        self.events, tiers=self.tiers),
                         transition_action(self.s3.api.bucket_meta,
                                           self.transition_worker),
                         restore_reclaim_action(self.object_layer,
                                                self.tiers)],
                bucket_actions=[
                    mpu_abort_action(self.s3.api.bucket_meta,
                                     self.object_layer),
                    noncurrent_sweep_action(self.s3.api.bucket_meta,
                                            self.object_layer,
                                            tiers=self.tiers),
                    noncurrent_transition_action(
                        self.s3.api.bucket_meta,
                        self.transition_worker),
                ]).start()
            self.s3.api.usage = self.crawler

    # ------------------------------------------------------------------
    # topology: online pool expansion
    # ------------------------------------------------------------------

    def add_pool(self, drive_roots: list[str],
                 set_drive_count: int = 0,
                 parity: Optional[int] = None) -> int:
        """Append one pool of LOCAL drives to the running node (online
        expansion; single-node form of upstream's server-pool list).
        Bumps+persists the placement epoch; new writes immediately
        weigh the new capacity. Returns the new pool index."""
        paths = ellipses.expand_args(list(drive_roots))
        if set_drive_count:
            if len(paths) % set_drive_count:
                raise ValueError("drives not divisible into sets")
            set_count = len(paths) // set_drive_count
        else:
            set_count, set_drive_count = ellipses.divide_into_sets(
                len(paths), [len(paths)])
        if parity is None:
            parity = set_drive_count // 2
        sets = ErasureSets.from_drives(
            paths, set_count, set_drive_count, parity,
            block_size=self._block_size, scheduler=self.scheduler)
        idx = self.object_layer.add_pool(sets)
        # the running DiskMonitor must cover the new pool's drives too:
        # a drive dying in a post-boot pool re-admits/heals exactly like
        # a boot-time one (ROADMAP follow-up from the topology PR)
        if getattr(self, "disk_monitor", None) is not None:
            self.disk_monitor.add_pool(sets)
        for p in paths:
            if p not in self.local_drives:
                try:
                    self.local_drives[p] = XLStorage(p)
                except serr.StorageError:
                    pass
        self.console.log_line(
            "INFO", f"pool {idx} added ({len(paths)} drives, "
            f"epoch {self.object_layer.topology.epoch})")
        return idx

    # ------------------------------------------------------------------

    def _start_server(self, region: str, iam) -> None:
        certfile, keyfile = getattr(self, "_tls", (None, None))
        self.s3 = S3Server(None, address=self.spec.host,
                           port=self.spec.port, region=region,
                           creds=self.creds, iam=iam,
                           certfile=certfile, keyfile=keyfile)
        self.s3.register_router("/minio/storage/",
                                self._storage_rpc.route)
        self.s3.register_router("/minio/lock/", self._lock_rpc.route)
        self.s3.register_router("/minio/peer/", self._peer_rpc.route)
        self.s3.register_router("/minio/bootstrap/",
                                self._bootstrap_rpc.route)
        self.s3.start()

    @property
    def url(self) -> str:
        return self.s3.url

    def shutdown(self) -> None:
        """Idempotent; safe on a partially-booted node."""
        if getattr(self, "_iam_refresh_stop", None) is not None:
            self._iam_refresh_stop.set()
        # persist the journal tail (flush, not close: in-process test
        # clusters share the process-global journal across nodes)
        from .utils import eventlog
        try:
            eventlog.JOURNAL.flush()
        except Exception:  # noqa: BLE001 — best-effort on the way down
            pass
        if getattr(self, "disk_monitor", None) is not None:
            self.disk_monitor.close()
            self.disk_monitor = None
        if getattr(self, "crawler", None) is not None:
            self.crawler.close()
            self.crawler = None
        if getattr(self, "transition_worker", None) is not None:
            self.transition_worker.close()
            self.transition_worker = None
        if getattr(self, "heal_scanner", None) is not None:
            self.heal_scanner.close()
            self.heal_scanner = None
        if getattr(self, "metacache", None) is not None:
            self.metacache.close()
            self.metacache = None
        if getattr(self, "update_tracker", None) is not None:
            try:
                self.update_tracker.flush()
            except Exception:  # noqa: BLE001 — hints only
                pass
            self.update_tracker = None
        if getattr(self, "events", None) is not None:
            self.events.close()
            self.events = None
        if getattr(self, "replication", None) is not None:
            self.replication.close()
            self.replication = None
        if getattr(self, "notify_plane", None) is not None:
            self.notify_plane.close()
            self.notify_plane = None
        if getattr(self, "scheduler", None) is not None:
            self.scheduler.close()
            self.scheduler = None
        if self.s3 is not None:
            try:
                self.s3.stop()
            except Exception:  # noqa: BLE001 — already stopped
                pass
            self.s3 = None
        if self.sets is not None:
            self.sets.close()
            self.sets = None
        self._lock_rpc.close()
        for c in self._remote_clients:
            c.close()
        self._remote_clients = []
        for c in self._lock_clients:
            c.close()
        self._lock_clients = []
        for c in self._peer_clients:
            c.close()
        self._peer_clients = []


def start_node(nodes: list[NodeSpec], this: int, creds: Credentials,
               **kw) -> ClusterNode:
    """Boot node `this` of a cluster described by `nodes`."""
    return ClusterNode(nodes, this, creds, **kw)


def start_single(drives: list[str], address: str = "127.0.0.1",
                 port: int = 0, creds: Optional[Credentials] = None,
                 **kw) -> ClusterNode:
    """Single-node server over local drives (reference `minio server
    /data/d{1...16}`)."""
    from .s3.credentials import global_credentials
    creds = creds or global_credentials()
    paths = ellipses.expand_args(drives)
    spec = NodeSpec(address, port, paths)
    return ClusterNode([spec], 0, creds, **kw)


class FSNode:
    """Single-directory FS-backend server (reference newObjectLayer's
    one-endpoint branch, cmd/server-main.go:524-532): no erasure, plain
    file tree, full S3 surface."""

    def __init__(self, root: str, address: str = "127.0.0.1",
                 port: int = 0, creds: Optional[Credentials] = None,
                 region: str = "us-east-1"):
        from .object.fs import FSObjects
        from .s3.credentials import global_credentials
        from .s3.admin import mount_admin
        from .iam import IAMSys
        self.creds = creds or global_credentials()
        self.object_layer = FSObjects(root)
        iam = IAMSys(self.object_layer, root_cred=self.creds)
        self.s3 = S3Server(self.object_layer, address=address, port=port,
                           region=region, creds=self.creds, iam=iam)
        self.iam = iam
        iam.bucket_policy_lookup = \
            lambda b: self.s3.api.bucket_meta.get(b).policy_json
        mount_admin(self.s3)
        self.s3.start()

    @property
    def url(self) -> str:
        return self.s3.url

    def shutdown(self) -> None:
        self.s3.stop()


def start_fs(root: str, address: str = "127.0.0.1", port: int = 0,
             creds: Optional[Credentials] = None, **kw) -> FSNode:
    return FSNode(root, address, port, creds, **kw)
