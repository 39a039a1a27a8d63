"""`python -m minio_tpu.server` — boot a (possibly distributed) S3 server.

The analogue of the reference's serverMain (cmd/server-main.go:746):
run the boot self-tests (hard-fail on wrong math, like the reference's
erasure/bitrot self-tests at :799-803), bring up the grid mesh when the
topology spans nodes (initGlobalGrid, :882-889), quorum-verify
format.json, build pools/sets over local + remote drives, and serve the
S3 API.

Usage (single node):
    python -m minio_tpu.server --address 127.0.0.1:9000 /data/d{1...4}

Distributed (run the SAME command on every node; endpoints owned by
other nodes are reached over the grid on port+1000):
    python -m minio_tpu.server --address 127.0.0.1:9001 \\
        http://127.0.0.1:9001/data/n1/d{1...2} \\
        http://127.0.0.1:9002/data/n2/d{1...2}

Credentials come from MTPU_ROOT_USER / MTPU_ROOT_PASSWORD
(default minioadmin/minioadmin).
"""

from __future__ import annotations

import argparse
import os
import signal
import socket as socket_mod
import sys
import threading
import time

GRID_PORT_OFFSET = 1000
# Graceful stop: how long, in all, the background healers get to finish
# the object they are on before the stop is called unclean (below a
# supervisor's usual 90 s kill timeout, with the request drain after).
_QUIESCE_S = 45.0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="minio_tpu.server")
    ap.add_argument("--address", default="0.0.0.0:9000")
    ap.add_argument("--parity", type=int, default=None,
                    help="EC parity shards (default: by drive count)")
    ap.add_argument("--ec-backend", choices=["auto", "host", "tpu"],
                    default="auto",
                    help="where the GF(2^8) math runs (tpu = JAX device)")
    ap.add_argument("--set-size", type=int, default=None,
                    help="drives per erasure set (default: auto 2-16)")
    ap.add_argument("--boot-timeout", type=float, default=120.0,
                    help="seconds to wait for peer nodes at boot")
    ap.add_argument("--scanner-interval", type=float, default=60.0,
                    help="seconds between background scanner cycles "
                         "(0 disables the background thread)")
    ap.add_argument("--drive-timeout", type=float, default=10.0,
                    help="per-op drive deadline in seconds; a drive "
                         "tripping it repeatedly is circuit-broken "
                         "(0 disables the health wrapper)")
    ap.add_argument("--notify-webhook", default="",
                    help="webhook endpoint URL for bucket event "
                         "notifications (target id 'webhook')")
    ap.add_argument("--notify-mqtt", default="",
                    help="host:port/topic of an MQTT 3.1.1 broker for "
                         "event notifications (target id 'mqtt')")
    ap.add_argument("--notify-nats", default="",
                    help="host:port/subject of a NATS server for event "
                         "notifications (target id 'nats')")
    ap.add_argument("--notify-redis", default="",
                    help="host:port/listkey of a Redis server for event "
                         "notifications (target id 'redis')")
    ap.add_argument("--audit-webhook", default="",
                    help="webhook endpoint URL receiving one audit "
                         "record per completed request")
    ap.add_argument("--compression", action="store_true",
                    help="transparently compress eligible objects "
                         "(text-like extensions/content types)")
    ap.add_argument("--ftp-address", default="",
                    help="also serve the namespace over FTP at "
                         "host:port (reference: --ftp)")
    ap.add_argument("drives", nargs="+",
                    help="drive dirs or http://host:port/path endpoints; "
                         "`{1...N}` ellipses expand, and each ellipses "
                         "argument forms its own server pool")
    args = ap.parse_args(argv)

    from minio_tpu.object.erasure_object import ErasureSet
    from minio_tpu.object.pools import ServerPools
    from minio_tpu.object.sets import ErasureSets
    from minio_tpu.s3.server import Credentials, S3Server
    from minio_tpu.storage.local import LocalStorage, OfflineDisk
    from minio_tpu.storage.remote import RemoteStorage, StorageRPCService
    from minio_tpu.topology import ellipses, format as fmt_mod

    my_host, _, my_port_s = args.address.rpartition(":")
    my_host = my_host or "0.0.0.0"
    my_port = int(my_port_s)
    local_hosts = {"127.0.0.1", "localhost", "0.0.0.0", my_host,
                   socket_mod.gethostname()}

    def is_local(ep: ellipses.Endpoint) -> bool:
        return ep.host is None or (ep.port == my_port
                                   and ep.host in local_hosts)

    try:
        pool_specs = ellipses.parse_pools(args.drives)
        pool_eps = [[ellipses.parse_endpoint(s) for s in spec]
                    for spec in pool_specs]
    except ValueError as e:
        ap.error(str(e))

    all_eps = [ep for spec in pool_eps for ep in spec]
    remote_nodes = sorted({(ep.host, ep.port) for ep in all_eps
                           if not is_local(ep)})
    distributed = bool(remote_nodes)

    # Argument validation that must fail in THIS process, before any
    # worker fork: a bad flag erroring only inside a forked child
    # would leave a supervising parent waiting on nothing.
    for spec in pool_eps:
        try:
            ss = args.set_size or ellipses.choose_set_size(len(spec))
        except ValueError as e:
            ap.error(str(e))
        if len(spec) % ss:
            ap.error(f"{len(spec)} drives not divisible into sets "
                     f"of {ss}")
        if args.parity is not None and not 0 <= args.parity <= ss // 2:
            ap.error(f"--parity must be in [0, {ss // 2}] for "
                     f"{ss}-drive sets")

    # Where the GF(2^8) math runs is settled HERE, before any fork and
    # without importing JAX in this process. A chip belongs to one
    # process: a pre-forked fleet in which "every child does its own
    # detection" hands the chip to whichever worker initialises JAX
    # first and leaves the rest to crash or to serve some other codec
    # under the same label. So: `auto` asks a short-lived child what
    # platform JAX comes up on (ops/device.probe_platform — it exits
    # and frees the chip); a device-backed boot — `tpu`, or `auto` that
    # found one — does NOT pre-fork: this process alone initialises JAX
    # and serves; and the fleet, when there is one, is told `host`
    # outright so no worker ever imports JAX to find out. (ROADMAP A3's
    # measured choice between an owner process fed by workers, a worker
    # per chip, and this single process stays open.)
    from minio_tpu.io import workers as workers_mod
    from minio_tpu.ops import device as device_mod
    worker_id = os.environ.get("MTPU_WORKER_ID", "")
    want_device = args.ec_backend == "tpu" or (
        args.ec_backend == "auto" and device_mod.probe_platform() == "tpu")
    # Pre-forked SO_REUSEPORT front-end (io/workers.py): N worker
    # processes each run this whole boot (MTPU_HTTP_WORKERS=1 in the
    # children prevents recursion). Default = cores. Distributed
    # topologies pre-fork too (N nodes x M workers): the node's SINGLE
    # grid port is owned by worker 0, and sibling workers reach the
    # node's lock authority / coherence singleton over loopback — see
    # the worker-topology wiring below.
    if not worker_id:
        n_workers = workers_mod.worker_count_from_env()
        if n_workers > 1 and want_device:
            print(f"ec-backend on the device: one process owns the chip, "
                  f"not pre-forking {n_workers} workers", flush=True)
        elif n_workers > 1:
            return workers_mod.serve_cli(
                (list(argv) if argv is not None else sys.argv[1:])
                + ["--ec-backend", "host"],
                args.address, n_workers, main)
    # Worker identity: "" = plain single-process boot; "0" = the
    # pre-forked worker that owns node-singleton duties (grid listener,
    # lock authority, coherence, recovery sweeps); "1".."M-1" = sibling
    # workers. MTPU_WORKER_TOTAL is the fleet width M (1 outside worker
    # mode) — background ownership shards over node_count x M slots.
    is_w0 = worker_id in ("", "0")
    try:
        worker_total = max(1, int(os.environ.get("MTPU_WORKER_TOTAL",
                                                 "") or "1"))
    except ValueError:
        worker_total = 1

    # Boot self-tests: identical math to the reference or refuse to serve.
    from minio_tpu.erasure.selftest import erasure_self_test
    from minio_tpu.storage.bitrot import bitrot_self_test
    erasure_self_test()
    bitrot_self_test()

    backend = None
    dev = None              # ops/device.DeviceInfo when a device serves
    if want_device:
        # One rule for every process that serves with a device backend,
        # asked for by name or found by `auto`'s probe: it gets a TPU —
        # or the portable path chosen on purpose (JAX_PLATFORMS=cpu,
        # `tpu` only) and labelled as such below — or it refuses to
        # boot, and from here on no device fault is absorbed into
        # another codec under the device's label (device.required()).
        try:
            dev = device_mod.require()
        except device_mod.DeviceUnavailable as e:
            print(f"FATAL: --ec-backend {args.ec_backend}: {e}",
                  file=sys.stderr)
            return 1
        # Boot gate for the DEVICE kernels too: the golden-vector sweep
        # with the host cutover disabled, so the Pallas/XLA GF path that
        # large PUTs will actually run is what gets verified (the plain
        # erasure_self_test above covers the host core only — its
        # 256-byte vectors are all below HOST_CUTOVER_BYTES).
        from minio_tpu.ops.rs_device import DeviceBackend
        backend = DeviceBackend()
        erasure_self_test(DeviceBackend(host_cutover=0))

    # -- grid mesh up BEFORE the object layer (reference: initGlobalGrid
    #    precedes newObjectLayer, cmd/server-main.go:882-942) ----------
    local_disks: dict[str, LocalStorage] = {}
    for ep in all_eps:
        if is_local(ep):
            local_disks[ep.path] = LocalStorage(
                ep.path, endpoint=str(ep) if ep.is_url else "")

    grid_srv = None
    lockers = []
    if distributed:
        from minio_tpu.grid import GridServer, client_for
        from minio_tpu.grid.dsync import (DistNSLock, LocalLocker,
                                          LockServer, RemoteLocker)
        grid_port = my_port + GRID_PORT_OFFSET
        if is_w0:
            grid_srv = GridServer(grid_port)
            StorageRPCService(local_disks).register_into(grid_srv)
            lock_server = LockServer()
            lock_server.register_into(grid_srv)
            node_info = {"deployment_id": ""}
            grid_srv.register("node.info", lambda p: dict(node_info))
            grid_srv.start()
            print(f"grid mesh on :{grid_srv.port} "
                  f"({len(local_disks)} local drives)", flush=True)

            # Wait for every peer's grid before touching formats (the
            # reference's bootstrap handshake,
            # cmd/bootstrap-peer-server.go).
            deadline = time.monotonic() + args.boot_timeout
            for host, port in remote_nodes:
                c = client_for(host, port + GRID_PORT_OFFSET)
                while not c.ping(timeout=2.0):
                    if time.monotonic() > deadline:
                        print(f"WARN: peer {host}:{port} unreachable; "
                              f"its drives boot offline", file=sys.stderr)
                        break
                    time.sleep(0.5)

            lockers = [LocalLocker(lock_server)] + [
                RemoteLocker(client_for(h, p + GRID_PORT_OFFSET))
                for h, p in remote_nodes]
        else:
            # Sibling worker on an N x M node: worker 0 owns the node's
            # grid plane, so this process binds nothing — the node's own
            # lock vote is one more RemoteLocker, over loopback. Worker
            # 0 booted first (the pool forks siblings only after it
            # accepts), so the wait below only spins across a worker-0
            # respawn window; an unreachable loopback then degrades to
            # quorum fast-fails (503s) until it returns, never a wedge.
            self_client = client_for("127.0.0.1", grid_port)
            deadline = time.monotonic() + args.boot_timeout
            while not self_client.ping(timeout=2.0):
                if time.monotonic() > deadline:
                    print("WARN: node grid plane (worker 0) unreachable; "
                          "lock quorum degraded", file=sys.stderr)
                    break
                time.sleep(0.2)
            lockers = [RemoteLocker(self_client)] + [
                RemoteLocker(client_for(h, p + GRID_PORT_OFFSET))
                for h, p in remote_nodes]

    def make_disk(ep: ellipses.Endpoint):
        if is_local(ep):
            return local_disks[ep.path]
        return RemoteStorage(ep.host, ep.port + GRID_PORT_OFFSET, ep.path)

    # -- format boot + object layer ------------------------------------
    pools = []
    deployment_id = None
    n_sets = n_drives = 0
    # (pool_idx, bucket, path) found damaged by the mount-time recovery
    # sweep — enqueued onto the owning set's MRF once sets exist.
    pending_heals: list[tuple] = []
    for spec in pool_eps:
        disks = [make_disk(ep) for ep in spec]
        # Set-size/divisibility/parity were validated pre-fork above
        # (they must error in the parent, not inside a worker child);
        # this recomputation cannot fail.
        set_size = args.set_size or ellipses.choose_set_size(len(disks))

        # Only the node owning the pool's first endpoint initializes a
        # fresh format; everyone else waits for it to appear (reference:
        # prepare-storage leader init + waitForFormatErasure).
        if distributed and not is_local(spec[0]):
            deadline = time.monotonic() + args.boot_timeout
            while all(fmt_mod._safe_read(d) is None for d in disks):
                if time.monotonic() > deadline:
                    break
                time.sleep(0.5)
        attempts = 5 if distributed else 1
        ordered = fmt = None
        for attempt in range(attempts):
            try:
                ordered, fmt = fmt_mod.boot(disks, set_size, deployment_id)
                break
            except fmt_mod.FormatError as e:
                # Distributed boot race: the leader may still be writing
                # formats; retry before declaring the layout broken.
                if attempt == attempts - 1:
                    print(f"FATAL: format verification failed: {e}",
                          file=sys.stderr)
                    return 1
                time.sleep(2.0)
        if deployment_id is not None and fmt.deployment_id != deployment_id:
            # Two unrelated deployments must never be federated
            # (reference: mixed deployment ids are a fatal boot error).
            print(f"FATAL: pool {len(pools)} belongs to deployment "
                  f"{fmt.deployment_id}, expected {deployment_id}",
                  file=sys.stderr)
            return 1
        deployment_id = deployment_id or fmt.deployment_id
        ordered = [d if d is not None else OfflineDisk(f"pos-{i}")
                   for i, d in enumerate(ordered)]
        # Boot janitor + crash recovery: crashed PUTs leave staged
        # shards under the system volume and interrupted rename_data
        # commits leave dangling data dirs / journals referencing lost
        # data (reference sweeps .minio.sys/tmp at startup). The
        # recovery sweep purges the former, removes the latter's
        # orphans, and reports journal-vs-data mismatches for MRF
        # repair. First-boot worker 0 only: siblings (and a RESPAWNED
        # worker 0) boot while others are already serving — pid-tagged
        # staging names and the age gate add a second line of defense
        # (storage/local.sweep_stale_tmp). MTPU_RECOVERY_SWEEP=off
        # falls back to the plain tmp/staging purge.
        if is_w0 and not os.environ.get("MTPU_WORKER_RESPAWN"):
            from minio_tpu.storage.local import (consume_clean_shutdown,
                                                 recovery_sweep,
                                                 sweep_stale_tmp)
            deep_sweep = os.environ.get(
                "MTPU_RECOVERY_SWEEP", "on").lower() not in ("0", "off",
                                                             "false")
            for d in ordered:
                try:
                    # The deep sweep walks the whole namespace — only
                    # worth it when the previous stop was NOT graceful
                    # (crash/power cut). Clean restarts take the cheap
                    # tmp/staging purge.
                    if deep_sweep and not consume_clean_shutdown(d):
                        rep = recovery_sweep(d)
                        for vol, path in rep["heal"]:
                            pending_heals.append((len(pools), vol, path))
                    else:
                        # Clean restart: still replay any group-commit
                        # WALs a SIGKILLed worker left behind (cheap
                        # no-op when gcommit/ is empty).
                        from minio_tpu.storage.group_commit import \
                            replay_wals
                        replay_wals(d)
                        sweep_stale_tmp(d)
                except Exception:  # noqa: BLE001 - janitor never blocks boot
                    pass
        # Deadline + circuit-breaker wrapper: a hung (not dead) drive
        # fails fast instead of stalling every quorum fan-out
        # (reference: cmd/xl-storage-disk-id-check.go).
        if args.drive_timeout > 0:
            from minio_tpu.storage.health import wrap_disks
            ordered = wrap_disks(ordered, op_timeout=args.drive_timeout)
        sets = [ErasureSet(ordered[i:i + set_size], parity=args.parity,
                           backend=backend)
                for i in range(0, len(ordered), set_size)]
        if distributed:
            from minio_tpu.grid.dsync import DistNSLock
            for s in sets:
                s.ns = DistNSLock(lockers)
        pools.append(ErasureSets(sets, fmt.deployment_id))
        n_sets += len(sets)
        n_drives += len(ordered)

    if distributed and grid_srv is not None:
        node_info["deployment_id"] = deployment_id
        # Cross-node config handshake: peers must agree on deployment
        # (reference: verifyServerSystemConfig, cmd/server-main.go:928).
        # Worker 0 only — it owns the node's grid identity; siblings
        # booted after it already verified.
        from minio_tpu.grid import client_for as _cf
        for host, port in remote_nodes:
            try:
                info = _cf(host, port + GRID_PORT_OFFSET).call(
                    "node.info", None, timeout=3.0)
                peer_dep = info.get("deployment_id", "")
                if peer_dep and peer_dep != deployment_id:
                    print(f"FATAL: peer {host}:{port} deployment "
                          f"{peer_dep} != {deployment_id}", file=sys.stderr)
                    return 1
            except Exception:  # noqa: BLE001 - peer still booting
                pass

    layer = ServerPools(pools)
    if distributed:
        # Coordinator election for fleet-wide migrations: rebalance and
        # decommission take a dsync write lease (decom.coordinator_lease)
        # over the same lockers as the namespace locks, so exactly one
        # node drives a walk and a SIGKILLed coordinator's lease expires
        # after MTPU_GRID_LOCK_TTL for any peer to take over.
        layer.lockers = lockers
    # Resume an interrupted pool decommission from its checkpoint
    # (reference: pools.Init resuming persisted decom state; with the
    # lease above, at most one booting node actually wins the resume).
    if len(pools) > 1:
        try:
            if layer.resume_decommission() is not None:
                print("resuming interrupted pool decommission",
                      flush=True)
        except Exception as e:  # noqa: BLE001 - decom must not block boot
            print(f"WARN: decommission resume failed: {e}",
                  file=sys.stderr)
        # Likewise an interrupted rebalance (reference: pools.Init
        # loading persisted rebalanceMeta).
        try:
            if layer.resume_rebalance() is not None:
                print("resuming interrupted pool rebalance", flush=True)
        except Exception as e:  # noqa: BLE001 - must not block boot
            print(f"WARN: rebalance resume failed: {e}", file=sys.stderr)
    # Crash-recovery repairs found by the mount-time sweep: route each
    # damaged object to its owning set's MRF (heals are idempotent and
    # deep-verified there).
    for pool_idx, vol, path in pending_heals:
        try:
            p = pools[pool_idx]
            p.sets[p.set_index(path)].mrf.enqueue(vol, path)
        except Exception:  # noqa: BLE001 - scanner converges it later
            pass
    # Background data scanner: usage accounting, 1/1024 deep-heal
    # sampling, replaced-drive format restore (reference:
    # cmd/data-scanner.go's scanner loop).
    from minio_tpu.object.scanner import Scanner
    all_sets = [s for p in pools for s in p.sets]
    # Fleet-sharded background ownership (N nodes x M workers): every
    # erasure set is owned by exactly ONE (node, worker) slot, so each
    # cycle covers each set once FLEET-wide — distributed nodes used to
    # scan/heal every set on every node (N x duplication), and worker
    # mode parked all of it on worker 0 while siblings idled. Node
    # ranks come from the sorted endpoint topology, identical on every
    # node by construction (the same server command runs everywhere);
    # a dead slot's sets go unscanned only until its worker respawns.
    widx = int(worker_id or 0)
    if distributed:
        _nodes = sorted({(ep.host, ep.port) for ep in all_eps})
        _remote = set(remote_nodes)
        node_rank = next((i for i, hp in enumerate(_nodes)
                          if hp not in _remote), 0)
        fleet_slots = len(_nodes) * worker_total
        bg_slot = node_rank * worker_total + widx
    else:
        fleet_slots = worker_total
        bg_slot = widx
    owned_sets = [s for i, s in enumerate(all_sets)
                  if i % fleet_slots == bg_slot]
    scanner = Scanner(owned_sets, interval=args.scanner_interval)
    # ILM: lifecycle rules stored per bucket evaluate on every scanned
    # object (reference: cmd/bucket-lifecycle.go via the scanner).
    from minio_tpu.object.lifecycle import make_scanner_hook

    def _ilm_deleted(es, bucket, key, deleted):
        # Late binding: this hook is wired before the replication
        # engine boots.  ILM-created delete markers replicate like API
        # deletes — expiry on the source must not strand a live latest
        # on the target.
        del es
        try:
            r = srv.replicator
        except NameError:
            return
        if r is not None and hasattr(r, "ilm_deleted"):
            r.ilm_deleted(bucket, key, deleted)

    scanner.on_object.append(make_scanner_hook(on_delete=_ilm_deleted))
    # A slot with no owned sets (more slots than sets) starts nothing;
    # the single-process single-node boot degenerates to slot 0 of 1
    # owning everything — exactly the old behavior.
    if args.scanner_interval > 0 and owned_sets:
        scanner.start()
    layer.scanner = scanner
    # Drive lifecycle manager: detect hot-replaced (fresh) drives while
    # serving, restore their slot format, and run checkpointed bulk
    # heals that resume across restarts (object/drive_heal). Sharded
    # over the same ownership slots as the scanner — format restore and
    # healing markers ride the generic disk interface, so an owner
    # converges another node's replaced drive over the grid.
    from minio_tpu.object.drive_heal import (DriveHealManager,
                                             admission_pressure)
    drive_heal = DriveHealManager(
        owned_sets, total_hint=lambda: scanner.usage.objects)
    layer.drive_heal = drive_heal
    if owned_sets:
        drive_heal.start(interval=args.scanner_interval
                         if args.scanner_interval > 0 else 10.0)
    # IAM: users/service-accounts/policies, replicated on pool 0's
    # drives (reference: cmd/iam.go bootstrap).
    from minio_tpu.iam import IAMSys
    creds = Credentials()
    creds.iam = IAMSys(pools[0].sets, creds.access_key, creds.secret_key)
    srv = S3Server(layer, address=args.address, credentials=creds)
    # Quota enforcement reads the scanner's usage accounting.
    srv.scanner = scanner
    # Drive-heal progress in admin heal status + Prometheus; the bulk
    # heal sheds while admission control reports client queueing.
    srv.drive_heal = drive_heal
    drive_heal.pressure = lambda: admission_pressure(srv.admission)
    # Migration walks (rebalance/decommission) are a background class
    # too: they pause while foreground requests queue, same signal as
    # the bulk heal above (object/decom.MigrationGovernor).
    layer.migration_pressure = lambda: admission_pressure(srv.admission)
    # Warm tiers: registry on pool 0's drives, resolved by every set's
    # read/transition paths (reference: globalTierConfigMgr).
    from minio_tpu.object.tier import TierRegistry
    srv.tiers = TierRegistry(pools[0].sets)
    for s in all_sets:
        s.tiers = srv.tiers
    # Site replication: re-arm a persisted peer registry
    # (reference: site replication config survives restarts).
    from minio_tpu.replication.site import (SiteReplicator,
                                            hook_iam_changes, load_config)
    site_cfg = load_config(pools[0].sets)
    if site_cfg:
        srv.site = SiteReplicator(layer, pools[0].sets, site_cfg,
                                  iam=creds.iam)
        print(f"site replication armed "
              f"({len(site_cfg.get('peers', []))} peers)", flush=True)
    hook_iam_changes(srv)
    # Batch jobs: resume any that a crash or restart interrupted
    # (reference: batch jobs survive restarts via their checkpoints).
    from minio_tpu.object.batch import BatchJobs
    srv.batch = BatchJobs(layer, pools[0].sets)
    srv.batch.kms = srv.kms
    if is_w0:
        # Checkpointed batch jobs resume once, not once per worker.
        try:
            resumed = srv.batch.resume_all()
            if resumed:
                print(f"resumed {resumed} interrupted batch job(s)",
                      flush=True)
        except Exception as e:  # noqa: BLE001 - batch must not block boot
            print(f"WARN: batch resume failed: {e}", file=sys.stderr)
    srv.compression = args.compression
    # Persisted config overrides flags (the flags seed first boot).
    from minio_tpu.s3 import config as cfg_mod
    try:
        cfg_mod.apply_config(srv, cfg_mod.load_config(layer))
    except Exception:  # noqa: BLE001 - config is optional
        pass
    if distributed:
        # Self-declared node identity: unique per node, stable across
        # restarts. The bind address is neither when every node runs
        # the default 0.0.0.0:9000 — fall back to the hostname, which
        # is what distinguishes nodes in a same-port deployment. Every
        # worker carries it: slow-op records, trace spans, and the
        # federated telemetry snapshots are labeled with the node that
        # produced them.
        from minio_tpu.utils import tracing as tracing_mod
        ident_host = my_host if my_host not in ("0.0.0.0", "::", "") \
            else socket_mod.gethostname()
        node_id = f"{ident_host}:{my_port}"
        srv.node_id = node_id
        tracing_mod.set_node(node_id)
        # Peer control plane: mutations of shared state (IAM, config,
        # decom) fan out an immediate cache invalidation to every
        # peer; the per-cache TTL covers unreachable peers
        # (reference: cmd/notification.go + cmd/peer-rest-client.go:304).
        from minio_tpu.grid.peers import (PeerNotifier, RELOAD_HANDLER,
                                          make_reload_handler)
        peer_notifier = PeerNotifier(
            [client_for(h, p + GRID_PORT_OFFSET) for h, p in remote_nodes])
        if grid_srv is not None:
            # Inbound reload pings land on the node's grid listener —
            # worker 0's process. Sibling workers converge through
            # their per-cache TTLs, the same backstop that covers an
            # unreachable peer.
            grid_srv.register(RELOAD_HANDLER, make_reload_handler(
                iam=creds.iam, object_layer=layer,
                apply_config=lambda: cfg_mod.apply_config(
                    srv, cfg_mod.load_config(layer))))
        srv.peer_notify = peer_notifier.broadcast
        srv.peer_notifier = peer_notifier
        creds.iam.on_change = lambda: peer_notifier.broadcast("iam")
        layer.on_decom_change = lambda: peer_notifier.broadcast("decom")
        # Namespace + bucket-meta invalidation rides the GENERATION
        # protocol (grid/coherence): acked-or-escalated pushes, and a
        # reconnecting peer must resync generations before its caches
        # re-arm — the contract that lets fi_cache and the listing
        # caches stay ON cluster-wide.
        from minio_tpu.grid.coherence import (CLASS_BUCKET_META,
                                              CLASS_LISTING, FileGate,
                                              PeerCoherence, RELAY_HANDLER,
                                              make_set_invalidator)
        all_sets_d = [s for p in pools for s in p.sets]
        # N x M worker topology: the gate state file and relay-failure
        # flag live in the same shared dir io/workers.py keeps its
        # bump-generation files in (worker mode only — a plain
        # single-process node needs neither).
        shared_dir = None
        if worker_id:
            _root = workers_mod._first_drive_root(layer)
            if _root is not None:
                shared_dir = os.path.join(_root, ".mtpu.sys", "workers")
                os.makedirs(shared_dir, exist_ok=True)
        if grid_srv is not None:
            # Coherence reuses the node identity above (peers key
            # applied-generation records by it; restart detection rides
            # the instance id).
            coherence = PeerCoherence(
                node_id=node_id,
                peers={f"{h}:{p}": client_for(h, p + GRID_PORT_OFFSET)
                       for h, p in remote_nodes},
                on_invalidate=make_set_invalidator(all_sets_d,
                                                   layer=layer))
            coherence.register_into(grid_srv)
            if shared_dir is not None:
                coherence.state_path = os.path.join(
                    shared_dir, "coherence.state")
                coherence.relay_flag_path = os.path.join(
                    shared_dir, "coherence.relay-flag")
            layer.on_bucket_meta_change = \
                lambda bucket: coherence.broadcast(bucket,
                                                   CLASS_BUCKET_META)
            # A write on this node orphans peers' walk streams +
            # fileinfo entries for the bucket (leading-edge coalesced
            # inside MetaCache.bump, trailing-guaranteed).
            for s in all_sets_d:
                s.metacache.on_bump = (
                    lambda bucket: coherence.broadcast(bucket,
                                                       CLASS_LISTING))
                # Synchronous acked pushes: a timer-deferred
                # invalidation would be a cross-node staleness window
                # no gate covers.
                s.metacache.bump_coalesce = 0.0
                # EVERY set gates on coherence in distributed mode — a
                # set whose drives are all local here is remote from
                # the peers' side, so peers mutate it too.
                s.fi_cache.remote_gate = coherence.coherent
                s.metacache.remote_gate = coherence.coherent
            coherence.start()
            srv.coherence = coherence
        else:
            # Sibling worker: worker 0 owns the node's PeerCoherence.
            # Outbound bumps relay to it over loopback (it bumps the
            # node generation and fans out to peers); a failed relay
            # leaves the dead-man flag its next sync tick converts into
            # a wildcard broadcast, so a mutation can never vanish into
            # a worker-0 respawn window. Inbound peer invalidations
            # reach this process through the shared list.gen/meta.gen
            # files the wrapped bump funnel already maintains. The
            # cache gate is worker 0's published state file — stale
            # heartbeat reads as incoherent (fail closed).
            relay_client = client_for("127.0.0.1",
                                      my_port + GRID_PORT_OFFSET)
            _flag = os.path.join(shared_dir, "coherence.relay-flag") \
                if shared_dir is not None else None

            def _relay(bucket, cls):
                try:
                    relay_client.call(RELAY_HANDLER,
                                      {"b": bucket, "c": cls},
                                      timeout=5.0)
                except Exception:  # noqa: BLE001 - dead-man flag below
                    if _flag is not None:
                        try:
                            with open(_flag, "w"):
                                pass
                        except OSError:
                            pass
            gate = FileGate(os.path.join(shared_dir, "coherence.state")) \
                if shared_dir is not None else (lambda: False)
            layer.on_bucket_meta_change = \
                lambda bucket: _relay(bucket, CLASS_BUCKET_META)
            for s in all_sets_d:
                s.metacache.on_bump = (
                    lambda bucket: _relay(bucket, CLASS_LISTING))
                s.metacache.bump_coalesce = 0.0
                s.fi_cache.remote_gate = gate
                s.metacache.remote_gate = gate
        # Cluster-wide profiling fan-out (reference: profiling rides
        # NotificationSys too). Inbound verbs live on the node's grid
        # listener (worker 0); outbound peer clients on every worker.
        if grid_srv is not None:
            from minio_tpu.s3.profiling import (PROFILE_HANDLER,
                                                make_profile_handler)
            grid_srv.register(PROFILE_HANDLER,
                              make_profile_handler(srv.profiler))
            # Per-node admin-info summaries for the cluster info fan-out.
            from minio_tpu.s3.metrics import node_info as _node_info
            grid_srv.register("peer.info",
                              lambda payload: _node_info(srv))
            # Fleet-federated telemetry: peers pull this node's merged
            # metrics snapshot (all its workers) in one call, and tail
            # its live trace entries as a stream (?cluster=true admin
            # trace). Both land on worker 0, which holds the node's
            # control plane and merges siblings through it.
            from minio_tpu.s3.metrics import \
                peer_metrics_state as _peer_metrics_state
            from minio_tpu.s3.trace import make_trace_stream
            grid_srv.register("peer.metrics",
                              lambda payload: _peer_metrics_state(srv))
            grid_srv.register_stream("trace.stream",
                                     make_trace_stream(srv))
        srv.profile_peers = [
            (f"{h}:{p}", client_for(h, p + GRID_PORT_OFFSET))
            for h, p in remote_nodes]
        # Any-node elastic admin verbs: status fans IN (the coordinator
        # holds counters fresher than the persisted checkpoint), stop
        # fans OUT (it must reach whichever node drives the walk).
        def _elastic_status(payload):
            rb = getattr(layer, "_rebalance", None)
            dc = layer._decom
            return {
                "rebalance": layer.rebalance_status(),
                "rebalance_live": bool(rb is not None
                                       and not rb.wait(timeout=0)),
                "decommission": layer.decommission_status(),
                "decommission_live": bool(dc is not None
                                          and not dc.wait(timeout=0)),
            }

        def _elastic_stop(payload):
            kind = (payload or {}).get("kind", "")
            if kind == "rebalance":
                layer.stop_rebalance()
            elif kind == "decommission":
                layer.cancel_decommission()
            return {"ok": True}

        if grid_srv is not None:
            grid_srv.register("elastic.status", _elastic_status)
            grid_srv.register("elastic.stop", _elastic_stop)
            # Fleet-sharded migration batches: the coordinator ships
            # listing-page shards here; this node migrates them with
            # its OWN pools layer and returns counters only
            # (object/decom.exec_page — no peer ever checkpoints).
            from minio_tpu.object.decom import exec_page as _exec_page
            grid_srv.register(
                "mig.page",
                lambda p: _exec_page(layer, int(p["src"]), p["b"],
                                     list(p.get("keys") or ()),
                                     p.get("ex") or ()))
        # Every worker may win the coordinator lease; the dispatcher
        # targets each peer NODE's grid plane (its worker 0).
        layer.migration_peers = [client_for(h, p + GRID_PORT_OFFSET)
                                 for h, p in remote_nodes]
        if len(pools) > 1 and is_w0:
            # Orphan-recovery loop: resumes a dead coordinator's walk
            # from its checkpoint once the lease expires.
            layer.start_elastic_janitor()
    if args.audit_webhook:
        from minio_tpu.s3.trace import AuditLogger
        srv.audit = AuditLogger(args.audit_webhook)
    # Async bucket replication: rules + remote targets live in bucket
    # metadata; the scanner hook re-queues PENDING/FAILED versions.
    from minio_tpu.replication import ReplicationEngine
    srv.replicator = ReplicationEngine(layer)
    scanner.on_object.append(srv.replicator.scanner_hook)
    notify_targets = []
    if args.notify_webhook:
        from minio_tpu.events import WebhookTarget
        notify_targets.append(WebhookTarget("webhook",
                                            args.notify_webhook))
    for flag, cls, tid in ((args.notify_mqtt, "MQTTTarget", "mqtt"),
                           (args.notify_nats, "NATSTarget", "nats"),
                           (args.notify_redis, "RedisTarget", "redis")):
        if not flag:
            continue
        import minio_tpu.events as _ev
        broker, _, chan = flag.partition("/")
        if not chan:
            print(f"FATAL: --notify-{tid} needs host:port/"
                  f"{'topic' if tid == 'mqtt' else 'subject' if tid == 'nats' else 'listkey'}",
                  file=sys.stderr)
            return 1
        try:
            notify_targets.append(getattr(_ev, cls)(tid, broker, chan))
        except ValueError:
            print(f"FATAL: --notify-{tid}: {broker!r} is not host:port",
                  file=sys.stderr)
            return 1
    if notify_targets:
        # Store-and-forward notifications; the queue lives on the
        # first local drive so it survives restarts.
        from minio_tpu.events import EventNotifier
        first_local = next((d for p in pools for s in p.sets
                            for d in s.disks
                            if getattr(d, "root", None)), None)
        # Durable queue location: a local drive when we have one, else a
        # per-deployment dir under $HOME (reboot-durable, unlike /tmp).
        store = os.path.join(first_local.root, ".mtpu.sys", "events") \
            if first_local is not None else \
            os.path.join(os.path.expanduser("~"), ".mtpu",
                         f"events-{deployment_id}")
        srv.notifier = EventNotifier(layer, store, targets=notify_targets)
    ftp = None
    if args.ftp_address:
        from minio_tpu.gateway import FTPGateway
        ftp = FTPGateway(layer, creds, address=args.ftp_address)
        ftp.start()
        print(f"minio-tpu serving FTP on {ftp.address}", flush=True)
    # Pre-forked worker wiring (no-op outside worker mode): control
    # pipes, divided admission budgets, cross-process locks and cache
    # generations, SIGTERM drain.
    workers_mod.maybe_attach_worker(srv)
    # The label is what JAX reported in THIS process, never what was
    # asked for: "tpu" only on a TPU; the explicit-CPU portable path
    # says so.
    if dev is None:
        srv.ec_backend = ec_label = "host"
    else:
        srv.ec_backend = "tpu" if dev.platform == "tpu" else "portable"
        ec_label = (f"{srv.ec_backend}, platform={dev.platform}, "
                    f"device_kind={dev.device_kind!r}, "
                    f"devices={dev.devices}, mesh={dev.mesh_devices}")
        if dev.platform != "tpu":
            ec_label += (", JAX_PLATFORMS="
                         f"{os.environ.get('JAX_PLATFORMS', '')}: "
                         "XLA reference path, no TPU")
    srv.start()
    if not worker_id and \
            threading.current_thread() is threading.main_thread():
        # Single-process boot (every device-backed boot is one): SIGTERM
        # takes the graceful path below — drain, stamp the drives clean,
        # exit 0 — and the chip frees with the process. Workers install
        # their own drain handler (io/workers.WorkerContext.attach).
        def _on_sigterm(signum, frame):
            raise KeyboardInterrupt
        signal.signal(signal.SIGTERM, _on_sigterm)
    try:
        # Said once it is true: accepting, and a SIGTERM from whoever
        # waited for this line already stops gracefully.
        print(f"minio-tpu serving S3 on {srv.address} "
              f"({len(pools)} pools, {n_sets} sets, {n_drives} drives, "
              f"{'distributed, ' if distributed else ''}"
              f"{'worker ' + worker_id + ', ' if worker_id else ''}"
              f"ec-backend={ec_label})", flush=True)
        while True:
            time.sleep(3600)
    except KeyboardInterrupt:
        # Background healers first, JOINED with the object layer still
        # whole under them: the scanner, the drive-heal manager and
        # every set's MRF worker take no new work and finish the heal
        # in flight (one object; a 64 MiB deep heal outlasts the 2 s
        # their stop() waits by default). Two things hang on it. The
        # clean stamp below tells the next boot to skip the recovery
        # sweep, so it must not be written while a heal is between
        # rename_data's steps. And a heal's device call runs on the
        # kernel lane's daemon thread: finalising the interpreter under
        # it aborts from inside the runtime (SIGABRT, seen after a
        # degraded read). Calibration probes need no mention here —
        # they write nothing to the drives and are non-daemon threads,
        # so returning from main() waits for them.
        deadline = time.monotonic() + _QUIESCE_S
        busy = [what for what, stop in (
            ("scanner", scanner.stop), ("drive heal", drive_heal.stop),
            *((f"MRF heal (set {i})", s.stop_mrf)
              for i, s in enumerate(all_sets)))
            if not stop(max(0.0, deadline - time.monotonic()))]
        layer.stop_elastic_janitor()
        if getattr(srv, "coherence", None) is not None:
            srv.coherence.stop()
        if ftp is not None:
            # Gateways stop BEFORE the S3 server closes the object
            # layer (their in-flight transfers use it).
            ftp.stop()
        srv.stop()
        if grid_srv is not None:
            grid_srv.stop()
        if busy:
            # Not a clean stop, and not called one: no stamp, so the
            # next boot runs the deep recovery sweep, and exit 1.
            print(f"WARN: shutdown: {', '.join(busy)} still running "
                  f"after {_QUIESCE_S:.0f} s; drives NOT stamped clean",
                  file=sys.stderr, flush=True)
            return 1
        # Graceful exit: stamp every local drive so the next boot skips
        # the deep crash-recovery sweep (storage/local.recovery_sweep).
        from minio_tpu.storage.local import mark_clean_shutdown
        for d in local_disks.values():
            mark_clean_shutdown(d)
    return 0


if __name__ == "__main__":
    sys.exit(main())
