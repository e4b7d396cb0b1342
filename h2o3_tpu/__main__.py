"""CLI launcher: ``python -m h2o3_tpu`` starts a serving node.

Reference: ``water/H2O.java:352-616,2238`` — the ``OptArgs`` CLI surface of
``java -jar h2o.jar`` (-name -port -baseport -ice_root -nthreads -cleaner
-auto_recovery_dir -jks/-hash_login ...) and the launcher modules
(``h2o-app/H2OApp.java:3``, SURVEY.md L11).

TPU-native: one process is one cloud (the device mesh is the "cluster");
the launcher parses the OptArgs subset that still has meaning here, starts
the REST server, optionally resumes interrupted Recoverables, and serves
until interrupted.
"""

from __future__ import annotations

import argparse
import signal
import sys


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="python -m h2o3_tpu",
        description="Start an h2o3-tpu serving node (REST API + Flow-lite).",
    )
    p.add_argument("--name", default="h2o3-tpu",
                   help="cloud name (-name)")
    p.add_argument("--port", type=int, default=54321,
                   help="REST port (-port); 0 picks a free port")
    p.add_argument("--ip", default="127.0.0.1",
                   help="bind address (-ip); use 0.0.0.0 in pods/containers")
    p.add_argument("--ice-root", default=None,
                   help="spill/log directory (-ice_root)")
    p.add_argument("--max-mem", default=None,
                   help="host-memory budget for frames before spilling, "
                        "e.g. 4g / 512m (the -Xmx + -cleaner pair)")
    p.add_argument("--auto-recovery-dir", default=None,
                   help="resume an interrupted grid/AutoML from this "
                        "directory at startup (-auto_recovery_dir)")
    p.add_argument("--ssl-cert", default=None, help="TLS certificate (PEM)")
    p.add_argument("--ssl-key", default=None, help="TLS private key (PEM)")
    p.add_argument("--hash-login-file", default=None,
                   help="hash-file Basic auth (-hash_login): lines of "
                        "user:sha256hex or the salted "
                        "user:pbkdf2:iters:salt:hash form emitted by "
                        "--hash-password")
    p.add_argument("--login-type", default=None,
                   choices=["hash", "ldap"],
                   help="auth SPI backend (LoginType); hash is implied "
                        "by --hash-login-file")
    p.add_argument("--ldap-url", default=None,
                   help="LDAP server URL for --login-type ldap "
                        "(-ldap_login)")
    p.add_argument("--ldap-bind-template", default=None,
                   help="bind-DN template with {} for the username, e.g. "
                        "'uid={},ou=people,dc=example,dc=org'")
    p.add_argument("--hash-password", nargs=2, metavar=("USER", "PASS"),
                   default=None,
                   help="print a salted PBKDF2 hash-file line for "
                        "USER/PASS and exit")
    p.add_argument("--log-dir", default=None,
                   help="write logs here in addition to the in-memory ring")
    # multi-host pod launch (the h2odriver / h2o-k8s analogue: instead of
    # flatfile/multicast cloud formation, hosts rendezvous at a JAX
    # coordinator and XLA owns the collective fabric)
    p.add_argument("--coordinator", default=None, metavar="HOST:PORT",
                   help="JAX distributed coordinator address; process 0 "
                        "binds it, others connect (multi-host pods; "
                        "replaces -flatfile cloud formation)")
    p.add_argument("--num-processes", type=int, default=None,
                   help="total processes in the pod")
    p.add_argument("--process-id", type=int, default=None,
                   help="this process's index (0-based); on k8s, derive "
                        "from the StatefulSet ordinal (see deploy/)")
    # application-plane clustering (the -flatfile / -name cloud formation
    # of the reference, h2o3_tpu/cluster/): heartbeat membership, node
    # RPC, distributed DKV homes, multi-node task fan-out
    p.add_argument("--flatfile", default=None, metavar="PATH",
                   help="peer list (one host:port RPC address per line, "
                        "# comments ok); presence of this flag boots the "
                        "application-plane cluster node (-flatfile)")
    p.add_argument("--cluster-name", default=None,
                   help="application-plane cloud name; members of one "
                        "cloud must agree on it (default: --name)")
    p.add_argument("--node-name", default=None,
                   help="this node's unique name in the cloud (default: "
                        "<name>-<pid>); a duplicate is rejected at join "
                        "with a clear 409")
    p.add_argument("--cluster-port", type=int, default=0,
                   help="node RPC bind port (0 = OS-assigned)")
    p.add_argument("--cluster-address-file", default=None, metavar="PATH",
                   help="write this node's resolved RPC host:port here "
                        "after bind (harness rendezvous for --cluster-port 0)")
    return p


def _parse_mem(s: str) -> int:
    s = s.strip().lower()
    mult = 1
    if s.endswith("g"):
        mult, s = 1 << 30, s[:-1]
    elif s.endswith("m"):
        mult, s = 1 << 20, s[:-1]
    elif s.endswith("k"):
        mult, s = 1 << 10, s[:-1]
    return int(float(s) * mult)


def main(argv=None) -> int:
    args = build_parser().parse_args(argv)

    if args.hash_password:
        from h2o3_tpu.api.auth import hash_entry

        print(hash_entry(*args.hash_password))
        return 0

    from h2o3_tpu.util import log as L

    L.init(dir=args.log_dir or args.ice_root)
    logger = L.get_logger("launcher")

    from h2o3_tpu.util import compile_cache, telemetry

    # before the first jit: a node that owns a chip must not recompile the
    # training-block programs on every start, and /3/Metrics must count
    # every XLA compile from the first one on
    logger.info("compile cache: %s", compile_cache.configure() or "off")
    telemetry.install_jax_compile_listener()

    if args.coordinator:
        # multi-host rendezvous BEFORE any backend use: after this, every
        # process sees the pod's full device set and default_mesh() spans
        # hosts (water/H2O.java cloud formation -> jax.distributed)
        from h2o3_tpu.parallel.mesh import distributed_initialize

        if args.num_processes is None or args.process_id is None:
            print("--coordinator requires --num-processes and --process-id",
                  file=sys.stderr)
            return 2
        if args.num_processes < 1 or not (
                0 <= args.process_id < args.num_processes):
            # catch the misconfiguration HERE with a clear message — fed
            # to the coordinator it becomes an opaque rendezvous stall
            print(f"--process-id must be in [0, --num-processes): got "
                  f"process-id={args.process_id} "
                  f"num-processes={args.num_processes}", file=sys.stderr)
            return 2
        distributed_initialize(
            coordinator_address=args.coordinator,
            num_processes=args.num_processes,
            process_id=args.process_id,
        )
        logger.info("joined pod: process %d/%d via %s",
                    args.process_id, args.num_processes, args.coordinator)

    if args.max_mem:
        from h2o3_tpu.keyed import DKV

        DKV.set_memory_budget(_parse_mem(args.max_mem), ice_dir=args.ice_root)
        logger.info("frame memory budget: %s (ice: %s)",
                    args.max_mem, args.ice_root or "<tmp>")

    cloud = None
    if args.flatfile is not None:
        # application-plane cloud BEFORE the REST server: /3/Cloud must
        # answer with real members from the first request; the server
        # advertises its resolved REST port into the cloud at bind time
        import os as _os

        from h2o3_tpu.cluster.membership import CloudJoinError, boot_node

        try:
            # a wildcard --ip binds the RPC server on all interfaces;
            # Cloud advertises a routable address in its place so
            # cross-host peers can actually dial back
            cloud = boot_node(
                args.cluster_name or args.name,
                args.node_name or f"{args.name}-{_os.getpid()}",
                host=args.ip,
                port=args.cluster_port,
                flatfile=args.flatfile,
                address_file=args.cluster_address_file,
            )
        except CloudJoinError as e:
            # the clear 4xx surface: a duplicate --node-name (409) or
            # wrong --cluster-name (400) fails fast and says so, instead
            # of stalling forever on a membership hash that never agrees
            print(f"cluster join rejected ({e.code}): {e}", file=sys.stderr)
            return 2
        logger.info("cluster node %s up in cloud '%s' (rpc %s:%d)",
                    cloud.info.name, cloud.cloud_name,
                    cloud.info.host, cloud.info.port)

    from h2o3_tpu.api import start_server

    auth_backend = None
    if args.login_type == "ldap":
        from h2o3_tpu.api.auth import make_backend

        auth_backend = make_backend(
            "ldap", ldap_url=args.ldap_url,
            ldap_bind_template=args.ldap_bind_template)

    server = start_server(
        port=args.port,
        name=args.name,
        ssl_cert=args.ssl_cert,
        ssl_key=args.ssl_key,
        auth_file=args.hash_login_file,
        auth_backend=auth_backend,
        ip=args.ip,
    )
    logger.info("%s listening on %s", args.name, server.url)
    print(f"h2o3-tpu node '{args.name}' up at {server.url}", flush=True)

    if args.auto_recovery_dir:
        from h2o3_tpu.recovery import Recovery, auto_recover

        if Recovery.present(args.auto_recovery_dir):
            logger.info("auto-recovering from %s", args.auto_recovery_dir)
            try:
                result = auto_recover(args.auto_recovery_dir)
                logger.info("auto-recovery finished: %r", result)
            except Exception as e:
                logger.error("auto-recovery failed: %s: %s",
                             type(e).__name__, e)

    stop = {"flag": False}

    def _sig(signum, frame):
        stop["flag"] = True

    signal.signal(signal.SIGINT, _sig)
    signal.signal(signal.SIGTERM, _sig)
    try:
        import time

        while not stop["flag"]:
            time.sleep(0.5)
    finally:
        server.stop()
        if cloud is not None:
            from h2o3_tpu.cluster.membership import set_local_cloud

            cloud.stop()
            set_local_cloud(None)
        logger.info("node stopped")
    return 0


if __name__ == "__main__":
    sys.exit(main())
