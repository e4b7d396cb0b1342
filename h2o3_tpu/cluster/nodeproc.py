"""Minimal cluster node process — membership + RPC + DKV + DTask, no REST.

``python -m h2o3_tpu.cluster.nodeproc --cluster-name c --node-name n1
--address-file /tmp/n1.addr [--flatfile peers.txt]`` boots the
application-plane node the multi-process tests peer against: it binds
port 0, writes the resolved ``host:port`` to the address file (the
rendezvous the harness folds into the other nodes' flatfiles), joins the
cloud, and serves until its stdin closes or it is signalled — the harness
owns its lifetime.

The full launcher (``python -m h2o3_tpu --flatfile ...``) layers the
REST server and JAX runtime on the same bootstrap; this entry exists so
cluster tests pay milliseconds, not a backend init, per node.
"""

from __future__ import annotations

import argparse
import os
import signal
import sys


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(prog="python -m h2o3_tpu.cluster.nodeproc")
    p.add_argument("--cluster-name", required=True)
    p.add_argument("--node-name", required=True)
    p.add_argument("--host", default="127.0.0.1")
    p.add_argument("--port", type=int, default=0,
                   help="RPC port (0 = OS-assigned)")
    p.add_argument("--flatfile", default=None,
                   help="host:port peer list (one per line)")
    p.add_argument("--address-file", default=None,
                   help="write the resolved host:port here after bind")
    p.add_argument("--hb-interval", type=float, default=None)
    p.add_argument("--client", action="store_true",
                   help="join as a client node (holds no keys)")
    return p


def main(argv=None) -> int:
    args = build_parser().parse_args(argv)
    # several of these run beside their harness on one host, and a chip
    # belongs to one process: a shard a peer executes here runs on the CPU
    # backend, never on an accelerator the harness (or another peer) holds
    os.environ["JAX_PLATFORMS"] = "cpu"
    from h2o3_tpu.cluster.membership import CloudJoinError, boot_node

    try:
        cloud = boot_node(
            args.cluster_name,
            args.node_name,
            host=args.host,
            port=args.port,
            client=args.client,
            hb_interval=args.hb_interval,
            flatfile=args.flatfile,
            address_file=args.address_file,
        )
    except CloudJoinError as e:
        print(f"cluster join failed ({e.code}): {e}", file=sys.stderr)
        return 2
    print(f"node {cloud.info.ident} up in cloud "
          f"'{args.cluster_name}'", flush=True)

    stop = {"flag": False}

    def _sig(signum, frame):
        stop["flag"] = True

    signal.signal(signal.SIGINT, _sig)
    signal.signal(signal.SIGTERM, _sig)
    # serve until the harness closes stdin or signals; a dead parent must
    # never leave an orphan listener behind (polling select so a signal
    # is noticed within half a second, not only at the next stdin byte)
    import select

    while not stop["flag"]:
        ready, _, _ = select.select([sys.stdin], [], [], 0.5)
        if ready and not sys.stdin.readline():
            break
    cloud.stop()
    return 0


if __name__ == "__main__":
    sys.exit(main())
