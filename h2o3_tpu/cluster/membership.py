"""L2 membership: heartbeat gossip + quorum cloud formation.

Reference: every H2O node multicasts/flatfile-unicasts a ``HeartBeat``
(``water/HeartBeat.java`` — free memory, K/V bytes, CPU ticks, client
flag) and Paxos-shaped agreement turns the set of heard-from nodes into
*the cloud*: a sorted member list whose hash every member must report
before consensus is declared (``water/Paxos.java:10-27``), with missed
heartbeats driving suspicion and removal, and a cloud version fencing
stale members out of a re-formed cloud.

TPU-native split: ``jax.distributed`` still owns the *data-plane*
rendezvous (collectives need XLA's fabric); this layer owns the
*application-plane* truth — who is in the cloud RIGHT NOW, which nodes
are suspect, where a key lives — which XLA neither tracks nor exposes.

Formation here is deliberately the flatfile/gossip flavor (no UDP
multicast): each node heartbeats its seeds + known members over
:mod:`~h2o3_tpu.cluster.rpc`; payloads carry the sender's member list and
cloud version, receivers merge, and the cloud has consensus when every
live member reports the same membership hash.  Suspicion after
``H2O3_TPU_HB_SUSPECT`` missed beats, removal after twice that, and a
removed (tombstoned) member heartbeating with its stale cloud version is
rejected with a coded fault until it acknowledges the newer version and
rejoins.
"""

from __future__ import annotations

import dataclasses
import hashlib
import os
import socket
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

from h2o3_tpu.cluster import faults as _faults
from h2o3_tpu.cluster import rpc as _rpc
from h2o3_tpu.util import flight as _flight
from h2o3_tpu.util import telemetry

_CLUSTER_SIZE = telemetry.gauge(
    "cluster_size", "members in the application-plane cloud")
_CLUSTER_VERSION = telemetry.gauge(
    "cluster_version", "membership epoch (bumps on every join/removal)")
_CLUSTER_CONSENSUS = telemetry.gauge(
    "cluster_consensus", "1 when every live member reports our cloud hash")
_HEARTBEATS = telemetry.counter(
    "cluster_heartbeats_total", "heartbeats exchanged",
    labels=("direction", "result"),
)
_SUSPICIONS = telemetry.counter(
    "cluster_suspicions_total", "members marked suspect (missed beats)")
_REMOVALS = telemetry.counter(
    "cluster_removals_total", "members removed from the cloud")
_REJOINS = telemetry.counter(
    "cluster_rejoins_total",
    "fenced members that completed the 410 -> rejoin handshake and "
    "re-entered the cloud")
_SCRAPE_ERRORS = telemetry.counter(
    "metrics_scrape_errors_total",
    "cluster-wide metric/timeline scrapes that could not reach a member "
    "(the federation degrades to partial=true instead of 5xx-ing)",
    labels=("node", "method"),
)


class CloudJoinError(Exception):
    """Joining the cloud was rejected (duplicate name, wrong cloud...);
    carries the rejecting node's HTTP-ish code for a clear 4xx surface."""

    def __init__(self, msg: str, code: int = 400) -> None:
        super().__init__(msg)
        self.code = code


@dataclasses.dataclass(frozen=True)
class NodeInfo:
    """Identity of one node: name + RPC address (+ advertised REST port)."""

    name: str
    host: str
    port: int
    client: bool = False
    rest_port: int = 0

    @property
    def addr(self) -> Tuple[str, int]:
        return (self.host, self.port)

    @property
    def ident(self) -> str:
        return f"{self.name}@{self.host}:{self.port}"

    def to_dict(self) -> Dict[str, Any]:
        return dataclasses.asdict(self)

    @staticmethod
    def from_dict(d: Dict[str, Any]) -> "NodeInfo":
        return NodeInfo(
            name=str(d["name"]), host=str(d["host"]), port=int(d["port"]),
            client=bool(d.get("client", False)),
            rest_port=int(d.get("rest_port", 0)),
        )


class Member:
    """One cloud member as this node sees it: identity + freshest
    HeartBeat payload + liveness bookkeeping."""

    def __init__(self, info: NodeInfo, now: Optional[float] = None) -> None:
        self.info = info
        self.last_heard = now if now is not None else time.monotonic()
        self.stats: Dict[str, Any] = {}
        self.reported_hash: Optional[str] = None
        self.reported_version: int = 0
        self.healthy = True
        #: EWMA clock-skew estimate (peer wall clock minus ours, ms) and
        #: heartbeat RTT — sampled on every beat via the response timestamp
        #: midpointed against the send/receive instants (Cristian's method);
        #: the merged cluster timeline aligns remote events with it
        self.clock_skew_ms: Optional[float] = None
        self.rtt_ms: Optional[float] = None

    def heartbeat_age(self) -> float:
        return time.monotonic() - self.last_heard

    def observe_clock(self, peer_now_ms: float, t_sent: float,
                      t_received: float) -> None:
        """Fold one (send wall-time, receive wall-time, peer wall-time)
        triple into the skew/RTT estimates.  EWMA (alpha 0.3) smooths
        scheduler jitter; accuracy is bounded by RTT asymmetry — good to a
        few ms on a LAN, which is what aligning timeline events needs."""
        rtt_ms = max(0.0, (t_received - t_sent) * 1000.0)
        skew_ms = float(peer_now_ms) - (t_sent + t_received) / 2.0 * 1000.0
        if self.rtt_ms is None or self.clock_skew_ms is None:
            self.rtt_ms = rtt_ms
            self.clock_skew_ms = skew_ms
        else:
            self.rtt_ms = 0.7 * self.rtt_ms + 0.3 * rtt_ms
            self.clock_skew_ms = 0.7 * self.clock_skew_ms + 0.3 * skew_ms


def cpu_ticks_payload() -> Dict[str, Any]:
    """Host CPU tick counters (api/WaterMeterCpuTicksHandler.java:6) —
    shared by the local REST handler, the heartbeat payload and the
    cross-node RPC proxy so all three report identical shapes."""
    try:
        with open("/proc/stat") as f:
            first = f.readline().split()
    except OSError:  # non-Linux host: degrade gracefully, not a 500
        return {"cpu_ticks": [], "columns": [], "available": False}
    ticks = [int(x) for x in first[1:8]]
    return {"cpu_ticks": [ticks], "columns": [
        "user", "nice", "system", "idle", "iowait", "irq", "softirq"
    ], "available": True}


def _routable_host() -> str:
    """Best-effort routable address for a wildcard bind: the source
    address the kernel would pick for an outbound dial (a connected UDP
    socket sends no packets)."""
    try:
        s = socket.socket(socket.AF_INET, socket.SOCK_DGRAM)
        try:
            s.connect(("10.255.255.255", 1))
            return s.getsockname()[0]
        finally:
            s.close()
    except OSError:
        return "127.0.0.1"


def _free_mem_bytes() -> int:
    try:
        with open("/proc/meminfo") as f:
            for line in f:
                if line.startswith("MemAvailable:"):
                    return int(line.split()[1]) * 1024
    except OSError:
        pass
    return 0


class Cloud:
    """This node's view of the application-plane cloud.

    One instance per process (``set_local_cloud``); a cloud of size 1 is
    indistinguishable from no cloud to every wired call path.
    """

    def __init__(
        self,
        cloud_name: str,
        node_name: str,
        host: str = "127.0.0.1",
        port: int = 0,
        client: bool = False,
        rest_port: int = 0,
        hb_interval: Optional[float] = None,
        suspect_beats: Optional[int] = None,
        advertise_host: Optional[str] = None,
    ) -> None:
        self.cloud_name = cloud_name
        self.hb_interval = hb_interval if hb_interval is not None else float(
            os.environ.get("H2O3_TPU_HB_INTERVAL", 1.0))
        self.suspect_beats = suspect_beats if suspect_beats is not None else int(
            os.environ.get("H2O3_TPU_HB_SUSPECT", 5))
        self.rpc_server = _rpc.RpcServer(host=host, port=port,
                                         node_name=node_name)
        self.client = _rpc.RpcClient(node_name=node_name)
        # bind host and advertised host are distinct: a wildcard bind
        # (0.0.0.0 in a pod) must still gossip an address peers can dial
        if advertise_host is None:
            advertise_host = host
        if advertise_host in ("0.0.0.0", "::", ""):
            advertise_host = _routable_host()
        self.info = NodeInfo(
            name=node_name, host=advertise_host,
            port=self.rpc_server.address[1],
            client=client, rest_port=rest_port,
        )
        self.version = 1
        self.start_time = time.time()
        self._lock = threading.RLock()
        self._members: Dict[str, Member] = {node_name: Member(self.info)}
        #: removed member name -> cloud version at removal (the fence)
        self._tombstones: Dict[str, int] = {}
        self._seeds: List[Tuple[str, int]] = []
        self._needs_rejoin = False
        self._stopping = threading.Event()
        self._hb_thread: Optional[threading.Thread] = None
        #: monotonic stamp of the last COMPLETED gossip cycle — the
        #: heartbeat_overrun watchdog's only input from this class
        self.last_cycle_mono: Optional[float] = None
        #: per-gossip-cycle callbacks (bounded anti-entropy piggybacks)
        self._cycle_hooks: List[Any] = []
        self.rpc_server.register("heartbeat", self._on_heartbeat)
        self.rpc_server.register("ping", lambda p: {
            "pong": True, "name": self.info.name})
        self.rpc_server.register("echo", lambda p: p)
        self.rpc_server.register("cpu_ticks", lambda p: cpu_ticks_payload())
        self.rpc_server.register("logs", self._on_logs)
        self.rpc_server.register("metrics", lambda p: (
            telemetry.REGISTRY.summary()))
        self.rpc_server.register("metrics_snapshot", self._on_metrics_snapshot)
        self.rpc_server.register("timeline_snapshot", self._on_timeline_snapshot)
        self.rpc_server.register("profiler_snapshot", self._on_profiler_snapshot)
        self.rpc_server.register("trace_ledger", self._on_trace_ledger)
        self.rpc_server.register("diagnostics_snapshot",
                                 self._on_diagnostics_snapshot)
        self.rpc_server.register("members", lambda p: {
            "members": [m.info.ident for m in self.members_sorted()],
            "hash": self.cloud_hash(),
            "version": self.version,
            "consensus": self.consensus(),
            "size": self.size(),
        })
        if _faults.surface_enabled():
            self.enable_fault_surface()
        _CLUSTER_SIZE.set(1)
        _CLUSTER_VERSION.set(self.version)

    # -- views ---------------------------------------------------------------
    def size(self) -> int:
        with self._lock:
            return len(self._members)

    def members_sorted(self) -> List[Member]:
        """Members in the canonical order (by ident) — node index ``i`` in
        ``/3/Logs/nodes/{i}`` and key-home arithmetic both refer to it."""
        with self._lock:
            return sorted(self._members.values(), key=lambda m: m.info.ident)

    def cloud_hash(self) -> str:
        """Digest of the sorted member list — Paxos's agreement object:
        two nodes are in the same cloud iff their hashes match."""
        idents = ";".join(m.info.ident for m in self.members_sorted())
        return hashlib.md5(
            f"{self.cloud_name}|{idents}".encode()).hexdigest()

    def consensus(self) -> bool:
        """True when every OTHER live member has reported our hash."""
        ours = self.cloud_hash()
        with self._lock:
            others = [m for m in self._members.values()
                      if m.info.name != self.info.name]
        ok = all(m.reported_hash == ours for m in others)
        _CLUSTER_CONSENSUS.set(1 if ok else 0)
        return ok

    def local_member(self) -> Member:
        with self._lock:
            return self._members[self.info.name]

    def add_cycle_hook(self, fn) -> None:
        """Run ``fn()`` once per gossip cycle, after suspicion/consensus
        — the piggyback point for bounded anti-entropy work (the DKV
        replica sweep rides it).  A hook that raises is logged and kept;
        it must never kill the heartbeat loop."""
        self._cycle_hooks.append(fn)

    def enable_fault_surface(self) -> None:
        """Register the test-only nemesis RPC methods so multi-process
        chaos harnesses can script faults on (and crash) a live node.
        Called automatically when ``H2O3_TPU_FAULTS=1`` or a fault-plan
        env is present; never in production boots."""
        def _set(p: Optional[Dict[str, Any]]) -> Dict[str, Any]:
            plan = _faults.plan_from_dict(p or {})
            _faults.set_plan(plan)
            return {"installed": True, "seed": plan.seed,
                    "rules": len(plan.rules)}

        def _get(p: Any) -> Dict[str, Any]:
            plan = _faults.active_plan()
            return {"plan": plan.to_dict() if plan is not None else None,
                    "hits": plan.hits() if plan is not None else []}

        def _clear(p: Any) -> Dict[str, Any]:
            _faults.clear_plan()
            return {"cleared": True}

        def _crash(p: Optional[Dict[str, Any]]) -> Dict[str, Any]:
            # ack first, die a beat later: the nemesis learns its kill
            # LANDED rather than inferring it from a connection error
            delay = float((p or {}).get("delay_s", 0.05))
            threading.Timer(delay, _faults.crash_now).start()
            return {"crashing": True, "delay_s": delay}

        self.rpc_server.register("fault_plan_set", _set)
        self.rpc_server.register("fault_plan_get", _get)
        self.rpc_server.register("fault_plan_clear", _clear)
        self.rpc_server.register("fault_crash", _crash)

    def advertise_rest_port(self, port: int) -> None:
        """Publish this node's REST port into its member info (gossip
        carries it to the rest of the cloud) — the REST server binds
        after the cloud forms when both use OS-assigned ports."""
        with self._lock:
            self.info = dataclasses.replace(self.info, rest_port=int(port))
            m = self._members.get(self.info.name)
            if m is not None:
                m.info = self.info

    def member_schemas(self) -> List[Dict[str, Any]]:
        """The /3/Cloud ``nodes`` array (CloudV3.NodeV3 analogue)."""
        leader = self.members_sorted()[0].info.name if self.size() else None
        out = []
        for m in self.members_sorted():
            is_self = m.info.name == self.info.name
            out.append({
                "h2o": f"{m.info.host}:{m.info.port}",
                "ip_port": f"{m.info.host}:{m.info.rest_port or m.info.port}",
                "name": m.info.name,
                "healthy": bool(m.healthy),
                "last_heartbeat_age_ms": 0 if is_self else int(
                    m.heartbeat_age() * 1000),
                "client": m.info.client,
                "leader": m.info.name == leader,
                "rest_port": m.info.rest_port,
                "free_mem": m.stats.get("free_mem", 0),
                "dkv_bytes": m.stats.get("dkv_bytes", 0),
                "dkv_keys": m.stats.get("dkv_keys", 0),
                "num_cpus": m.stats.get("num_cpus", 0),
                "sys_cpu_ticks": m.stats.get("cpu_ticks", []),
                "clock_skew_ms": (0.0 if is_self else m.clock_skew_ms),
                "rtt_ms": (0.0 if is_self else m.rtt_ms),
            })
        return out

    # -- lifecycle -----------------------------------------------------------
    def start(self, seeds: Optional[List[Tuple[str, int]]] = None) -> "Cloud":
        """Begin gossip.  ``seeds`` (the flatfile) are addresses to court
        until they answer; the FIRST round runs synchronously so a coded
        rejection (duplicate name: 409, wrong cloud: 400) surfaces as
        :class:`CloudJoinError` at the launcher instead of a silent
        hash-mismatch stall."""
        with self._lock:
            self._seeds = [s for s in (seeds or [])
                           if s != self.info.addr]
        for addr in list(self._seeds):
            try:
                self._beat_one(addr, timeout=max(2.0, self.hb_interval * 2))
            except _rpc.RemoteError as e:
                if e.code == 410:
                    # a restarted node wearing a tombstoned name: adopt
                    # the cloud's epoch and rejoin rather than die
                    self._adopt_fence(e)
                    try:
                        self._beat_one(
                            addr, timeout=max(2.0, self.hb_interval * 2))
                    except _rpc.RPCError:
                        pass  # the periodic loop finishes the rejoin
                elif 400 <= e.code < 500:
                    raise CloudJoinError(
                        f"cloud join rejected by {addr[0]}:{addr[1]}: "
                        f"{e.msg}", code=e.code) from e
            except _rpc.RPCError:
                pass  # seed not up yet: the periodic loop keeps courting it
        self.last_cycle_mono = time.monotonic()  # arm heartbeat_overrun
        self._hb_thread = threading.Thread(
            target=self._hb_loop, daemon=True,
            name=f"heartbeat-{self.info.name}")
        self._hb_thread.start()
        return self

    def stop(self) -> None:
        self._stopping.set()
        self.rpc_server.stop()
        self.client.close()

    # -- heartbeat plumbing --------------------------------------------------
    def _hb_stats(self) -> Dict[str, Any]:
        """The HeartBeat payload (water/HeartBeat.java fields that still
        mean something here)."""
        try:
            from h2o3_tpu.keyed import DKV

            dkv_bytes = DKV.resident_frame_bytes()
            dkv_keys = len(DKV)
        except Exception:
            dkv_bytes, dkv_keys = 0, 0
        ticks = cpu_ticks_payload()
        return {
            "free_mem": _free_mem_bytes(),
            "dkv_bytes": dkv_bytes,
            "dkv_keys": dkv_keys,
            "cpu_ticks": ticks["cpu_ticks"][0] if ticks["cpu_ticks"] else [],
            "num_cpus": os.cpu_count() or 0,
            "client": self.info.client,
            "uptime_ms": int((time.time() - self.start_time) * 1000),
        }

    def _payload(self) -> Dict[str, Any]:
        with self._lock:
            members = [m.info.to_dict() for m in self._members.values()]
            version = self.version
            rejoin = self._needs_rejoin
        return {
            "cloud_name": self.cloud_name,
            "sender": self.info.to_dict(),
            "version": version,
            "hash": self.cloud_hash(),
            "members": members,
            "stats": self._hb_stats(),
            "rejoin": rejoin,
        }

    def _merge_members(self, infos: List[Dict[str, Any]],
                       direct_sender: Optional[NodeInfo] = None) -> bool:
        """Fold a peer's member list into ours.  Tombstoned names only
        come back via a DIRECT heartbeat from the node itself (a peer's
        stale gossip must not resurrect a removed member).  Returns True
        when membership changed.  Caller holds the lock."""
        changed = False
        for d in infos:
            try:
                info = NodeInfo.from_dict(d)
            except (KeyError, ValueError, TypeError):
                continue
            if info.name in self._tombstones and (
                    direct_sender is None or info.name != direct_sender.name):
                continue
            cur = self._members.get(info.name)
            if cur is None:
                self._tombstones.pop(info.name, None)
                self._members[info.name] = Member(info)
                changed = True
            elif cur.info.addr != info.addr and not cur.healthy:
                # a node that died and came back on a new ephemeral port
                # replaces its old registration (same name, fresh addr)
                self._members[info.name] = Member(info)
                changed = True
        return changed

    def _on_heartbeat(self, payload: Dict[str, Any]) -> Dict[str, Any]:
        """Server side of one gossip exchange."""
        if payload.get("cloud_name") != self.cloud_name:
            _HEARTBEATS.inc(direction="received", result="wrong_cloud")
            raise _rpc.RpcFault(
                f"wrong cloud: heartbeat for {payload.get('cloud_name')!r} "
                f"reached cloud {self.cloud_name!r}", code=400)
        sender = NodeInfo.from_dict(payload["sender"])
        peer_version = int(payload.get("version", 0))
        with self._lock:
            cur = self._members.get(sender.name)
            if (cur is not None and cur.info.addr != sender.addr
                    and cur.healthy
                    and cur.info.name != self.info.name):
                # two live nodes claiming one name can never agree on a
                # member list; reject the latecomer with a clear code
                # instead of letting hashes flap forever
                _HEARTBEATS.inc(direction="received", result="duplicate")
                raise _rpc.RpcFault(
                    f"duplicate node name {sender.name!r}: already held by "
                    f"{cur.info.ident}", code=409)
            if sender.name == self.info.name and sender.addr != self.info.addr:
                _HEARTBEATS.inc(direction="received", result="duplicate")
                raise _rpc.RpcFault(
                    f"duplicate node name {sender.name!r}: it is THIS "
                    f"node's name", code=409)
            fence = self._tombstones.get(sender.name)
            if (fence is not None and peer_version < self.version
                    and not payload.get("rejoin")):
                # stale member of a pre-removal epoch: fenced until it
                # acknowledges the current version and rejoins
                _HEARTBEATS.inc(direction="received", result="fenced")
                raise _rpc.RpcFault(
                    f"stale cloud version {peer_version} (cloud is at "
                    f"{self.version}); rejoin required", code=410,
                    detail={"version": self.version})
            changed = self._merge_members(
                payload.get("members", []), direct_sender=sender)
            if sender.name in self._tombstones:
                self._tombstones.pop(sender.name, None)
                self._members[sender.name] = Member(sender)
                changed = True
            m = self._members.get(sender.name)
            if m is not None:
                if m.info.addr == sender.addr:
                    # a node's DIRECT heartbeat is the authority on its
                    # own metadata — rest_port arrives only after the
                    # REST server binds, well after the join beat
                    m.info = sender
                m.last_heard = time.monotonic()
                m.healthy = True
                m.stats = payload.get("stats", {})
                m.reported_hash = payload.get("hash")
                m.reported_version = peer_version
            if changed or peer_version > self.version:
                self.version = max(self.version, peer_version) + (
                    1 if changed else 0)
            response = {
                "cloud_name": self.cloud_name,
                "receiver": self.info.to_dict(),
                "version": self.version,
                "hash": self.cloud_hash(),
                "members": [m.info.to_dict()
                            for m in self._members.values()],
                # wall clock at response build: the beating peer midpoints
                # it against its send/receive instants to estimate skew
                "now_ms": time.time() * 1000.0,
            }
        _HEARTBEATS.inc(direction="received", result="ok")
        self._publish_gauges()
        return response

    def _beat_one(self, addr: Tuple[str, int], timeout: float) -> None:
        """Client side of one gossip exchange with one peer.  Single
        attempt (``retries=0``): the periodic loop IS the retry, and a
        ladder here would serialize ~4 timeouts against one dead peer
        per cycle — long enough to starve healthy peers past the
        suspicion window and flap the whole cloud's health."""
        t_sent = time.time()
        resp = self.client.call(
            addr, "heartbeat", self._payload(),
            timeout=timeout, target=f"{addr[0]}:{addr[1]}", retries=0)
        t_received = time.time()
        _HEARTBEATS.inc(direction="sent", result="ok")
        receiver = NodeInfo.from_dict(resp["receiver"])
        with self._lock:
            changed = self._merge_members(
                resp.get("members", []), direct_sender=receiver)
            peer_version = int(resp.get("version", 0))
            m = self._members.get(receiver.name)
            if m is not None:
                if m.info.addr == receiver.addr:
                    m.info = receiver  # self-reported metadata refresh
                m.last_heard = time.monotonic()
                m.healthy = True
                m.reported_hash = resp.get("hash")
                m.reported_version = peer_version
                peer_now_ms = resp.get("now_ms")
                if peer_now_ms is not None:
                    m.observe_clock(float(peer_now_ms), t_sent, t_received)
            if changed or peer_version > self.version:
                self.version = max(self.version, peer_version) + (
                    1 if changed else 0)
            rejoined = self._needs_rejoin
            if self._needs_rejoin:
                # a fenced epoch just got acknowledged end-to-end: the
                # peer accepted our rejoin beat at the current version
                _REJOINS.inc()
            self._needs_rejoin = False
        if rejoined:
            _flight.record(_flight.MEMBERSHIP, "info", "rejoin",
                           peer=receiver.ident, version=self.version)

    def _beat_quietly(self, addr: Tuple[str, int]) -> None:
        """One peer's beat with every outcome metered, never raising —
        the per-peer unit the gossip cycle fans out."""
        try:
            self._beat_one(addr, timeout=max(1.0, self.hb_interval * 2))
        except _rpc.RemoteError as e:
            if e.code == 410:  # fenced: adopt the epoch, rejoin
                self._adopt_fence(e)
                _HEARTBEATS.inc(direction="sent", result="fenced")
            else:
                _HEARTBEATS.inc(direction="sent", result="rejected")
        except _rpc.RPCError:
            _HEARTBEATS.inc(direction="sent", result="unreachable")

    def _hb_loop(self) -> None:
        while not self._stopping.wait(self.hb_interval):
            with self._lock:
                targets = {
                    m.info.addr: m.info.ident
                    for m in self._members.values()
                    if m.info.name != self.info.name
                }
                for s in self._seeds:
                    targets.setdefault(s, f"{s[0]}:{s[1]}")
            # beat peers CONCURRENTLY: serially, each black-holed peer
            # would block the cycle a full timeout, and two of them push
            # the gap between beats to live members past the suspicion
            # window — dead nodes must not flap healthy ones
            beats = [
                threading.Thread(target=self._beat_quietly, args=(addr,),
                                 daemon=True, name=f"hb-{label}")
                for addr, label in targets.items()
            ]
            for t in beats:
                t.start()
            deadline = time.monotonic() + max(1.0, self.hb_interval * 2) + 0.5
            for t in beats:
                t.join(timeout=max(0.0, deadline - time.monotonic()))
            if self._stopping.is_set():
                return
            self._check_suspicion()
            self.consensus()
            self._publish_gauges()
            self.last_cycle_mono = time.monotonic()
            for hook in list(self._cycle_hooks):
                try:
                    hook()
                except Exception:  # noqa: BLE001 — hooks never kill gossip
                    from h2o3_tpu.util.log import get_logger

                    get_logger("cluster").warning(
                        "gossip cycle hook %r failed", hook, exc_info=True)

    def _adopt_fence(self, e: "_rpc.RemoteError") -> None:
        """A 410 fence carries the cloud's current version: adopt it and
        flag the next heartbeat as a rejoin so the fence opens."""
        with self._lock:
            self.version = max(
                self.version, int(e.detail.get("version", self.version)))
            self._needs_rejoin = True
        _flight.record(_flight.MEMBERSHIP, "warn", "fenced",
                       version=self.version)

    def _check_suspicion(self) -> None:
        """Missed-beat suspicion → removal (Paxos's failure detection):
        suspect after ``suspect_beats`` silent intervals, remove (and
        tombstone, bumping the cloud version) after twice that."""
        suspect_after = self.suspect_beats * self.hb_interval
        removed = []
        suspected = []
        with self._lock:
            for name, m in list(self._members.items()):
                if name == self.info.name:
                    continue
                age = m.heartbeat_age()
                if age > 2 * suspect_after:
                    del self._members[name]
                    self._tombstones[name] = self.version
                    self.version += 1
                    removed.append(m.info.ident)
                    _REMOVALS.inc()
                elif age > suspect_after and m.healthy:
                    m.healthy = False
                    suspected.append((m.info.ident, age))
                    _SUSPICIONS.inc()
        for ident, age in suspected:
            _flight.record(_flight.MEMBERSHIP, "warn", "suspect",
                           member=ident, silent_s=round(age, 2))
        for ident in removed:
            _flight.record(_flight.MEMBERSHIP, "error", "tombstone",
                           member=ident, version=self.version)
        if removed:
            from h2o3_tpu.util.log import get_logger

            get_logger("cluster").warning(
                "removed unresponsive member(s) %s; cloud version now %d",
                ", ".join(removed), self.version)

    def _publish_gauges(self) -> None:
        with self._lock:
            _CLUSTER_SIZE.set(len(self._members))
            _CLUSTER_VERSION.set(self.version)

    # -- built-in RPC methods -------------------------------------------------
    @staticmethod
    def _on_logs(payload: Optional[Dict[str, Any]]) -> Dict[str, Any]:
        from h2o3_tpu.util import log as L

        L.init()
        count = int((payload or {}).get("count", 10000))
        return {"lines": L.recent(count), "log_file": L.log_file()}

    def _on_metrics_snapshot(
            self, payload: Optional[Dict[str, Any]]) -> Dict[str, Any]:
        """Full registry snapshot (not the compact ``metrics`` summary) —
        the per-member half of ``GET /3/Metrics?cluster=true``."""
        return {
            "node": self.info.name,
            "metrics": telemetry.REGISTRY.snapshot(),
            "now_ms": time.time() * 1000.0,
        }

    def _on_timeline_snapshot(
            self, payload: Optional[Dict[str, Any]]) -> Dict[str, Any]:
        """This node's event ring — the per-member half of the merged
        cluster timeline (and the ``/3/Timeline/nodes/{i}`` proxy body)."""
        from h2o3_tpu.util import timeline

        out = timeline.snapshot_payload(
            int((payload or {}).get("count", 1000)))
        out["node"] = self.info.name
        return out

    def _on_profiler_snapshot(
            self, payload: Optional[Dict[str, Any]]) -> Dict[str, Any]:
        """Sample this node's Python stacks — the per-member half of
        ``GET /3/Profiler?cluster=true``.  Blocks for ``duration`` seconds
        (the caller's poll timeout must cover it)."""
        from h2o3_tpu.util import profiler

        p = payload or {}
        exclude = p.get("exclude")
        from h2o3_tpu.cluster import health as _health

        return {
            "node": self.info.name,
            "exclude": exclude,
            # the serving node's watchdog verdict rides the existing
            # payload — one scrape answers "is this node ok", no 2nd RPC
            "health": _health.summary(),
            "profile": profiler.collect(
                duration_s=float(p.get("duration", 0.25)),
                depth=int(p.get("depth", 10)),
                exclude=exclude or None,
            ),
        }

    def _on_diagnostics_snapshot(
            self, payload: Optional[Dict[str, Any]]) -> Dict[str, Any]:
        """This node's diagnostics bundle — the per-member half of
        ``GET /3/Diagnostics?cluster=true`` (knobs, verdicts, last-K
        flight events, worst SlowOps, membership view, thread stacks)."""
        from h2o3_tpu.cluster import health as _health

        return _health.diagnostics_snapshot(
            cloud=self, events=int((payload or {}).get("events", 200)))

    def _on_trace_ledger(
            self, payload: Optional[Dict[str, Any]]) -> Dict[str, Any]:
        """This node's cost-ledger entry for one trace — the per-member
        half of ``GET /3/Traces/{trace_id}``.  ``ledger: None`` when the
        trace never charged anything here (absence is data, not error)."""
        from h2o3_tpu.util import ledger as _ledger_mod

        tid = str((payload or {}).get("trace_id", ""))
        return {
            "node": self.info.name,
            "trace_id": tid,
            "ledger": _ledger_mod.LEDGER.get(tid) if tid else None,
        }

    # -- cluster-wide scrape fan-out ------------------------------------------
    def poll_members(
        self,
        method: str,
        payload: Any = None,
        timeout: float = 5.0,
    ) -> Tuple[Dict[str, Any], Dict[str, str]]:
        """Fan one built-in RPC to every cloud member concurrently and
        return ``(results, errors)`` keyed by member name.

        The local node answers in-process (no loopback RPC, no dedup memo
        churn).  A member that cannot be reached — or does not answer
        inside the deadline — lands in ``errors`` and bumps
        ``metrics_scrape_errors_total{node,method}``; it never raises, so
        the REST federation degrades to ``partial: true`` instead of a
        5xx.  One bounded retry per member (``retries=1``): an HTTP worker
        is usually waiting on the merge."""
        members = self.members_sorted()
        # workers write ONLY their own slot (a single reference
        # assignment); results/errors are built from a one-shot snapshot
        # of the slots after the join deadline, so a straggler thread that
        # answers late mutates nothing the caller is iterating — the
        # federation endpoints keep their never-5xx contract even against
        # a peer that dribbles bytes past every timeout
        slots: List[Optional[Tuple[str, Any]]] = [None] * len(members)

        def _one(i: int, m: Member) -> None:
            if m.info.name == self.info.name:
                fn = self.rpc_server._methods.get(method)
                try:
                    if fn is None:
                        raise KeyError(f"unknown RPC method {method!r}")
                    slots[i] = ("ok", fn(payload))
                except Exception as e:  # noqa: BLE001 — degrade, don't 5xx
                    slots[i] = ("err", f"{type(e).__name__}: {e}")
                return
            try:
                slots[i] = ("ok", self.client.call(
                    m.info.addr, method, payload,
                    timeout=timeout, target=m.info.ident, retries=1))
            except _rpc.RPCError as e:
                slots[i] = ("err", str(e))

        threads = [threading.Thread(target=_one, args=(i, m), daemon=True,
                                    name=f"scrape-{m.info.name}")
                   for i, m in enumerate(members)]
        for t in threads:
            t.start()
        deadline = time.monotonic() + 2 * timeout + 1.0
        for t in threads:
            t.join(timeout=max(0.0, deadline - time.monotonic()))
        results: Dict[str, Any] = {}
        errors: Dict[str, str] = {}
        for m, slot in zip(members, list(slots)):  # one-shot snapshot
            name = m.info.name
            if slot is None:
                errors[name] = f"no answer within {timeout}s"
            elif slot[0] == "ok":
                results[name] = slot[1]
            else:
                errors[name] = slot[1]
            if name in errors:
                _SCRAPE_ERRORS.inc(node=name, method=method)
        return results, errors


# ---------------------------------------------------------------------------
# process-global cloud (the H2O.CLOUD static)

_LOCAL: Optional[Cloud] = None
_LOCAL_LOCK = threading.Lock()


def local_cloud() -> Optional[Cloud]:
    return _LOCAL


def set_local_cloud(cloud: Optional[Cloud]) -> None:
    global _LOCAL
    with _LOCAL_LOCK:
        _LOCAL = cloud


def boot_node(
    cloud_name: str,
    node_name: str,
    *,
    host: str = "127.0.0.1",
    port: int = 0,
    client: bool = False,
    hb_interval: Optional[float] = None,
    flatfile: Optional[str] = None,
    address_file: Optional[str] = None,
    store=None,
) -> Cloud:
    """One-call cluster-node bootstrap shared by the REST launcher
    (``__main__``) and the light ``nodeproc`` harness:
    construct the Cloud, install the DKV router and DTask registry,
    publish it as the process cloud, write the resolved RPC address
    atomically, and run the synchronous join round.  On
    :class:`CloudJoinError` the node is already stopped and unpublished
    before the error propagates."""
    from h2o3_tpu.cluster import dkv as _dkv
    from h2o3_tpu.cluster import tasks as _tasks

    # a plan shipped via H2O3_TPU_FAULT_PLAN must be live before the
    # first join beat — chaos scenarios fault the join itself
    _faults.install_from_env()
    cloud = Cloud(cloud_name, node_name, host=host, port=port,
                  client=client, hb_interval=hb_interval)
    # declare the process's trace identity: every timeline event this node
    # records from here on carries node=<name>, so merged cluster timelines
    # and propagated traces attribute work to the member that did it
    telemetry.set_node_name(node_name)
    _dkv.install(cloud, store)
    _tasks.install(cloud)
    set_local_cloud(cloud)
    if address_file:
        tmp = address_file + ".tmp"
        with open(tmp, "w") as f:
            f.write(f"{cloud.info.host}:{cloud.info.port}\n")
        os.replace(tmp, address_file)  # atomic: readers never see half
    seeds = parse_flatfile(flatfile) if flatfile else []
    try:
        cloud.start(seeds)
    except CloudJoinError:
        cloud.stop()
        set_local_cloud(None)
        raise
    # the node's watchdog thread + crash hooks come up with the cloud
    # (H2O3_TPU_HEALTH=0 leaves the monitor idle)
    from h2o3_tpu.cluster import health as _health

    _health.start(node=node_name)
    return cloud


def parse_flatfile(path: str) -> List[Tuple[str, int]]:
    """Flatfile lines -> RPC addresses.  The reference's ``-flatfile``
    format: one ``host:port`` per line, ``#`` comments and blanks
    ignored."""
    seeds: List[Tuple[str, int]] = []
    with open(path) as f:
        for line in f:
            line = line.split("#", 1)[0].strip()
            if not line:
                continue
            host, _, port = line.rpartition(":")
            if not host:
                raise ValueError(
                    f"flatfile line {line!r} is not host:port")
            seeds.append((host, int(port)))
    return seeds
