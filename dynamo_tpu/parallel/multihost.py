"""Multi-host workers: one engine spanning TPU hosts via jax.distributed.

The reference reaches multi-node scale by delegating to vLLM's headless
Ray mode — secondary nodes run engine processes with no Dynamo endpoints
(ref: components/src/dynamo/vllm/main.py:79-110 run_dynamo_headless).
The TPU equivalent is multi-controller JAX: every host runs the same SPMD
programs over one global mesh, and XLA moves data over ICI/DCN.

Design: rank 0 is the DRIVER — it owns the scheduler, the distributed
runtime, and the serving endpoints, exactly like a single-host worker.
Ranks 1..N-1 are FOLLOWERS — engine-only processes with no endpoints.
Multi-controller JAX requires every process to enqueue the same programs
in the same order, so the driver wraps its ModelRunner in a
`MirroredRunner`: each host-API call (prefill_chunk / decode / ...)
is broadcast over a TCP step channel before running locally, and each
follower replays it verbatim against its own identical runner. All
arguments at this boundary are numpy/scalars by construction (the
runner's host API), so plans serialize without pickle.

Why this works without consensus machinery:
  * the runner's compiled steps are deterministic given their host args,
    so replicated outputs (sampled tokens) are identical on every host —
    followers never need to report anything back;
  * program ORDER is the only invariant XLA needs; a single mutex around
    (publish + local dispatch) on the driver and a single-threaded replay
    loop on followers preserve it;
  * an ack window bounds follower lag (flow control), and any follower
    error tears the worker down loudly — a diverged SPMD program must
    never keep serving.
"""

from __future__ import annotations

import dataclasses
import socket
import struct
import threading
from typing import Optional

import msgpack
import numpy as np

from ..runtime.logging import get_logger

log = get_logger("parallel.multihost")

_ACK_WINDOW = 64
_CLOSE = "__close__"


# ---------------------------------------------------------------------------
# Config / initialize
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class MultihostConfig:
    coordinator: str  # host:port for the jax.distributed coordinator
    num_processes: int
    process_id: int
    # step-plan channel: rank 0 listens on the coordinator host at
    # coordinator port + 1 unless overridden
    plan_address: str = ""

    @classmethod
    def parse(cls, spec: str) -> "MultihostConfig":
        """Parse "R/N@host:port" (e.g. "0/2@10.0.0.1:8476")."""
        try:
            rank_part, addr = spec.split("@", 1)
            rank_s, n_s = rank_part.split("/", 1)
            host, port_s = addr.rsplit(":", 1)
            return cls(coordinator=f"{host}:{int(port_s)}",
                       num_processes=int(n_s), process_id=int(rank_s))
        except (ValueError, IndexError) as exc:
            raise ValueError(
                f"bad --multihost spec {spec!r} (want R/N@host:port)"
            ) from exc

    @property
    def plan_host_port(self) -> tuple[str, int]:
        if self.plan_address:
            host, port_s = self.plan_address.rsplit(":", 1)
            return host, int(port_s)
        host, port_s = self.coordinator.rsplit(":", 1)
        return host, int(port_s) + 1

    @property
    def is_driver(self) -> bool:
        return self.process_id == 0


def initialize(cfg: MultihostConfig) -> None:
    """jax.distributed.initialize (must run before the first backend
    touch). On the CPU backend the cross-process collectives
    implementation is gloo."""
    import jax

    platforms = jax.config.jax_platforms or ""
    if "cpu" in platforms:
        jax.config.update("jax_cpu_collectives_implementation", "gloo")
    jax.distributed.initialize(
        coordinator_address=cfg.coordinator,
        num_processes=cfg.num_processes,
        process_id=cfg.process_id,
    )
    log.info("multihost process %d/%d up: %d global / %d local devices",
             cfg.process_id, cfg.num_processes,
             jax.device_count(), jax.local_device_count())


# ---------------------------------------------------------------------------
# Plan codec (msgpack + explicit numpy tagging; no pickle on the wire)
# ---------------------------------------------------------------------------


def _enc(obj):
    if isinstance(obj, np.ndarray):
        return {"__nd__": 1, "d": obj.dtype.str if obj.dtype.kind != "V"
                else obj.dtype.name, "s": list(obj.shape),
                "b": np.ascontiguousarray(obj).tobytes()}
    if isinstance(obj, np.generic):
        return {"__ns__": 1, "d": np.dtype(obj.dtype).name,
                "v": obj.item()}
    if isinstance(obj, tuple):
        return {"__tu__": 1, "v": [_enc(x) for x in obj]}
    if isinstance(obj, list):
        return [_enc(x) for x in obj]
    if isinstance(obj, dict):
        return {str(k): _enc(v) for k, v in obj.items()}
    if obj is None or isinstance(obj, (bool, int, float, str, bytes)):
        return obj
    raise TypeError(f"cannot encode {type(obj).__name__} into a step plan")


def _dec(obj):
    if isinstance(obj, dict):
        if obj.get("__nd__") == 1:
            arr = np.frombuffer(obj["b"], dtype=np.dtype(obj["d"]))
            return arr.reshape(obj["s"])
        if obj.get("__ns__") == 1:
            return np.dtype(obj["d"]).type(obj["v"])
        if obj.get("__tu__") == 1:
            return tuple(_dec(x) for x in obj["v"])
        return {k: _dec(v) for k, v in obj.items()}
    if isinstance(obj, list):
        return [_dec(x) for x in obj]
    return obj


def _send_frame(sock: socket.socket, msg: dict) -> None:
    data = msgpack.packb(msg, use_bin_type=True)
    sock.sendall(struct.pack(">I", len(data)) + data)


def _recv_frame(sock: socket.socket) -> Optional[dict]:
    header = b""
    while len(header) < 4:
        part = sock.recv(4 - len(header))
        if not part:
            return None
        header += part
    (n,) = struct.unpack(">I", header)
    chunks: list[bytes] = []
    got = 0
    while got < n:
        part = sock.recv(min(1 << 20, n - got))
        if not part:
            return None
        chunks.append(part)
        got += len(part)
    return msgpack.unpackb(b"".join(chunks), raw=False)


# ---------------------------------------------------------------------------
# Step channel (driver side)
# ---------------------------------------------------------------------------


class _FollowerConn:
    def __init__(self, sock: socket.socket, peer: str) -> None:
        self.sock = sock
        self.peer = peer
        self.outstanding = threading.Semaphore(_ACK_WINDOW)
        self.error: Optional[str] = None
        self._reader = threading.Thread(target=self._read_acks,
                                        daemon=True,
                                        name=f"mh-acks-{peer}")
        self._reader.start()

    def _read_acks(self) -> None:
        try:
            while True:
                msg = _recv_frame(self.sock)
                if msg is None:
                    self.error = self.error or "follower closed connection"
                    break
                if not msg.get("ok", False):
                    self.error = msg.get("err", "follower error")
                    log.error("follower %s failed: %s", self.peer,
                              self.error)
                    break
                self.outstanding.release()
        except OSError as exc:
            self.error = self.error or repr(exc)
        finally:
            # Unblock any publisher stuck on the window.
            for _ in range(_ACK_WINDOW):
                self.outstanding.release()


class StepChannel:
    """Rank 0's fan-out of runner calls to follower processes."""

    def __init__(self, host: str, port: int, n_followers: int) -> None:
        from ..runtime.config import env

        self.n_followers = n_followers
        # Bound on how long a follower may sit on a full ack window
        # without acking anything. A follower that hangs without
        # erroring (e.g. a stuck collective) must tear the driver down
        # loudly, not block its scheduler thread forever. Followers ack
        # a step only after executing it, so the default (10 min) must
        # stay above the slowest cold XLA compile a follower can hit.
        self.publish_timeout = float(
            env("DYNT_MULTIHOST_PUBLISH_TIMEOUT_SECS"))
        self._conns: list[_FollowerConn] = []
        self._server = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        self._server.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        self._server.bind((host, port))
        self._server.listen(max(1, n_followers))

    def wait_for_followers(self, timeout: float = 300.0) -> None:
        self._server.settimeout(timeout)
        while len(self._conns) < self.n_followers:
            conn, addr = self._server.accept()
            conn.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
            self._conns.append(_FollowerConn(conn, f"{addr[0]}:{addr[1]}"))
            log.info("follower %d/%d connected from %s",
                     len(self._conns), self.n_followers, self._conns[-1].peer)
        self._server.close()

    def publish(self, method: str, args: tuple, kwargs: dict) -> None:
        timeout = self.publish_timeout
        frame = {"m": method, "a": _enc(list(args)), "k": _enc(kwargs)}
        for conn in self._conns:
            if conn.error:
                raise RuntimeError(
                    f"multihost follower {conn.peer} failed: {conn.error} "
                    "— the SPMD program has diverged; restart the worker")
            if not conn.outstanding.acquire(timeout=timeout):
                conn.error = conn.error or (
                    f"no ack for {timeout:.0f}s "
                    f"(window {_ACK_WINDOW} full, last method {method!r})")
                raise RuntimeError(
                    f"multihost follower {conn.peer} hung: {conn.error} "
                    "— the SPMD program has diverged; restart the worker")
            _send_frame(conn.sock, frame)

    def close(self) -> None:
        import time

        for conn in self._conns:
            # Drain outstanding acks first: closing the socket while a
            # follower's final ack is in flight resets its connection and
            # turns a clean shutdown into a follower crash.
            deadline = time.monotonic() + 10.0
            drained = 0
            while drained < _ACK_WINDOW and time.monotonic() < deadline:
                if conn.error:
                    break
                if conn.outstanding.acquire(timeout=0.1):
                    drained += 1
            try:
                _send_frame(conn.sock, {"m": _CLOSE, "a": [], "k": {}})
                conn.sock.close()
            except OSError:
                pass
        self._conns.clear()


# ---------------------------------------------------------------------------
# MirroredRunner (driver) / replay loop (followers)
# ---------------------------------------------------------------------------

# The runner host-API surface that launches device programs. Everything
# here takes numpy/scalar args only. Program ORDER across processes is
# the SPMD invariant — one lock spans publish + local dispatch.
MIRRORED_METHODS = (
    "prefill_chunk",
    "prefill_ring",
    "prefill_ring_batch",
    "decode",
    "decode_multi",
    "embed",
    "warmup",
    "gather_pages",
    "gather_pages_device",
    "scatter_pages",
    "clear_lora_slot",
    # Distributed KVBM (block_manager/distributed.py): every rank moves
    # its own shards; the leader only plans.
    "kvbm_store_shards",
    "kvbm_load_shards",
)


class MirroredRunner:
    """Wraps the driver's ModelRunner: every device-program launch is
    broadcast to followers first (under one lock, so the channel order
    equals the local enqueue order), then dispatched locally. Non-compute
    attributes pass through."""

    # Schedulers check this to disable device-resident token chaining
    # (decode pipeline depth > 1): a jax.Array argument cannot travel
    # the step channel, so chained blocks would force a host sync here
    # anyway — better to choose depth 1 up front.
    is_mirrored = True

    def __init__(self, runner, channel: StepChannel) -> None:
        self._runner = runner
        self._channel = channel
        self._lock = threading.Lock()

    @staticmethod
    def _to_host(obj):
        """Device arrays can't be encoded into a step plan — force the
        readback (correctness net; the scheduler avoids this path on
        mirrored runners)."""
        if isinstance(obj, np.ndarray) or not hasattr(obj, "__array__"):
            return obj
        return np.asarray(obj)

    def __getattr__(self, name: str):
        target = getattr(self._runner, name)
        if name not in MIRRORED_METHODS:
            return target

        def mirrored(*args, **kwargs):
            if name == "gather_pages_device":
                # Cross-host bundles must be replicated or no single
                # process can read them back; force it consistently on
                # driver AND followers (the kwarg travels in the plan).
                kwargs.setdefault("replicated", True)
            args = tuple(self._to_host(a) for a in args)
            kwargs = {k: self._to_host(v) for k, v in kwargs.items()}
            with self._lock:
                self._channel.publish(name, args, kwargs)
                return target(*args, **kwargs)

        return mirrored

    # cache / params are read by transfer paths via attribute access —
    # __getattr__ already forwards them. Assignment must hit the inner
    # runner, not this wrapper:
    def __setattr__(self, name, value):
        if name.startswith("_"):
            object.__setattr__(self, name, value)
        else:
            setattr(self._runner, name, value)

    def close_channel(self) -> None:
        self._channel.close()


def follower_serve(runner, cfg: MultihostConfig,
                   connect_timeout: float = 300.0) -> None:
    """Follower main loop: replay the driver's runner calls in order.
    Blocks until the driver closes the channel. Raises on any replay
    error (a diverged follower must die loudly, not serve garbage)."""
    import time

    host, port = cfg.plan_host_port
    deadline = time.monotonic() + connect_timeout
    sock = None
    while True:
        try:
            sock = socket.create_connection((host, port), timeout=5.0)
            break
        except OSError:
            if time.monotonic() > deadline:
                raise TimeoutError(
                    f"could not reach driver step channel at {host}:{port}")
            time.sleep(0.2)
    sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
    sock.settimeout(None)
    log.info("follower %d connected to driver step channel", cfg.process_id)
    try:
        while True:
            msg = _recv_frame(sock)
            if msg is None or msg["m"] == _CLOSE:
                log.info("step channel closed; follower exiting")
                return
            method = msg["m"]
            if method not in MIRRORED_METHODS:
                _send_frame(sock, {"ok": False,
                                   "err": f"unknown method {method!r}"})
                raise RuntimeError(f"driver sent unknown method {method!r}")
            args = _dec(msg["a"])
            kwargs = _dec(msg["k"])
            try:
                getattr(runner, method)(*args, **kwargs)
            except Exception as exc:  # noqa: BLE001 — report then die
                _send_frame(sock, {"ok": False, "err": repr(exc)})
                raise
            try:
                _send_frame(sock, {"ok": True})
            except (ConnectionError, BrokenPipeError):
                # Driver shut down between its last plan and our ack:
                # a clean exit, not a divergence.
                log.info("driver closed during final ack; follower exiting")
                return
    finally:
        sock.close()
