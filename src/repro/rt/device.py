"""The CPSL device worker process.

One worker = one wireless device. Lifecycle:

  1. dial the server (retry/backoff), REGISTER, receive the PLAN
     (cut layer, seeds, round layout, data spec);
  2. rebuild its world deterministically from the plan — the synthetic
     dataset + non-IID shards (``data.synthetic``) and the device-side
     split model — so nothing bulky ships at startup;
  3. optional warmup: compile the forward/backward jits on dummy
     params/batches, then READY (keeps measured QoS clean of jit time);
  4. serve CLUSTER_STARTs: for each local epoch draw the same batch the
     in-process ``CPSLDataset.cluster_batch`` would draw (same
     ``batch_seed`` stream, same member order — bit-exactness), run the
     forward, ship SMASHED, await GRAD (timeout + exponential-backoff
     resend), run backward + optimizer step; after L epochs upload the
     device model (AGG) with piggybacked QoS records and await AGG_ACK.

The numerics are the *decomposed* protocol-step jits — device forward
(``device_apply``) and per-client backward (vjp + optimizer) — which
reproduce the monolithic ``CPSL._protocol_step`` bit-exactly on XLA:CPU
(pinned by tests/test_rt_loopback.py).

Placement: a worker always runs JAX on the CPU. ``device_main`` pins
the platform before any backend exists, so a worker never opens the
accelerator that the server process holds (one process per chip; edge
devices on the CPU, the edge server on the accelerator). READY reports
the worker's platform to the server.

Robustness: an ERROR reply (server dropped us as a straggler) or a new
CLUSTER_START mid-RPC aborts the current cluster and returns to the
main loop; SIGTERM (``repro.lifecycle.GracefulStop``) finishes the
in-flight RPC, sends BYE, and exits cleanly.

Elastic recovery: with ``reconnect`` enabled, losing the server
connection (server crash, not SHUTDOWN) does not end the worker — it
re-dials the server port with backoff and re-handshakes with REJOIN
instead of REGISTER, because its model/jits are already built; the
REJOIN_ACK tells it the committed round the resumed run continues
from, and it reports READY immediately (no rebuild, no warmup).
Fault rules are filtered by the worker's *incarnation* (respawn count,
passed by the orchestrator), so one-shot chaos faults don't re-fire in
a kill/respawn loop. RPC retry backoff is capped
(``lifecycle.retry_sleeps``) and the total retry budget is validated
against the server's straggler deadline at config time.
"""
from __future__ import annotations

import threading
import time
from collections import deque
from typing import List, Optional, Sequence

import numpy as np

from repro import streams
from repro.lifecycle import Backoff, GracefulStop, retry_sleeps
from repro.rt import protocol as pr
from repro.rt.faults import FaultInjector, FaultRule, InjectedDisconnect
from repro.rt.protocol import MsgType
from repro.rt.qos import QoSMonitor
from repro.rt.transport import Channel, RpcTimeout, connect_with_retry


def build_shards(spec: dict):
    """(images, labels, per-device index arrays) rebuilt deterministically
    from the plan's data spec — identical on server and every worker."""
    from repro.data.synthetic import non_iid_split, synthetic_mnist
    xtr, ytr, _, _ = synthetic_mnist(spec["n_train"], spec["n_test"],
                                     seed=spec["data_seed"])
    shards = non_iid_split(
        ytr, n_devices=spec["n_devices"],
        classes_per_device=spec.get("classes_per_device", 3),
        samples_per_device=spec["samples_per_device"],
        seed=spec["data_seed"])
    return xtr, ytr, shards


def member_batch_indices(device_indices, members: Sequence[int], B: int,
                         seed: int, rnd: int, m: int, l: int
                         ) -> List[np.ndarray]:
    """Per-member sample picks for (round, cluster, epoch) — entry for
    entry the draws ``CPSLDataset.cluster_batch(members,
    seed=batch_seed(seed, rnd, m, l))`` makes: one fresh ``default_rng``
    per (m, l), members drawn in slot order (draws are prefix-stable, so
    every worker reproduces the full cluster's stream and slices its own
    row; the server reuses the same picks for the labels)."""
    rng = streams.batch_rng(seed, rnd, m, l)
    picks = []
    for d in members:
        idx = device_indices[d]
        picks.append(rng.choice(idx, B, replace=len(idx) < B))
    return picks


class _Aborted(Exception):
    """Current cluster abandoned (server moved on / shutdown / error)."""


class DeviceWorker:
    def __init__(self, cfg: dict):
        self.cfg = cfg
        self.gid = int(cfg["device"])
        self.incarnation = int(cfg.get("incarnation", 0))
        self.injector = FaultInjector(
            [r for r in (FaultRule.from_dict(d)
                         for d in cfg.get("faults", []))
             if r.active_in(self.incarnation)])
        self.stop = GracefulStop().install()
        self.pending = deque()
        self.qos = QoSMonitor(device=self.gid)
        self._round: Optional[int] = None
        self._hb_stop = threading.Event()
        self.ch: Optional[Channel] = None

    # -- setup -----------------------------------------------------------

    def _connect_and_plan(self) -> dict:
        cfg = self.cfg
        sock = connect_with_retry(cfg["host"], cfg["port"],
                                  cfg.get("connect_timeout_s", 20.0))
        self.ch = Channel(sock, self.injector, round_fn=lambda: self._round)
        self.ch.send(MsgType.REGISTER, {"device": self.gid})
        mtype, plan = self.ch.recv(timeout=cfg.get("plan_timeout_s", 120.0))
        if mtype != MsgType.PLAN:
            raise pr.BadFrame(f"expected PLAN, got {mtype.name}")
        return plan

    def _build(self, plan: dict):
        # heavyweight imports deferred to the spawned process
        import jax
        import jax.numpy as jnp
        from repro import optim
        from repro.core.splitting import make_split_model

        assert plan["model"] == "lenet", plan["model"]
        self.plan = plan
        self.L = int(plan["local_epochs"])
        self.B = int(plan["batch"])
        self.seed = int(plan["seed"])
        self.x, _, self.shards = build_shards(plan["data"])
        split = make_split_model(plan["model"], int(plan["v"]))
        dev_opt = optim.make(plan["optimizer"], plan["lr_device"],
                             momentum=plan["momentum"],
                             weight_decay=plan["weight_decay"])

        # the decomposed protocol-step kernels (see module docstring)
        self._fwd = jax.jit(split.device_apply)

        def _bwd(dp, dopt, step, b, g):
            _, vjp = jax.vjp(lambda q: split.device_apply(q, b)[0], dp)
            g_dev = vjp(g)[0]
            return dev_opt.step(g_dev, dopt, dp, step)

        self._bwd = jax.jit(_bwd)
        self._jnp, self._jax = jnp, jax

        if plan.get("warmup", True):
            p0 = split.init_device(streams.warmup_key())
            batch = {"image": jnp.zeros((self.B, 28, 28, 1), jnp.float32)}
            sm, _ = self._fwd(p0, batch)
            g0 = jnp.zeros(split.smashed_spec(self.B).shape, jnp.float32)
            jax.block_until_ready(
                self._bwd(p0, dev_opt.init(p0), np.int32(0), batch, g0))

    def _send_ready(self):
        import jax
        self.ch.send(MsgType.READY, {"device": self.gid,
                                     "platform": jax.default_backend()})

    def _start_heartbeat(self):
        interval = self.cfg.get("heartbeat_s", 0.5)
        ch = self.ch     # bind THIS channel: after a reconnect the old
                         # thread dies on the closed socket instead of
                         # silently adopting the new one

        def hb():
            while not self._hb_stop.wait(interval):
                try:
                    ch.send(MsgType.HEARTBEAT,
                            {"device": self.gid, "t": time.monotonic()})
                except Exception:
                    return

        threading.Thread(target=hb, daemon=True).start()

    # -- RPC -------------------------------------------------------------

    def _rpc(self, send_type: MsgType, payload, match) -> dict:
        """Send and await the matching reply, resending with exponential
        backoff on timeout. Raises _Aborted when the server moved on
        (new CLUSTER_START / SHUTDOWN pushed to pending, or ERROR), and
        after exhausting retries."""
        cfg = self.cfg
        timeout = cfg.get("rpc_timeout_s", 5.0)
        retries = int(cfg.get("retries", 3))
        sleeps = retry_sleeps(retries, cfg.get("backoff_s", 0.25),
                              cap=cfg.get("backoff_max_s", 2.0))
        for attempt in range(retries + 1):
            if attempt:
                time.sleep(sleeps[attempt - 1])
            self.ch.send(send_type, dict(payload, attempt=attempt))
            deadline = time.monotonic() + timeout
            while True:
                left = deadline - time.monotonic()
                if left <= 0:
                    break
                try:
                    mtype, msg = self.ch.recv(timeout=left)
                except RpcTimeout:
                    break
                if mtype in (MsgType.CLUSTER_START, MsgType.SHUTDOWN):
                    self.pending.append((mtype, msg))
                    raise _Aborted("server moved on")
                if mtype == MsgType.ERROR:
                    raise _Aborted(msg.get("reason", "server error"))
                if match(mtype, msg):
                    return msg
                # stale reply from an earlier attempt/epoch: ignore
        raise _Aborted(f"no reply to {send_type.name} "
                       f"after {retries + 1} attempts")

    # -- cluster participation -------------------------------------------

    def _run_cluster(self, msg: dict):
        jnp = self._jnp
        rnd, m, k = int(msg["round"]), int(msg["m"]), int(msg["k"])
        members = [int(d) for d in msg["members"]]
        step0 = int(msg["step"])
        self._round = rnd
        dev, dopt = msg["dev"], msg["dev_opt"]

        for l in range(self.L):
            picks = member_batch_indices(self.shards, members, self.B,
                                         self.seed, rnd, m, l)
            batch = {"image": jnp.asarray(self.x[picks[k]])}
            self.injector.sleep_compute(rnd)
            t0 = time.monotonic()
            smashed, _ = self._fwd(dev, batch)
            smashed = np.asarray(smashed)
            self.qos.emit(rnd, "fwd", time.monotonic() - t0,
                          cluster=m, epoch=l, slot=k)
            t0 = time.monotonic()
            reply = self._rpc(
                MsgType.SMASHED,
                {"round": rnd, "m": m, "epoch": l, "k": k,
                 "device": self.gid, "smashed": smashed},
                lambda mt, ms, l=l: (mt == MsgType.GRAD
                                     and ms.get("round") == rnd
                                     and ms.get("m") == m
                                     and ms.get("epoch") == l))
            self.qos.emit(rnd, "grad_wait", time.monotonic() - t0,
                          cluster=m, epoch=l, slot=k,
                          bytes=smashed.nbytes)
            t0 = time.monotonic()
            dev, dopt = self._bwd(dev, dopt, np.int32(step0 + l),
                                  batch, jnp.asarray(reply["g"]))
            self._jax.block_until_ready(dev)
            self.qos.emit(rnd, "bwd", time.monotonic() - t0,
                          cluster=m, epoch=l, slot=k)

        t0 = time.monotonic()
        self._rpc(
            MsgType.AGG,
            {"round": rnd, "m": m, "k": k, "device": self.gid,
             "dev": self._jax.tree.map(np.asarray, dev),
             "dev_opt": self._jax.tree.map(np.asarray, dopt),
             "qos": self.qos.drain()},
            lambda mt, ms: (mt == MsgType.AGG_ACK
                            and ms.get("round") == rnd
                            and ms.get("m") == m))

    # -- main loop -------------------------------------------------------

    def _serve(self):
        """Dispatch loop on the current channel; returns on clean
        SHUTDOWN (or triggered stop), raises on connection loss."""
        while not self.stop:
            if self.pending:
                mtype, msg = self.pending.popleft()
            else:
                try:
                    mtype, msg = self.ch.recv(timeout=0.5)
                except RpcTimeout:
                    continue
            if mtype == MsgType.SHUTDOWN:
                self.ch.send(MsgType.BYE, {"device": self.gid})
                return
            if mtype == MsgType.CLUSTER_START:
                try:
                    self._run_cluster(msg)
                except _Aborted:
                    self.qos.drain()   # cluster abandoned: QoS stale
            # anything else (stale GRAD/ACK/ERROR) is ignored here

    def _rejoin(self) -> bool:
        """Re-dial the server after losing it and re-handshake with
        REJOIN (model/jits already built, so no PLAN rebuild and no
        warmup — READY follows immediately). The WHOLE handshake is
        retried with capped backoff until ``reconnect_timeout_s``
        elapses, not just the TCP connect: racing a dying server's
        socket teardown can land a connect in a dead listener's backlog
        (accepted, then RST on first read), and a restarted server may
        be mid-bind — both are transient. Returns False only when the
        budget is exhausted — the worker then exits like before."""
        cfg = self.cfg
        self.pending.clear()
        self._round = None
        self.qos.drain()               # pre-crash QoS is unmatchable now
        deadline = time.monotonic() + cfg.get("reconnect_timeout_s", 30.0)
        backoff = Backoff(cfg.get("backoff_s", 0.25),
                          cap=cfg.get("backoff_max_s", 2.0))
        while not self.stop:
            left = deadline - time.monotonic()
            if left <= 0:
                return False
            if self._rejoin_once(left):
                return True
            time.sleep(min(backoff.next(),
                           max(0.0, deadline - time.monotonic())))
        return False

    def _rejoin_once(self, budget_s: float) -> bool:
        """One rejoin attempt: connect, REJOIN, await REJOIN_ACK (or a
        PLAN — server wants a full rebuild), READY. Any transport or
        protocol failure just fails this attempt."""
        cfg = self.cfg
        try:
            sock = connect_with_retry(cfg["host"], cfg["port"], budget_s)
            self.ch = Channel(sock, self.injector,
                              round_fn=lambda: self._round)
            self.ch.send(MsgType.REJOIN, {"device": self.gid,
                                          "incarnation": self.incarnation})
            mtype, msg = self.ch.recv(
                timeout=min(budget_s, cfg.get("plan_timeout_s", 120.0)))
            if mtype == MsgType.PLAN:
                self._build(msg)       # server asked for a full rebuild
            elif mtype != MsgType.REJOIN_ACK:
                return False
            self._send_ready()
        except (pr.ProtocolError, RpcTimeout, OSError):
            return False
        self._start_heartbeat()
        return True

    def run(self):
        plan = self._connect_and_plan()
        self._build(plan)
        self._send_ready()
        self._start_heartbeat()
        try:
            while True:
                try:
                    self._serve()
                    return             # SHUTDOWN / stop: clean exit
                except (pr.ConnectionClosed, pr.TruncatedFrame,
                        InjectedDisconnect, OSError):
                    if not self.cfg.get("reconnect", False) or self.stop:
                        return
                    if not self._rejoin():
                        return
        finally:
            self._hb_stop.set()
            try:
                self.ch.close()
            except Exception:
                pass


def pin_to_cpu():
    """Make the CPU this process's only JAX platform. JAX fixes its
    platforms when the first backend starts, so this must run before
    anything touches a backend — raises otherwise, rather than leave the
    worker free to open the accelerator."""
    import jax
    from jax._src import xla_bridge
    if xla_bridge.backends_are_initialized():
        raise RuntimeError("pin_to_cpu: a JAX backend is already "
                           "initialised in this process")
    jax.config.update("jax_platforms", "cpu")


def device_main(cfg: dict):
    """Spawn entrypoint (top-level so multiprocessing can pickle it)."""
    pin_to_cpu()
    DeviceWorker(cfg).run()
