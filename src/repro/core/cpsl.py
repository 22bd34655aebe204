"""CPSL — Cluster-based Parallel Split Learning (paper Alg. 1).

"First-parallel-then-sequential": within a cluster, K device-side models
train in parallel against ONE shared server-side model fed the concatenated
smashed data (eqs. 4-7); after L local epochs the device-side models are
FedAvg-aggregated (eq. 8) and handed to the next cluster (eq. 9).

Two train-step implementations:
  - ``fused``:    one jax.grad through server+device models. The chain rule
                  *is* the smashed-gradient protocol; this is the
                  performance path (single fused HLO, no duplicate device
                  forward).
  - ``protocol``: the explicit two-phase wire protocol — device FP ->
                  smashed data -> server FP/BP -> smashed gradient ->
                  device BP. Bit-identical updates (tested); used to
                  demonstrate faithfulness and to price the phases.

Three orchestration levels, each one jit bigger than the last:
  - ``run_round``:       the readable reference — one jitted step per
                         (cluster, local epoch) plus one jitted FedAvg per
                         cluster, batches gathered host-side.
  - ``run_round_fused``: the whole round as ONE donated jit (``lax.scan``
                         over the cluster axis, local epochs unrolled in
                         the body) with device-resident data gathered
                         in-jit and FedAvg folded in at cluster
                         boundaries. Reproduces ``run_round`` at the same
                         seeds and lowering: ints/rng bit-exact, floats
                         ULP-equal per leaf (tests/test_fused_round.py);
                         see ``CPSLConfig.fused_round`` /
                         ``unroll_clients``.
  - ``run_training_fused`` / ``run_fleet``: the whole R-round training
                         CURVE as one donated jit (round axis unrolled,
                         or scanned via ``CPSLConfig.scan_rounds`` +
                         the im2col conv lowering) with periodic in-jit
                         eval — and its ``jax.vmap`` over E experiment
                         replicas whose seeds, shard tables, eq.-8
                         weights, learning rates, and padded layouts all
                         enter as data, so a whole sweep grid is one
                         compile + one dispatch (tests/test_fleet.py).

Vanilla SL is CPSL with cluster_size=1 / n_clusters=N (paper §III). FL is
the v=V degenerate case (`FLTrainer`).
"""
from __future__ import annotations

import functools
from typing import Callable, List, Optional

import jax
import jax.numpy as jnp

from repro import streams
from repro import optim
from repro.configs.base import CPSLConfig
from repro.core import compression as cmp
from repro.core import partitioning as pt
from repro.core.splitting import SplitModel


def _flat(tree):
    return jax.tree.map(lambda t: t.reshape((-1,) + t.shape[2:]), tree)


class CPSL:
    def __init__(self, split: SplitModel, ccfg: CPSLConfig,
                 dev_opt: Optional[optim.Optimizer] = None,
                 srv_opt: Optional[optim.Optimizer] = None):
        self.split = split
        self.ccfg = ccfg
        self.dev_opt = dev_opt or optim.make(ccfg.optimizer, ccfg.lr_device,
                                             momentum=ccfg.momentum,
                                             weight_decay=ccfg.weight_decay)
        self.srv_opt = srv_opt or optim.make(ccfg.optimizer, ccfg.lr_server,
                                             momentum=ccfg.momentum,
                                             weight_decay=ccfg.weight_decay)
        self._step_fn = (self._fused_step if ccfg.fused_step
                         else self._protocol_step)

    # -- state --------------------------------------------------------------

    def init_state(self, key) -> dict:
        k1, k2, k3 = jax.random.split(key, 3)
        K = 1 if self.ccfg.share_device_params else self.ccfg.cluster_size
        dev0 = self.split.init_device(k1)
        dev = jax.tree.map(
            lambda t: jnp.broadcast_to(t[None], (K,) + t.shape), dev0)
        srv = self.split.init_server(k2)
        state = {
            "step": jnp.zeros((), jnp.int32),
            "dev": dev,
            "dev_opt": self.dev_opt.init(dev),
            "srv": srv,
            "srv_opt": self.srv_opt.init(srv),
            "rng": k3,
        }
        if self.ccfg.compress_uploads != "none":
            state["ef"] = jax.tree.map(
                lambda t: jnp.zeros_like(t, jnp.float32), dev)
        return state

    # -- loss ---------------------------------------------------------------

    def _clients_unrolled(self, dev, batch):
        """Trace-time unroll of the K-client device pass (same math as
        ``jax.vmap(device_apply)``, stacked in client order).

        ``jax.vmap`` over per-client weights lowers the device conv
        gradients to grouped convolutions, which XLA:CPU executes on its
        naive emitter — ~10x slower than the K plain convolutions this
        unrolled form emits (measured in benchmarks/bench_round.py).
        Results match the vmapped lowering to ULP (tested); TPU/GPU are
        indifferent, so ``unroll_clients`` stays off by default."""
        K = jax.tree.leaves(dev)[0].shape[0]
        outs = [self.split.device_apply(jax.tree.map(lambda t: t[k], dev),
                                        jax.tree.map(lambda t: t[k], batch))
                for k in range(K)]
        return (jnp.stack([o[0] for o in outs]),
                jnp.stack([o[1] for o in outs]))

    def _total_loss(self, dev, srv, batch):
        """batch leaves: (K, B, ...). Returns (scalar, metrics)."""
        if self.ccfg.share_device_params:
            flat = _flat(batch)
            dev0 = jax.tree.map(lambda t: t[0], dev)
            smashed, aux_d = self.split.device_apply(dev0, flat)
        else:
            if self.ccfg.unroll_clients:
                smashed, aux_d = self._clients_unrolled(dev, batch)
            else:
                K = jax.tree.leaves(dev)[0].shape[0]
                ax = pt.spmd_client_axes(K)
                with pt.exclude_axes(ax):
                    smashed, aux_d = jax.vmap(
                        self.split.device_apply, spmd_axis_name=ax)(dev,
                                                                    batch)
            # eq. (5): concatenate client smashed data into the server batch
            smashed = smashed.reshape((-1,) + smashed.shape[2:])
            aux_d = aux_d.mean()
            flat = _flat(batch)
        smashed = pt.shard(smashed, "batch")
        loss, aux_s = self.split.server_loss(srv, smashed, flat)
        total = loss + aux_d + aux_s
        return total, {"loss": loss, "aux": aux_d + aux_s}

    # -- fused step ----------------------------------------------------------

    def fused_step_impl(self, state, batch, lr_scale=None):
        """Unjitted fused step — the dry-run wraps this with explicit
        in/out shardings; interactive use goes through the jitted method.

        ccfg.microbatches > 1 splits the per-client batch B and
        accumulates gradients over a rematted scan (activation memory
        scales 1/m; the straggler/latency model is unaffected — the
        device still processes B samples per epoch).

        ``lr_scale``: optional traced scalar multiplying both optimizers'
        learning rates (fleet per-replica hyper-parameters as data)."""
        grad_fn = jax.value_and_grad(self._total_loss, argnums=(0, 1),
                                     has_aux=True)
        m = self.ccfg.microbatches
        if m > 1:
            mb = jax.tree.map(
                lambda t: jnp.moveaxis(
                    t.reshape((t.shape[0], m, t.shape[1] // m)
                              + t.shape[2:]), 1, 0), batch)

            def acc(carry, mbatch):
                g_dev, g_srv, loss, aux = carry
                (_, mt), (gd, gs) = grad_fn(state["dev"], state["srv"],
                                            mbatch)
                g_dev = jax.tree.map(lambda a, b: a + b / m, g_dev, gd)
                g_srv = jax.tree.map(lambda a, b: a + b / m, g_srv, gs)
                return (g_dev, g_srv, loss + mt["loss"] / m,
                        aux + mt["aux"] / m), None

            zeros = lambda t: jax.tree.map(  # noqa: E731
                lambda p: jnp.zeros(p.shape, jnp.float32), t)
            (g_dev, g_srv, loss, aux), _ = jax.lax.scan(
                acc, (zeros(state["dev"]), zeros(state["srv"]),
                      jnp.zeros(()), jnp.zeros(())), mb)
            metrics = {"loss": loss, "aux": aux}
        else:
            (_, metrics), (g_dev, g_srv) = grad_fn(state["dev"],
                                                   state["srv"], batch)
        new_dev, dev_opt = self.dev_opt.step(g_dev, state["dev_opt"],
                                             state["dev"], state["step"],
                                             lr_scale=lr_scale)
        new_srv, srv_opt = self.srv_opt.step(g_srv, state["srv_opt"],
                                             state["srv"], state["step"],
                                             lr_scale=lr_scale)
        state = dict(state, dev=new_dev, dev_opt=dev_opt, srv=new_srv,
                     srv_opt=srv_opt, step=state["step"] + 1)
        return state, metrics

    @functools.partial(jax.jit, static_argnums=0)
    def _fused_step(self, state, batch):
        # NOTE: no donation here — interactive/test use keeps the input
        # state alive; the dry-run/launcher jits fused_step_impl with
        # donate_argnums for production memory behaviour.
        return self.fused_step_impl(state, batch)

    # -- explicit two-phase protocol step -------------------------------------

    def protocol_step_impl(self, state, batch, lr_scale=None):
        assert not self.ccfg.share_device_params
        split = self.split

        # Phase 1 (paper steps 3, eq. 4): device FP -> smashed data
        Kc = jax.tree.leaves(state["dev"])[0].shape[0]
        ax = pt.spmd_client_axes(Kc)
        if self.ccfg.unroll_clients:
            smashed, _ = self._clients_unrolled(state["dev"], batch)
        else:
            with pt.exclude_axes(ax):
                smashed, _ = jax.vmap(split.device_apply,
                                      spmd_axis_name=ax)(state["dev"], batch)
        K, B = smashed.shape[:2]
        smashed_flat = smashed.reshape((-1,) + smashed.shape[2:])
        flat = _flat(batch)

        # Phase 2 (eqs. 5-6): server FP/BP; emits smashed-data gradient
        def srv_loss(srv, sm):
            loss, aux = split.server_loss(srv, sm, flat)
            return loss + aux, loss

        (_, loss), (g_srv, g_smashed) = jax.value_and_grad(
            srv_loss, argnums=(0, 1), has_aux=True)(state["srv"],
                                                    smashed_flat)
        new_srv, srv_opt = self.srv_opt.step(g_srv, state["srv_opt"],
                                             state["srv"], state["step"],
                                             lr_scale=lr_scale)

        # Phase 3 (eq. 7): device BP from the smashed gradient
        g_smashed = g_smashed.reshape(smashed.shape)

        def dev_bwd(dp, b, g):
            _, vjp = jax.vjp(lambda q: split.device_apply(q, b)[0], dp)
            return vjp(g)[0]

        if self.ccfg.unroll_clients:
            gs = [dev_bwd(jax.tree.map(lambda t: t[k], state["dev"]),
                          jax.tree.map(lambda t: t[k], batch), g_smashed[k])
                  for k in range(Kc)]
            g_dev = jax.tree.map(lambda *ts: jnp.stack(ts), *gs)
        else:
            with pt.exclude_axes(ax):
                g_dev = jax.vmap(dev_bwd, spmd_axis_name=ax)(state["dev"],
                                                             batch,
                                                             g_smashed)
        new_dev, dev_opt = self.dev_opt.step(g_dev, state["dev_opt"],
                                             state["dev"], state["step"],
                                             lr_scale=lr_scale)
        state = dict(state, dev=new_dev, dev_opt=dev_opt, srv=new_srv,
                     srv_opt=srv_opt, step=state["step"] + 1)
        return state, {"loss": loss, "aux": jnp.zeros(())}

    @functools.partial(jax.jit, static_argnums=0)
    def _protocol_step(self, state, batch):
        return self.protocol_step_impl(state, batch)

    def cluster_step(self, state, batch):
        """One local epoch for the active cluster (paper Alg. 1 lines 7-19)."""
        return self._step_fn(state, batch)

    # -- aggregation (eq. 8) --------------------------------------------------

    def fedavg_impl(self, state, weights):
        """Pure eq. (8) aggregation, jit-safe (the fused round folds it
        into the scan): straggler dropout drawn from the carried rng,
        optional upload compression with error feedback, then the
        data-size-weighted mean broadcast back to every client row."""
        ccfg = self.ccfg
        w = weights.astype(jnp.float32)
        if ccfg.straggler_dropout > 0:
            rng, sub = jax.random.split(state["rng"])
            keep = jax.random.bernoulli(
                sub, 1.0 - ccfg.straggler_dropout, w.shape)
            # never drop everyone
            keep = keep.at[0].set(True)
            w = w * keep
            state = dict(state, rng=rng)

        dev = state["dev"]
        if ccfg.compress_uploads != "none":
            ref = jax.tree.map(lambda t: t[:1], dev)   # broadcast model
            delta = jax.tree.map(lambda t, r: t - r, dev, ref)
            delta, ef = cmp.apply_with_error_feedback(
                delta, state["ef"], ccfg.compress_uploads, ccfg.compress_topk)
            dev = jax.tree.map(lambda r, d: r + d, ref, delta)
            state = dict(state, ef=ef)

        def avg(t):
            ww = w / jnp.maximum(w.sum(), 1e-12)
            m = jnp.tensordot(ww, t.astype(jnp.float32), axes=(0, 0))
            return jnp.broadcast_to(m[None].astype(t.dtype), t.shape)

        new_dev = jax.tree.map(avg, dev)
        return dict(state, dev=new_dev)

    @functools.partial(jax.jit, static_argnums=0)
    def _fedavg(self, state, weights):
        return self.fedavg_impl(state, weights)

    def fedavg(self, state, data_sizes: Optional[jnp.ndarray] = None):
        """eq. (8): weights are the per-client local data sizes |D_{m,k}|
        (uniform when ``data_sizes`` is None)."""
        if self.ccfg.share_device_params:
            return state   # single shared device model: nothing to average
        K = self.ccfg.cluster_size
        w = (jnp.ones((K,), jnp.float32) if data_sizes is None
             else jnp.asarray(data_sizes, jnp.float32))
        return self._fedavg(state, w)

    # -- round orchestration (Alg. 1 lines 2-24) ------------------------------

    def run_round(self, state, batch_fn: Callable[[int, int], dict],
                  n_clusters: Optional[int] = None,
                  data_sizes=None) -> tuple:
        """batch_fn(m, l) -> batch with (K, B, ...) leaves for cluster m,
        local epoch l. Clusters run sequentially (inter-cluster, eq. 9).
        ``data_sizes``: optional (M, K) per-client local dataset sizes for
        the eq. (8) weighting (uniform when None)."""
        M = n_clusters or self.ccfg.n_clusters
        metrics = []
        for m in range(M):
            for l in range(self.ccfg.local_epochs):
                state, mt = self.cluster_step(state, batch_fn(m, l))
                metrics.append(mt)
            state = self.fedavg(
                state, None if data_sizes is None else data_sizes[m])
        loss = float(jnp.mean(jnp.stack([m["loss"] for m in metrics])))
        return state, {"loss": loss}

    # -- fused round (single donated jit over the (M, L) grid) ---------------

    def run_round_fused(self, state, data, idx, weights=None) -> tuple:
        """One CPSL round as a single donated jit: a ``jax.lax.scan`` over
        the cluster axis (local epochs unrolled in the body) with FedAvg
        folded in at each cluster boundary.

        ``data``     dict of device-resident dataset arrays, leading dim =
                     total sample count (``DeviceResidentDataset.data``).
        ``idx``      (M, L, K, B) int32 global sample indices — the exact
                     draws the looped path's ``cluster_batch`` would make
                     (``DeviceResidentDataset.round_index_table``); batches
                     are gathered from ``data`` inside the jit, so the
                     round runs with no host transfer in the loop.
        ``weights``  (M, K) eq.-8 data sizes (uniform when None).

        Contract (tests/test_fused_round.py): at identical seeds and the
        same ``unroll_clients`` lowering, the fused round reproduces the
        looped ``run_round`` — batches, rng stream, and step counter
        bit-for-bit; float leaves (params, optimizer state, error
        feedback, losses) ULP-equal per leaf (XLA:CPU emits conv/dot
        gradients with context-dependent fma contraction inside the
        single fused program, so last-ULP drift vs the separate looped
        jits is expected — measured <= 0.3 ULP after 3 paper-config
        rounds) — for both the ``fused`` and ``protocol`` step modes.
        Metrics come back as device arrays (``loss`` scalar + ``losses``
        (M*L,)); callers sync at most once per round (or every
        ``log_every`` rounds, see ``train.trainer``).

        Each distinct (M, L, K, B) signature compiles its own scan; with
        ``fused_round_unroll=0`` the scan is fully unrolled because
        XLA:CPU lowers conv gradients inside while-loop bodies to its
        naive emitter (~40x slower, measured). On conv models prefer
        ``unroll_clients=True`` — see ``_clients_unrolled``."""
        M, L = idx.shape[:2]
        assert L == self.ccfg.local_epochs, (L, self.ccfg.local_epochs)
        if weights is None:
            weights = jnp.ones((M, idx.shape[2]), jnp.float32)
        state, losses = self._run_round_fused(
            state, data, jnp.asarray(idx),
            jnp.asarray(weights, jnp.float32))
        return state, {"loss": jnp.mean(losses), "losses": losses}

    @functools.partial(jax.jit, static_argnums=0, donate_argnums=1)
    def _run_round_fused(self, state, data, idx, weights):
        M, L = idx.shape[:2]
        state, losses = self._cluster_scan(state, data, idx, weights)
        return state, losses.reshape(M * L)

    def _cluster_scan(self, state, data, idx, weights, cluster_mask=None,
                      client_mask=None, lr_scale=None):
        """One round's scan over the cluster axis; the shared body of
        ``_run_round_fused``, ``run_training_fused`` and ``run_fleet``.
        Returns ``(state, losses)`` with losses shaped (M, L).

        ``cluster_mask`` (M,) bool: padded cluster slots run (the fleet's
        replicas share one program) but their state update — including
        the rng stream and step counter — is discarded, so a replica with
        fewer real clusters than the padded layout reproduces its solo
        run; their losses come back NaN. ``client_mask`` (M, K) bool is
        injected into the batch as a per-sample weight mask: padded
        client rows carry exactly zero loss weight, so neither the
        server gradients nor (via zero eq.-8 weights) FedAvg ever see
        their data. ``lr_scale`` threads a traced per-run lr multiplier
        into both optimizers."""
        M, L, K, B = idx.shape
        step_impl = (self.fused_step_impl if self.ccfg.fused_step
                     else self.protocol_step_impl)
        masked = cluster_mask is not None or client_mask is not None
        if masked:
            if cluster_mask is None:
                cluster_mask = jnp.ones((M,), bool)
            if client_mask is None:
                client_mask = jnp.ones((M, K), bool)

        # Scan over the cluster axis (the paper's sequential eq.-9
        # dimension) with the L local epochs unrolled inside the body, so
        # FedAvg runs unconditionally at the cluster boundary — a
        # lax.cond would push the eq.-8 average into a sub-computation,
        # where XLA:CPU emits the small dots with different fma
        # contraction than the looped path's top-level _fedavg jit
        # (observed as last-ULP drift in the conv biases).
        def body(st, xs):
            if masked:
                idx_m, w, keep, km = xs     # (L,K,B), (K,), (), (K,)
            else:
                idx_m, w = xs               # (L, K, B), (K,)
            st_in = st
            if masked:
                # enforce the padding contract structurally: padded
                # client slots must never enter eq.-8 FedAvg even when
                # the caller left ``weights`` at the uniform default
                # (real slots multiply by 1.0 — float-exact, so the
                # bit-exactness contract vs solo runs is untouched)
                w = w * km.astype(w.dtype)
            losses = []
            for l in range(L):
                # The looped path runs the batch transfer, each step, and
                # each FedAvg as separate XLA programs;
                # optimization_barrier pins those same fusion boundaries
                # inside the scan, otherwise XLA may fuse the average
                # into the step's update chain and reassociate
                # reductions. Codegen inside the one fused program can
                # still contract fma differently, so the equivalence
                # contract is per-leaf ULP, not bitwise (see
                # run_round_fused).
                batch = jax.lax.optimization_barrier(
                    jax.tree.map(lambda a: a[idx_m[l]], data))  # in-jit
                if masked:
                    # reserved key, distinct from the LM datasets' per-
                    # token "mask" field: only losses that implement the
                    # per-sample-weight semantics read it (lenet; masked
                    # fleets assert that in run_training_fused/run_fleet)
                    batch = dict(batch, sample_weight=jnp.broadcast_to(
                        km[:, None], (K, B)).astype(jnp.float32))
                st, mt = step_impl(st, batch, lr_scale=lr_scale)
                st = jax.lax.optimization_barrier(st)
                losses.append(mt["loss"])
            if not self.ccfg.share_device_params:
                st = jax.lax.optimization_barrier(self.fedavg_impl(st, w))
            losses = jnp.stack(losses)
            if masked:
                # padded cluster slot: discard the whole update (state,
                # rng, step counter) so real clusters see the same
                # stream/counter a solo run of the unpadded layout would
                st = jax.tree.map(lambda n, o: jnp.where(keep, n, o),
                                  st, st_in)
                losses = jnp.where(keep, losses, jnp.nan)
            return st, losses

        xs = ((idx, weights, cluster_mask, client_mask) if masked
              else (idx, weights))
        return jax.lax.scan(body, state, xs,
                            unroll=self.ccfg.fused_round_unroll or M)

    # -- fused training curve (R rounds in ONE donated jit) -------------------

    def _eval_impl(self, state, eval_data):
        dev0 = jax.tree.map(lambda t: t[0], state["dev"])
        return self.split.eval_metrics(dev0, state["srv"], eval_data)

    def eval_rounds(self, rounds: int, eval_every: int):
        """The in-jit eval schedule: every ``eval_every`` rounds plus the
        final round (host-side mirror of the traced schedule)."""
        if not eval_every:
            return []
        return [r for r in range(rounds)
                if (r + 1) % eval_every == 0 or r == rounds - 1]

    def _training_impl(self, state, data, idx, weights, lr_scale,
                       eval_data, cluster_mask, client_mask, eval_every):
        R = idx.shape[0]
        do_eval = bool(eval_every) and eval_data is not None

        if self.ccfg.scan_rounds:
            # Round axis as lax.scan: compile cost is R-independent, but
            # XLA:CPU lowers *direct* conv gradients inside while-loop
            # bodies to its naive emitter (~36x, measured) — use the
            # im2col lowering (conv_impl="im2col"), whose dots stay fast
            # in loop bodies. Eval rides at block boundaries (requires
            # eval_every | R), so the schedule matches the unrolled path.
            def round_body(st, idx_r):
                st, lm = self._cluster_scan(st, data, idx_r, weights,
                                            cluster_mask, client_mask,
                                            lr_scale)
                return st, lm

            if do_eval:
                blocks = R // eval_every
                idx_b = idx.reshape((blocks, eval_every) + idx.shape[1:])

                def block(st, idx_blk):
                    st, lm = jax.lax.scan(round_body, st, idx_blk)
                    return st, (lm, self._eval_impl(st, eval_data))

                state, (losses, evals) = jax.lax.scan(block, state, idx_b)
                losses = losses.reshape((R,) + losses.shape[2:])
            else:
                state, losses = jax.lax.scan(round_body, state, idx)
                evals = None
        else:
            # default: rounds unrolled at trace time (compile scales with
            # R; required for direct-conv models on XLA:CPU)
            loss_list, eval_list = [], []
            ev_rounds = set(self.eval_rounds(R, eval_every))
            for r in range(R):
                state, lm = self._cluster_scan(state, data, idx[r],
                                               weights, cluster_mask,
                                               client_mask, lr_scale)
                loss_list.append(lm)
                if do_eval and r in ev_rounds:
                    eval_list.append(self._eval_impl(state, eval_data))
            losses = jnp.stack(loss_list)            # (R, M, L)
            evals = (jax.tree.map(lambda *ts: jnp.stack(ts), *eval_list)
                     if eval_list else None)

        if cluster_mask is None:
            loss = losses.mean(axis=(1, 2))          # (R,)
        else:
            keep = cluster_mask[None, :, None]
            loss = (jnp.where(keep, losses, 0.0).sum(axis=(1, 2))
                    / jnp.maximum(cluster_mask.sum() * losses.shape[2], 1))
        return state, losses, loss, evals

    @functools.partial(jax.jit, static_argnums=(0, 9), donate_argnums=1)
    def _run_training_fused(self, state, data, idx, weights, lr_scale,
                            eval_data, cluster_mask, client_mask,
                            eval_every):
        return self._training_impl(state, data, idx, weights, lr_scale,
                                   eval_data, cluster_mask, client_mask,
                                   eval_every)

    def run_training_fused(self, state, data, idx, weights=None, *,
                           lr_scale=None, eval_data=None, eval_every=0,
                           cluster_mask=None, client_mask=None) -> tuple:
        """A full R-round training curve as ONE donated jit: the fused
        round body of ``run_round_fused`` repeated over the round axis
        (trace-time unroll by default; ``CPSLConfig.scan_rounds`` scans
        it) with periodic in-jit test-set evaluation carried in the
        metrics stack — no host sync anywhere in the curve.

        ``idx``      (R, M, L, K, B) int32 index tables — row r is
                     exactly ``DeviceResidentDataset.round_index_table``
                     for round r (``training_index_table`` builds the
                     stack), so round r reproduces the looped
                     ``run_round_fused`` round-for-round (ints/rng
                     bit-exact, floats ULP-equal; tests/test_fleet.py).
        ``weights``  (M, K) eq.-8 data sizes, fixed across rounds
                     (uniform when None).
        ``lr_scale`` optional scalar lr multiplier applied as *data*
                     (see ``repro.optim``).
        ``eval_data``device-resident eval batch (e.g.
                     ``DeviceResidentDataset.eval_data``); evaluated via
                     ``SplitModel.eval_metrics`` every ``eval_every``
                     rounds plus the final round (``eval_rounds`` gives
                     the schedule).
        ``cluster_mask``/``client_mask``: padded-layout masks, see
                     ``_cluster_scan``.

        Returns ``(state, metrics)``: ``losses`` (R, M*L) device array
        (NaN on padded cluster slots), ``loss`` (R,) per-round means
        over real slots, ``eval`` dict of (n_evals,) curves + the
        matching ``eval_rounds`` list."""
        R, M, L, K, B = idx.shape
        assert L == self.ccfg.local_epochs, (L, self.ccfg.local_epochs)
        if client_mask is not None:
            assert self.split.masked_loss, \
                "client_mask needs a SplitModel whose server_loss " \
                "implements the sample_weight semantics (lenet)"
        if eval_every:
            assert self.split.eval_metrics is not None, \
                "eval_every > 0 needs a SplitModel with eval_metrics"
            assert eval_data is not None, "eval_every > 0 needs eval_data"
            if self.ccfg.scan_rounds:
                assert R % eval_every == 0, \
                    "scan_rounds needs eval_every to divide rounds"
        if weights is None:
            weights = jnp.ones((M, K), jnp.float32)
        state, losses, loss, evals = self._run_training_fused(
            state, data, jnp.asarray(idx),
            jnp.asarray(weights, jnp.float32), lr_scale, eval_data,
            cluster_mask, client_mask, int(eval_every))
        metrics = {"losses": losses.reshape(R, M * L), "loss": loss}
        if evals is not None:
            metrics["eval"] = evals
            metrics["eval_rounds"] = self.eval_rounds(R, eval_every)
        return state, metrics

    # -- experiment fleet (E replicas x R rounds, one batched program) --------

    def init_fleet_state(self, seeds) -> dict:
        """Stacked per-replica states; replica r == ``init_state(
        PRNGKey(seeds[r]))`` bit-for-bit (the fleet contract's solo
        reference)."""
        states = [self.init_state(streams.model_key(int(s)))
                  for s in seeds]
        return jax.tree.map(lambda *ts: jnp.stack(ts), *states)

    @functools.partial(jax.jit, static_argnums=(0, 9), donate_argnums=1)
    def _run_fleet(self, states, data, idx, weights, lr_scale, eval_data,
                   cluster_mask, client_mask, eval_every):
        ax = lambda x: None if x is None else 0  # noqa: E731

        def one(state, idx_e, w_e, ls_e, cm_e, km_e):
            return self._training_impl(state, data, idx_e, w_e, ls_e,
                                       eval_data, cm_e, km_e, eval_every)

        return jax.vmap(one, in_axes=(0, 0, 0, ax(lr_scale),
                                      ax(cluster_mask), ax(client_mask)))(
            states, idx, weights, lr_scale, cluster_mask, client_mask)

    def run_fleet(self, states, data, idx, weights=None, *, lr_scale=None,
                  eval_data=None, eval_every=0, cluster_mask=None,
                  client_mask=None) -> tuple:
        """E whole training curves as ONE batched program:
        ``jax.vmap`` of the ``run_training_fused`` body over the replica
        axis. Replicas differ only in *data* — seeds (``states`` rows),
        non-IID shard draws (``idx`` tables), eq.-8 ``weights``,
        per-replica ``lr_scale``, and padded-layout masks — so one XLA
        compile serves the whole grid, and on accelerators the replica
        axis is free to shard.

        ``states``   stacked replica states (``init_fleet_state``).
        ``idx``      (E, R, M, L, K, B); per-replica layouts padded to
                     the common (M, K) with ``cluster_mask`` (E, M) /
                     ``client_mask`` (E, M, K) marking real slots
                     (``data.pipeline.fleet_plan`` builds all of these).
        ``eval_data``shared device-resident eval batch (not batched
                     over replicas).

        Contract (tests/test_fleet.py, benchmarks/bench_fleet.py):
        replica r is bit-exact (ints/rng) and ULP-equal per leaf
        (floats) to the solo ``run_training_fused`` run with seed r at
        the same layout/lr. Masked (padded) slots never contribute:
        perturbing a padded slot's indices leaves every output
        bit-identical."""
        E, R, M, L, K, B = idx.shape
        assert L == self.ccfg.local_epochs, (L, self.ccfg.local_epochs)
        if client_mask is not None:
            assert self.split.masked_loss, \
                "client_mask needs a SplitModel whose server_loss " \
                "implements the sample_weight semantics (lenet)"
        if eval_every:
            assert self.split.eval_metrics is not None, \
                "eval_every > 0 needs a SplitModel with eval_metrics"
            assert eval_data is not None, "eval_every > 0 needs eval_data"
            if self.ccfg.scan_rounds:
                assert R % eval_every == 0, \
                    "scan_rounds needs eval_every to divide rounds"
        if weights is None:
            weights = jnp.ones((E, M, K), jnp.float32)
        if lr_scale is not None:
            lr_scale = jnp.asarray(lr_scale, jnp.float32)
            assert lr_scale.shape == (E,), lr_scale.shape
        states, losses, loss, evals = self._run_fleet(
            states, data, jnp.asarray(idx),
            jnp.asarray(weights, jnp.float32), lr_scale, eval_data,
            None if cluster_mask is None else jnp.asarray(cluster_mask),
            None if client_mask is None else jnp.asarray(client_mask),
            int(eval_every))
        metrics = {"losses": losses.reshape(E, R, M * L), "loss": loss}
        if evals is not None:
            metrics["eval"] = evals
            metrics["eval_rounds"] = self.eval_rounds(R, eval_every)
        return states, metrics

    def export_params(self, state):
        dev0 = jax.tree.map(lambda t: t[0], state["dev"])
        return self.split.export(dev0, state["srv"])


# --------------------------------------------------------------------------
# FL comparator (the paper's v = V degenerate case)
# --------------------------------------------------------------------------

class FLTrainer:
    """All devices train the FULL model locally; FedAvg each round."""

    def __init__(self, loss_fn: Callable, init_fn: Callable, n_devices: int,
                 lr: float = 0.1, local_steps: int = 1):
        self.loss_fn, self.init_fn = loss_fn, init_fn
        self.N, self.lr, self.local_steps = n_devices, lr, local_steps

    def init_state(self, key):
        p0 = self.init_fn(key)
        return {"params": jax.tree.map(
            lambda t: jnp.broadcast_to(t[None], (self.N,) + t.shape), p0)}

    @functools.partial(jax.jit, static_argnums=0)
    def round(self, state, batches):
        """batches leaves: (N, local_steps, B, ...)."""
        def local(params, bs):
            def one(params, b):
                loss, g = jax.value_and_grad(self.loss_fn)(params, b)
                params = jax.tree.map(
                    lambda p, gg: p - self.lr * gg, params, g)
                return params, loss

            return jax.lax.scan(one, params, bs)

        params, losses = jax.vmap(local)(state["params"], batches)
        avg = jax.tree.map(
            lambda t: jnp.broadcast_to(t.mean(0, keepdims=True)
                                       .astype(t.dtype), t.shape), params)
        return {"params": avg}, losses.mean()
