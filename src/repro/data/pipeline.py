"""Data pipeline: per-device local datasets -> CPSL cluster batches.

``CPSLDataset`` owns the non-IID device shards and yields batches shaped
(K, B, ...) for the active cluster — the mini-batch draw of paper eq. (4).

``DeviceResidentDataset`` is the fused-round mirror of ``CPSLDataset``:
the full dataset is uploaded to the accelerator once, and each round the
host precomputes only a small (M, L, K, B) int32 index table — drawn from
the SAME rng streams ``cluster_batch`` uses — that
``CPSL.run_round_fused`` gathers inside the jit. No per-step host
transfer, bit-identical batches. ``training_index_table`` stacks R of
those tables for the whole-curve jit (``CPSL.run_training_fused``), the
optional eval split rides along device-resident for the in-jit test-set
evaluation, and ``fleet_plan`` pads per-replica layouts/shards to a
common shape (+ masks) for the batched experiment fleet
(``CPSL.run_fleet``).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence

import numpy as np

from repro import streams
from repro.streams import batch_seed  # re-export: the formula moved to
                                      # repro.streams (shared registry);
                                      # importers of pipeline.batch_seed
                                      # keep working

__all__ = ["shard_sizes", "round_index_table", "batch_seed",
           "CPSLDataset", "DeviceResidentDataset", "FleetPlan",
           "fleet_plan", "LMClusterData"]


def shard_sizes(device_indices: List[np.ndarray],
                devices: Sequence[int]) -> np.ndarray:
    """Per-device local dataset sizes |D_{m,k}| — the eq. (8) weights."""
    return np.array([len(device_indices[d]) for d in devices], np.float32)


def round_index_table(device_indices: List[np.ndarray], batch: int,
                      clusters: Sequence[Sequence[int]], seed: int,
                      rnd: int, local_epochs: int) -> np.ndarray:
    """(M, L, K, B) int32 global sample indices for one round; row
    (m, l, k) is exactly the pick ``CPSLDataset.cluster_batch`` would
    draw for device ``clusters[m][k]`` at ``batch_seed(seed, rnd, m, l)``
    (same ``default_rng`` stream, same per-device call order — draws are
    prefix-stable, so appending padded slots never changes real rows).
    Host-side and numpy-only, so fleet builders can derive tables for
    many replicas without mirroring the data arrays per replica."""
    M, K = len(clusters), len(clusters[0])
    out = np.empty((M, local_epochs, K, batch), np.int32)
    for m, devices in enumerate(clusters):
        assert len(devices) == K, \
            "fused round needs rectangular (padded) clusters"
        for l in range(local_epochs):
            rng = streams.batch_rng(seed, rnd, m, l)
            for k, d in enumerate(devices):
                idx = device_indices[d]
                out[m, l, k] = rng.choice(idx, batch,
                                          replace=len(idx) < batch)
    return out


class CPSLDataset:
    def __init__(self, images: np.ndarray, labels: np.ndarray,
                 device_indices: List[np.ndarray], batch: int,
                 field_names=("image", "label"), seed: int = 0):
        self.x, self.y = images, labels
        self.device_indices = device_indices
        self.B = batch
        self.fields = field_names
        self.rng = streams.data_rng(seed)

    def data_sizes(self, devices: Sequence[int]) -> np.ndarray:
        return shard_sizes(self.device_indices, devices)

    def cluster_batch(self, devices: Sequence[int],
                      seed: Optional[int] = None) -> Dict[str, np.ndarray]:
        """Draw a (K, B, ...) batch: device k samples B items from its own
        local dataset (paper: B_{m,k} subset of D_{m,k}). Passing ``seed``
        makes the draw a pure function of (seed, devices) — required for
        bit-exact restart-after-failure."""
        rng = streams.premixed_rng(seed) if seed is not None else self.rng
        xs, ys = [], []
        for d in devices:
            idx = self.device_indices[d]
            pick = rng.choice(idx, self.B, replace=len(idx) < self.B)
            xs.append(self.x[pick])
            ys.append(self.y[pick])
        return {self.fields[0]: np.stack(xs), self.fields[1]: np.stack(ys)}


class DeviceResidentDataset:
    """Device-resident dataset + per-round index tables for the fused
    round (``CPSL.run_round_fused``).

    ``data`` holds the full sample arrays as jax device arrays (leading
    dim = sample count). ``round_index_table`` reproduces, entry for
    entry, the draws ``CPSLDataset.cluster_batch(clusters[m],
    seed=batch_seed(seed, rnd, m, l))`` would make — same
    ``default_rng`` stream, same per-device call order — so the in-jit
    gather ``data[field][idx[m, l]]`` is bit-identical to the host-side
    numpy gather of the looped path."""

    def __init__(self, images: np.ndarray, labels: np.ndarray,
                 device_indices: List[np.ndarray], batch: int,
                 field_names=("image", "label"), eval_images=None,
                 eval_labels=None):
        # deferred so the host-side pipeline stays importable without jax
        # (the engine's train=False control plane uses it numpy-only)
        import jax.numpy as jnp
        self.data = {field_names[0]: jnp.asarray(images),
                     field_names[1]: jnp.asarray(labels)}
        self.device_indices = [np.asarray(d) for d in device_indices]
        self.B = batch
        self.fields = field_names
        # eval split residency: uploaded once alongside the training
        # arrays so the fused training curve evaluates in-jit with no
        # host transfer (CPSL.run_training_fused eval_data=...)
        self.eval_data: Optional[dict] = None
        if eval_images is not None:
            self.eval_data = {field_names[0]: jnp.asarray(eval_images),
                              field_names[1]: jnp.asarray(eval_labels)}

    @classmethod
    def from_dataset(cls, ds: "CPSLDataset", eval_images=None,
                     eval_labels=None) -> "DeviceResidentDataset":
        return cls(ds.x, ds.y, ds.device_indices, ds.B, ds.fields,
                   eval_images, eval_labels)

    @classmethod
    def coerce(cls, dataset) -> "DeviceResidentDataset":
        """Accept a DeviceResidentDataset as-is, mirror any index-based
        dataset (one exposing ``device_indices``) onto the device, and
        reject generative datasets — shared by the trainer and the sim
        engine so the fused-round eligibility rule lives in one place."""
        if isinstance(dataset, cls):
            return dataset
        if hasattr(dataset, "device_indices"):
            return cls.from_dataset(dataset)
        raise ValueError(
            "CPSLConfig.fused_round needs an index-based dataset "
            "(CPSLDataset / DeviceResidentDataset); generative datasets "
            "cannot be gathered on device")

    def data_sizes(self, devices: Sequence[int]) -> np.ndarray:
        return shard_sizes(self.device_indices, devices)

    def cluster_weights(self, clusters: Sequence[Sequence[int]]
                        ) -> np.ndarray:
        """(M, K) eq.-8 weights: per-client local dataset sizes. Clusters
        must be rectangular (engine-padded to the trainer's K slots)."""
        return np.stack([self.data_sizes(c) for c in clusters])

    def round_index_table(self, clusters: Sequence[Sequence[int]],
                          seed: int, rnd: int, local_epochs: int
                          ) -> np.ndarray:
        """(M, L, K, B) int32 global sample indices for one round; row
        (m, l, k) is exactly the pick ``cluster_batch`` would draw for
        device ``clusters[m][k]`` at ``batch_seed(seed, rnd, m, l)``."""
        return round_index_table(self.device_indices, self.B, clusters,
                                 seed, rnd, local_epochs)

    def training_index_table(self, clusters: Sequence[Sequence[int]],
                             seed: int, rounds: int, local_epochs: int
                             ) -> np.ndarray:
        """(R, M, L, K, B): the round tables for a whole training curve
        (row r == ``round_index_table(..., rnd=r, ...)``), feeding
        ``CPSL.run_training_fused``."""
        return np.stack([self.round_index_table(clusters, seed, r,
                                                local_epochs)
                         for r in range(rounds)])


@dataclass
class FleetPlan:
    """Padded per-replica tables for ``CPSL.run_fleet``.

    ``idx`` (E, R, M, L, K, B) int32 — replica e's training index table,
    zero-filled on padded slots; ``weights`` (E, M, K) eq.-8 data sizes
    with exact zeros on padded client slots (so FedAvg never weighs
    them); ``cluster_mask`` (E, M) / ``client_mask`` (E, M, K) mark the
    real slots (both ``None`` when every replica already has the common
    shape — the masked and unmasked fleets compile different programs,
    and a homogeneous fleet must stay on the mask-free one to preserve
    bit-exactness against solo runs)."""
    idx: np.ndarray
    weights: np.ndarray
    cluster_mask: Optional[np.ndarray]
    client_mask: Optional[np.ndarray]
    layouts: List[List[List[int]]]
    seeds: List[int]

    @property
    def n_replicas(self) -> int:
        return self.idx.shape[0]


def fleet_plan(shards: List[List[np.ndarray]], batch: int,
               layouts: List[List[List[int]]], seeds: Sequence[int],
               rounds: int, local_epochs: int,
               pad_to: Optional[tuple] = None) -> FleetPlan:
    """Build the batched-fleet tables: replica e draws its batches from
    shard table ``shards[e]`` over its own (rectangular) cluster layout
    ``layouts[e]`` with batch-seed stream ``seeds[e]``, then everything
    is padded to the grid's (max M, max K).

    Real rows are built on the *unpadded* layout, so they are
    bit-identical to the tables a solo run of that replica would use;
    padded slots get index 0 (a valid gather) and are masked out of the
    loss, FedAvg, and metrics by the masks — ``CPSL.run_fleet`` promises
    perturbing them changes nothing.

    ``pad_to``: explicit (M, K) target overriding the grid max — lets
    sweep callers pad every variant (even solo, E=1) to one shared
    shape so they all reuse one compiled executable."""
    E = len(layouts)
    assert len(shards) == E and len(seeds) == E, (len(shards), len(seeds))
    Ms = [len(lay) for lay in layouts]
    Ks = [len(lay[0]) for lay in layouts]
    M, K = pad_to if pad_to is not None else (max(Ms), max(Ks))
    assert M >= max(Ms) and K >= max(Ks), (pad_to, Ms, Ks)
    homogeneous = all(m == M for m in Ms) and all(k == K for k in Ks)

    idx = np.zeros((E, rounds, M, local_epochs, K, batch), np.int32)
    weights = np.zeros((E, M, K), np.float32)
    cmask = np.zeros((E, M), bool)
    kmask = np.zeros((E, M, K), bool)
    for e, (lay, sh, seed) in enumerate(zip(layouts, shards, seeds)):
        for lay_m in lay:
            assert len(lay_m) == Ks[e], "replica layouts must be rectangular"
        real = np.stack([round_index_table(sh, batch, lay, seed, r,
                                           local_epochs)
                         for r in range(rounds)])
        idx[e, :, :Ms[e], :, :Ks[e]] = real
        weights[e, :Ms[e], :Ks[e]] = np.stack(
            [shard_sizes(sh, c) for c in lay])
        cmask[e, :Ms[e]] = True
        kmask[e, :Ms[e], :Ks[e]] = True
    return FleetPlan(idx, weights, None if homogeneous else cmask,
                     None if homogeneous else kmask,
                     [list(map(list, lay)) for lay in layouts],
                     [int(s) for s in seeds])


class LMClusterData:
    """Synthetic-LM equivalent: each simulated client has its own Markov
    seed (non-IID across clients)."""

    def __init__(self, lm, n_devices: int, batch: int, seq: int,
                 seed: int = 0):
        self.lm = lm
        self.B, self.S = batch, seq
        self.rngs = [streams.lm_device_rng(seed, d)
                     for d in range(n_devices)]

    def cluster_batch(self, devices: Sequence[int],
                      seed: Optional[int] = None):
        """``seed`` (as in ``CPSLDataset``) makes the draw a pure function
        of (seed, slot, device) — required by restartable/simulated
        trainers. The slot index is mixed in so a device repeated in the
        list (engine padding of churn-shrunk clusters) gets fresh samples
        rather than a bit-identical, double-weighted row."""
        if seed is not None:
            # streams.lm_batch_rng tags the key (seed, 7433, i, d): the
            # historical untagged (seed, i, d) collided with the fleet
            # churn namespaces (seed, s, 11/13/17/19) whenever d hit one
            # of those tags -- the collision the registry check found
            parts = [self.lm.sample(self.B, self.S,
                                    streams.lm_batch_rng(seed, i, d))
                     for i, d in enumerate(devices)]
        else:
            parts = [self.lm.sample(self.B, self.S, self.rngs[d])
                     for d in devices]
        return {k: np.stack([p[k] for p in parts]) for k in parts[0]}
