"""Config dataclasses for the repro framework.

A ModelConfig fully describes one architecture in the zoo. Layer stacks are
expressed as an optional unrolled ``prologue`` followed by a periodic
``pattern`` that is scanned ``n_periods`` times (compact HLO, fast
compiles). Heterogeneous stacks (gemma2 local/global, jamba
1:7 mamba:attn with alternating MoE) are one period of the repeating unit.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Optional, Tuple


@dataclass(frozen=True)
class MoECfg:
    n_experts: int                  # routed experts the router scores
    top_k: int
    d_ff_expert: int
    n_shared_experts: int = 0
    n_held: int = 0                 # routed experts held here, the
                                    # first n_held (0 = all): one chip's
                                    # share under expert parallelism
    norm_topk_prob: bool = True     # renormalise the top-k gate weights
    balance_loss: str = "switch"    # switch (over the batch) | seq
                                    # (DeepSeek's, per sequence)
    router_aux_weight: float = 0.01  # the balance loss's alpha

    @property
    def held(self) -> int:
        return self.n_held or self.n_experts


@dataclass(frozen=True)
class MLACfg:
    kv_lora_rank: int = 512
    q_lora_rank: int = 0            # 0 = full-rank q projection (V2-Lite)
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128


@dataclass(frozen=True)
class YaRNCfg:
    """YaRN rope scaling (arXiv:2309.00071) as DeepSeek-V2 applies it:
    frequencies ramped between extrapolation and interpolation by
    ``factor``, and the attention's softmax scale multiplied by
    ``yarn_mscale(factor, mscale_all_dim)`` squared."""
    factor: float
    original_max_position: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 0.0


@dataclass(frozen=True)
class SSMCfg:
    d_state: int = 128
    d_conv: int = 4
    expand: int = 2
    headdim: int = 64
    ngroups: int = 1
    chunk_size: int = 256
    dt_min: float = 0.001
    dt_max: float = 0.1


@dataclass(frozen=True)
class LayerSpec:
    """Kinds for one layer: mixer in {attn, mamba}, ffn in {dense, moe, none}.

    ``window`` > 0 selects sliding-window attention for this layer (gemma2
    local layers). ``window == 0`` means full (global) attention.
    """
    mixer: str = "attn"
    ffn: str = "dense"
    window: int = 0


@dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str                      # dense | moe | ssm | hybrid | encdec | vlm | audio | cnn
    d_model: int
    n_layers: int
    n_heads: int = 0
    n_kv_heads: int = 0
    head_dim: int = 0
    d_ff: int = 0
    vocab_size: int = 0
    prologue: Tuple[LayerSpec, ...] = ()
    pattern: Tuple[LayerSpec, ...] = (LayerSpec("attn", "dense"),)
    # attention details
    attn_kind: str = "gqa"           # gqa | mla
    qkv_bias: bool = False
    qk_norm: bool = False
    rope_theta: float = 1e4
    rope_scaling: Optional[YaRNCfg] = None
    attn_softcap: float = 0.0        # gemma2: 50.0
    final_softcap: float = 0.0       # gemma2: 30.0
    norm_kind: str = "rmsnorm"       # rmsnorm | layernorm
    norm_eps: float = 1e-6
    act: str = "silu"                # silu | gelu
    glu: bool = True                 # gated MLP (swiglu/geglu) vs plain 2-matmul
    post_norm: bool = False          # gemma2-style post-sublayer norms
    tie_embeddings: bool = True
    embed_scale: bool = False        # gemma: multiply embeddings by sqrt(d)
    # sub-configs
    moe: Optional[MoECfg] = None
    mla: Optional[MLACfg] = None
    ssm: Optional[SSMCfg] = None
    # encoder-decoder (whisper)
    encdec: bool = False
    n_enc_layers: int = 0
    enc_seq: int = 1500              # precomputed frame embeddings (frontend stub)
    # numerics / implementation selection
    dtype: str = "bfloat16"          # compute dtype
    param_dtype: str = "float32"
    attn_impl: str = "chunked"       # naive | chunked | pallas
    ssd_impl: str = "chunked"        # scan | chunked | pallas
    remat: bool = True
    remat_group: int = 1             # >1: two-level (sqrt) remat — the
                                     # layer scan saves one residual per
                                     # GROUP of this many periods
    q_chunk: int = 512
    kv_chunk: int = 1024
    loss_chunk: int = 0              # >0: chunked CE (never materializes
                                     # the full (tokens, vocab) logits)

    # -- derived -----------------------------------------------------------
    @property
    def n_periods(self) -> int:
        body = self.n_layers - len(self.prologue)
        if self.pattern:
            assert body % len(self.pattern) == 0, (
                f"{self.name}: {body} body layers not divisible by pattern "
                f"of {len(self.pattern)}")
            return body // len(self.pattern)
        return 0

    @property
    def resolved_head_dim(self) -> int:
        return self.head_dim if self.head_dim else self.d_model // max(self.n_heads, 1)

    def replace(self, **kw) -> "ModelConfig":
        return dataclasses.replace(self, **kw)

    def layer_specs(self) -> Tuple[LayerSpec, ...]:
        """Flattened per-layer specs, prologue first."""
        return self.prologue + self.pattern * self.n_periods


@dataclass(frozen=True)
class CPSLConfig:
    """Cluster-based Parallel Split Learning hyper-parameters (paper §IV)."""
    cut_layer: int = 2               # v: blocks [0, v) are device-side
    n_clusters: int = 6              # M
    cluster_size: int = 5            # K_m devices per cluster
    local_epochs: int = 1            # L
    lr_device: float = 0.05          # eta_d
    lr_server: float = 0.25          # eta_e
    batch_per_device: int = 16       # B
    optimizer: str = "sgd"           # sgd | momentum | adamw
    momentum: float = 0.0
    weight_decay: float = 0.0
    fused_step: bool = True          # fused autodiff vs explicit 2-phase protocol
    fused_round: bool = False        # whole-round lax.scan path: trainers use
                                     # CPSL.run_round_fused (device-resident
                                     # data, in-jit batch gather, FedAvg folded
                                     # into the scan) instead of per-step jits
    fused_round_unroll: int = 0      # scan unroll for the fused round; 0 = full
                                     # unroll (XLA:CPU lowers conv grads inside
                                     # while-loop bodies to its naive emitter,
                                     # ~40x slower, measured on XLA:CPU)
    unroll_clients: bool = False     # trace-time unroll of the K-client device
                                     # pass instead of jax.vmap: vmap over
                                     # per-client weights lowers conv grads to
                                     # grouped convolutions (~10x slower on
                                     # XLA:CPU); ULP-level lowering differences
                                     # vs the vmapped form (tested)
    straggler_dropout: float = 0.0   # fraction of clients allowed to miss FedAvg
    compress_uploads: str = "none"   # none | topk | int8 (device-model uploads)
    compress_topk: float = 0.1
    scan_rounds: bool = False        # run_training_fused round axis as a
                                     # lax.scan (R-independent compile) instead
                                     # of a trace-time unroll; needs a
                                     # loop-body-safe lowering on XLA:CPU —
                                     # pair with conv_impl="im2col" (direct
                                     # conv grads in while bodies hit the
                                     # naive emitter, ~36x, measured)
    conv_impl: str = "direct"        # lenet conv lowering: "direct" (lax conv,
                                     # fastest solo) | "im2col" (matmul form —
                                     # batches cleanly under vmap over client/
                                     # replica weights and stays fast inside
                                     # scans; forward equal up to summation
                                     # order, tested).
                                     # Consumed at split-model build time
                                     # (make_split_model("lenet", v,
                                     # conv_impl=...))


@dataclass(frozen=True)
class FleetConfig:
    """Experiment fleet: E = len(seeds) x len(cluster_sizes) x len(lrs)
    CPSL training replicas executed as ONE batched program
    (``CPSL.run_fleet``; built/driven by ``train.trainer.FleetRunner``).

    Replicas differ only in data — per-replica seeds (init + non-IID
    shard draws + batch streams), cluster layouts padded to the grid's
    (max M, max K) with masks, and learning rates applied as traced
    scalars — so the whole grid shares one XLA compile."""
    rounds: int = 10
    seeds: Tuple[int, ...] = (0,)
    cluster_sizes: Tuple[int, ...] = (5,)   # N_m grid axis (fig. 6)
    lr_scales: Tuple[float, ...] = ()       # lr grid axis, multiplying the
                                            # CPSLConfig lrs; () = base lr only
    n_devices: int = 30                     # N (shards drawn per seed)
    eval_every: int = 0                     # in-jit eval cadence; 0 = off
    samples_per_device: int = 180           # non-IID shard size

    @property
    def n_replicas(self) -> int:
        return (len(self.seeds) * len(self.cluster_sizes)
                * max(len(self.lr_scales), 1))

    def replace(self, **kw):
        return dataclasses.replace(self, **kw)


@dataclass
class SimCfg:
    """Dynamic-network simulation (``repro.sim``): round/timescale layout
    of one end-to-end "train under dynamics" run."""
    rounds: int = 20                 # small-timescale slots == CPSL rounds
    epoch_len: int = 5               # rounds per large timescale epoch (Alg. 2 rerun)
    cluster_size: int = 5            # target K; clusters shrink under churn
    saa_samples: int = 3             # J network samples per SAA evaluation
    saa_gibbs_iters: int = 40        # Gibbs iters inside the SAA inner loop
    gibbs_iters: int = 120           # Gibbs iters for the per-slot plan
    gibbs_chains: int = 1            # lockstep Gibbs replicas per plan
                                     # (best-of-R; chain 0 == single-chain
                                     # stream, so 1 reproduces the looped
                                     # planner bit-exactly)
    cuts: Optional[Tuple[int, ...]] = None  # candidate cut layers (None = all)
    trace_path: Optional[str] = None # JSONL trace destination
    seed: int = 0
    # -- population-scale planning knobs -----------------------------------
    plan_mode: str = "flat"          # "flat" = one Gibbs over all devices;
                                     # "bucketed" = hierarchical two-level
                                     # clustering (bucket_devices + per-
                                     # bucket lockstep Gibbs). With
                                     # n <= bucket_size the bucketed plan
                                     # is bit-identical to flat (tested)
    bucket_size: int = 320           # target devices per coarse bucket
    spectrum_topk: int = 0           # >0: greedy Alg. 3 argmins scan only
                                     # the k worst-score devices per step
                                     # (k >= cluster size is exact)

    def replace(self, **kw):
        return dataclasses.replace(self, **kw)


@dataclass(frozen=True)
class SimFleetCfg:
    """Episode fleet: E = cuts x policies x cluster_sizes x seeds dynamic-
    network episodes priced as ONE jitted/vmapped program
    (``repro.sim.fleet.SimFleetRunner``).

    Episodes differ only in data — per-episode profile constants (cut),
    policy/cluster-size selectors, device means and innovation streams
    (seed) — so the whole grid shares one XLA compile. Episodes with the
    same ``seed`` share their network realization (means + fading/compute
    innovations, and the churn/planner draws), which gives
    common-random-number coupling across the other grid axes (the fig. 7
    cut sweep and the fig. 8(b) three-arm comparison rely on it).

    The ``proposed`` policy is the paper's full two-timescale controller
    run inside the jit: per-slot Gibbs clustering with embedded greedy
    (Alg. 3/4, ``gibbs_iters`` sweeps, best of ``gibbs_chains`` lockstep
    chains) and — when ``saa_cuts`` is set — Alg. 2 SAA cut re-selection
    every ``epoch_len`` slots over the (cut x sample x chain) grid
    around the episode's device means. ``saa_cuts=None`` keeps the
    episode's spec cut fixed (pure small-timescale planning)."""
    rounds: int = 20                        # slots T per episode
    seeds: Tuple[int, ...] = (0,)
    policies: Tuple[str, ...] = ("greedy",)  # equal | greedy | proposed
    cluster_sizes: Tuple[int, ...] = (5,)   # target K per episode
    cuts: Tuple[int, ...] = (3,)            # cut layer v per episode
    batch_per_device: int = 16              # B in the eq. 15-25 cost model
    local_epochs: int = 1                   # L
    mean_seed: Optional[int] = None         # shared device_means seed;
                                            # None = per-episode seed
    # -- proposed-policy (two-timescale controller) knobs ------------------
    epoch_len: int = 5                      # slots per large-timescale epoch
    gibbs_iters: int = 120                  # Alg. 4 sweeps per slot plan
    gibbs_chains: int = 1                   # best-of-R lockstep chains
    gibbs_delta: float = 1e-4               # Metropolis temperature
    saa_samples: int = 3                    # J network samples per SAA cell
    saa_gibbs_iters: int = 40               # Alg. 4 sweeps inside SAA
    saa_cuts: Optional[Tuple[int, ...]] = None  # Alg. 2 candidate cuts;
                                            # None = no SAA (fixed spec cut)
    # -- stochastic-churn support ------------------------------------------
    n_reserve: int = 0                      # reserve device rows for
                                            # Bernoulli arrivals (p_arrive)
    min_devices_floor: bool = False         # honor DynamicsCfg.min_devices
                                            # (opt-in: False keeps every
                                            # departure/depletion executing)
    cost_chunk: int = 0                     # >0: stream the in-jit greedy
                                            # candidate tensors through
                                            # lax.map in tiles of this many
                                            # clusters (bounds peak memory;
                                            # decisions unchanged, tested)

    @property
    def n_episodes(self) -> int:
        return (len(self.cuts) * len(self.policies)
                * len(self.cluster_sizes) * len(self.seeds))

    def replace(self, **kw):
        return dataclasses.replace(self, **kw)
