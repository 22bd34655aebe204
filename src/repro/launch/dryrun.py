import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^ REQUIRED first lines: jax locks the device count at first init. The
# dry-run (and only the dry-run) builds the 256/512-chip production mesh
# out of host placeholder devices. Tests/benches must see 1 device.
if os.environ.get("REPRO_DRYRUN_DEVICES"):
    os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count="
                               + os.environ["REPRO_DRYRUN_DEVICES"])

"""Multi-pod dry-run (deliverable e).

For every (architecture x input-shape) cell, build the production mesh,
jit the corresponding step with explicit in/out shardings,
``.lower().compile()`` it, and record:
  - memory_analysis()  (per-device bytes: proves it fits),
  - cost_analysis()    (XLA's own numbers, loop bodies counted once),
  - the loop-aware HLO parse (FLOPs / HBM bytes / collective bytes),
  - the three roofline terms + MODEL_FLOPS ratio (deliverable g).

Usage:
    python -m repro.launch.dryrun --arch qwen3-32b --cell train_4k \
        --mesh pod1 --out experiments/dryrun
    python -m repro.launch.dryrun --all --mesh pod2
Variants (perf iterations) override config fields:
    --override remat=False --override attn_impl=naive --tag noremat
"""
import argparse
import dataclasses
import json
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import streams
from repro import optim
from repro.configs import registry
from repro.configs.base import CPSLConfig, SHAPES, ModelConfig, ShapeCfg
from repro.core import partitioning as pt
from repro.core.cpsl import CPSL
from repro.core.splitting import make_split_model
from repro.launch import hlo_analysis
from repro.launch.mesh import make_production_mesh
from repro.launch.roofline import roofline_terms
from repro.models import api
from repro.models import transformer as tfm
from repro.models import whisper as whp

# realistic default cut layers (shallow per the paper's POOL1 finding;
# below the first MoE block where one exists so expert banks stay
# server-side)
DEFAULT_CUTS = {
    "deepseek-v2-lite-16b": 1, "phi3.5-moe-42b-a6.6b": 1,
    "jamba-v0.1-52b": 1, "whisper-small": 2,
}

# grad-accumulation splits. Measured: with the
# fsdp profile at global_batch 256 == chip count, m=2 drops the per-step
# batch BELOW the chip count, the 'model' axis falls out of the batch
# sharding, and activations replicate 16x (compute term x15). Microbatching
# only helps when batch > chips; all cells here default to 1.
DEFAULT_MICROBATCHES = {}


def default_cut(cfg: ModelConfig) -> int:
    return DEFAULT_CUTS.get(cfg.name, 2)


def best_remat_group(n_periods: int) -> int:
    """Divisor of n_periods nearest sqrt(n_periods) (sqrt-remat)."""
    import math as _m
    best, target = 1, _m.sqrt(max(n_periods, 1))
    for d in range(1, n_periods + 1):
        if n_periods % d == 0 and abs(d - target) < abs(best - target):
            best = d
    return best


# --------------------------------------------------------------------------
# sharding builders
# --------------------------------------------------------------------------

def _client_axes(mesh, K=None):
    """Mesh axes for the stacked client dim, per the ACTIVE profile rules
    (fit to K when given)."""
    r = pt._resolve("clients")
    if r is None:
        return ()
    axes = r if isinstance(r, tuple) else (r,)
    if K is not None:
        fitted = pt._fit(tuple(axes), K)
        if fitted is None:
            return ()
        axes = fitted if isinstance(fitted, tuple) else (fitted,)
    return tuple(axes)


def dev_shardings(tree, mesh):
    """Stacked-client param trees: leading K axis per the profile's
    'clients' rule, inner dims by the param rules minus the client axes."""
    inner = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape[1:], s.dtype), tree)
    specs = pt.param_specs(inner)

    def mk(leaf_spec, leaf):
        K = leaf.shape[0]
        ca = _client_axes(mesh, K)

        def strip(ax):
            if ax is None:
                return None
            parts = ax if isinstance(ax, tuple) else (ax,)
            rest = tuple(a for a in parts if a not in ca)
            if not rest:
                return None
            return rest if len(rest) > 1 else rest[0]

        return NamedSharding(mesh, P(ca if ca else None,
                                     *[strip(a) for a in leaf_spec]))

    return jax.tree.map(mk, specs, tree,
                        is_leaf=lambda x: isinstance(x, P))


def srv_shardings(tree, mesh):
    specs = pt.param_specs(tree)
    return jax.tree.map(lambda sp: NamedSharding(mesh, sp), specs,
                        is_leaf=lambda x: isinstance(x, P))


def state_shardings(state_shapes, mesh):
    out = {}
    for key, sub in state_shapes.items():
        if key in ("dev", "dev_opt", "ef"):
            out[key] = dev_shardings(sub, mesh)
        elif key in ("srv", "srv_opt"):
            out[key] = srv_shardings(sub, mesh)
        else:
            out[key] = jax.tree.map(
                lambda _: NamedSharding(mesh, P()), sub)
    return out


def batch_shardings(batch_shapes, mesh, leading_clients=True):
    """(K, B, ...) batches: K per the clients rule; B picks up whatever
    batch-rule axes remain (fsdp: B shards over 'model')."""
    def mk(s):
        K = s.shape[0]
        ca = _client_axes(mesh, K)
        r = pt._resolve("batch")
        all_ax = (r if isinstance(r, tuple) else (r,)) if r else ()
        leftover = tuple(a for a in all_ax if a not in ca)
        b_ax = None
        if leading_clients and len(s.shape) > 1 and leftover:
            b_ax = pt._fit(leftover, s.shape[1])
        rest = (None,) * max(0, len(s.shape) - 2)
        return NamedSharding(mesh, P(ca if ca else None, b_ax, *rest))

    return jax.tree.map(mk, batch_shapes)


def cache_shardings(cache_shapes, mesh, long_ctx: bool):
    all_ax = tuple(a for a in ("pod", "data", "model") if a in mesh.axis_names)
    flat = jax.tree_util.tree_flatten_with_path(cache_shapes)[0]
    specs = []
    for path, leaf in flat:
        keys = [str(getattr(p, "key", getattr(p, "idx", ""))) for p in path]
        stacked = "stack" in keys
        name = keys[-1]
        nd = leaf.ndim - (1 if stacked else 0)
        bdim = leaf.shape[1] if stacked else leaf.shape[0]
        if long_ctx:
            batch_ax, seq_ax = None, all_ax
        else:
            batch_ax = _client_axes(mesh, bdim) or None
            seq_ax = "model"
        if name in ("k", "v", "mk", "mv"):      # (B, S, G, hd)
            sp = (batch_ax, seq_ax, None, None)
        elif name in ("ckv", "kr"):             # (B, S, r)
            sp = (batch_ax, seq_ax, None)
        elif name == "conv":                    # (B, K-1, C)
            sp = (batch_ax, None, "model" if not long_ctx else None)
        elif name == "ssm":                     # (B, H, N, P)
            sp = (batch_ax, "model" if not long_ctx else "model", None, None)
        else:
            sp = (None,) * nd
        sp = sp[:nd] + (None,) * max(0, nd - len(sp))
        if stacked:
            sp = (None,) + sp
        specs.append(NamedSharding(mesh, P(*sp)))
    return jax.tree_util.tree_unflatten(
        jax.tree_util.tree_structure(cache_shapes), specs)


# --------------------------------------------------------------------------
# cell builders: return (jitted, arg_shapes)
# --------------------------------------------------------------------------

def build_train(cfg: ModelConfig, shape: ShapeCfg, mesh, cut: int,
                cluster_size: int, microbatches: int = 1, ccfg_over=None):
    K = cluster_size
    B = shape.global_batch // K
    assert B >= 1, (shape.global_batch, K)
    ccfg = CPSLConfig(cut_layer=cut, cluster_size=K, batch_per_device=B,
                      optimizer="adamw_mixed", lr_device=1e-4,
                      lr_server=1e-4,
                      microbatches=min(microbatches, B))
    if ccfg_over:
        kw = {}
        for ov in ccfg_over:
            k_, v_ = ov.split("=", 1)
            cur = getattr(ccfg, k_)
            if isinstance(cur, bool):
                v_ = v_ in ("1", "true", "True")
            elif isinstance(cur, int):
                v_ = int(v_)
            elif isinstance(cur, float):
                v_ = float(v_)
            kw[k_] = v_
        ccfg = dataclasses.replace(ccfg, **kw)
    split = make_split_model(cfg, cut)
    cpsl = CPSL(split, ccfg)
    state_shapes = jax.eval_shape(cpsl.init_state, streams.warmup_key())
    sds = jax.ShapeDtypeStruct
    batch_shapes = {"tokens": sds((K, B, shape.seq_len), jnp.int32),
                    "labels": sds((K, B, shape.seq_len), jnp.int32)}
    if cfg.encdec:
        batch_shapes["frames"] = sds((K, B, cfg.enc_seq, cfg.d_model),
                                     jnp.dtype(cfg.dtype))
        batch_shapes["tokens"] = sds((K, B, shape.seq_len), jnp.int32)
    st_sh = state_shardings(state_shapes, mesh)
    b_sh = batch_shardings(batch_shapes, mesh)
    step_impl = (cpsl.fused_step_impl if ccfg.fused_step
                 else cpsl.protocol_step_impl)
    m_sh = jax.tree.map(lambda _: NamedSharding(mesh, P()),
                        jax.eval_shape(step_impl, state_shapes,
                                       batch_shapes)[1])

    def step(state, batch):
        return step_impl(state, batch)

    jitted = jax.jit(step, in_shardings=(st_sh, b_sh),
                     out_shardings=(st_sh, m_sh), donate_argnums=0)
    return jitted, (state_shapes, batch_shapes)


def build_prefill(cfg: ModelConfig, shape: ShapeCfg, mesh):
    sds = jax.ShapeDtypeStruct
    params_shapes = jax.eval_shape(lambda k: api.init(k, cfg),
                                   streams.warmup_key())
    batch_shapes = {"tokens": sds((shape.global_batch, shape.seq_len),
                                  jnp.int32)}
    if cfg.encdec:
        batch_shapes["frames"] = sds(
            (shape.global_batch, cfg.enc_seq, cfg.d_model),
            jnp.dtype(cfg.dtype))
    p_sh = srv_shardings(params_shapes, mesh)
    b_sh = batch_shardings(batch_shapes, mesh, leading_clients=False)

    def step(params, batch):
        return api.prefill(params, batch, cfg, cap=shape.seq_len)

    jitted = jax.jit(step, in_shardings=(p_sh, b_sh))
    return jitted, (params_shapes, batch_shapes)


def build_decode(cfg: ModelConfig, shape: ShapeCfg, mesh, long_ctx: bool):
    sds = jax.ShapeDtypeStruct
    gb, S = shape.global_batch, shape.seq_len
    params_shapes = jax.eval_shape(lambda k: api.init(k, cfg),
                                   streams.warmup_key())
    if cfg.encdec:
        def mkcache():
            b = {"tokens": jnp.zeros((gb, 8), jnp.int32),
                 "frames": jnp.zeros((gb, cfg.enc_seq, cfg.d_model),
                                     jnp.dtype(cfg.dtype))}
            return whp.prefill(params := api.init(streams.warmup_key(), cfg),
                               b, cfg, cap=S)[1]
        cache_shapes = jax.eval_shape(mkcache)
    else:
        cache_shapes = jax.eval_shape(
            lambda: tfm.init_cache(cfg, gb, S, long_ctx))
    tok_shapes = sds((gb,), jnp.int32)
    pos_shape = sds((), jnp.int32)
    p_sh = srv_shardings(params_shapes, mesh)
    c_sh = cache_shardings(cache_shapes, mesh, long_ctx)
    ca = _client_axes(mesh, gb)
    t_sh = NamedSharding(mesh, P(ca if ca else None))

    def step(params, cache, tokens, pos):
        return api.decode_step(params, cache, tokens, pos, cfg)

    vocab_ax = "model" if cfg.vocab_size % mesh.shape["model"] == 0 else None
    jitted = jax.jit(step, in_shardings=(p_sh, c_sh, t_sh,
                                         NamedSharding(mesh, P())),
                     out_shardings=(NamedSharding(mesh, P(
                         ca if ca else None, vocab_ax)), c_sh),
                     donate_argnums=1)
    return jitted, (params_shapes, cache_shapes, tok_shapes, pos_shape)


# --------------------------------------------------------------------------
# runner
# --------------------------------------------------------------------------

def apply_overrides(cfg: ModelConfig, overrides):
    kw = {}
    for ov in overrides or []:
        k, v = ov.split("=", 1)
        cur = getattr(cfg, k)
        if isinstance(cur, bool):
            v = v in ("1", "true", "True")
        elif isinstance(cur, int):
            v = int(v)
        elif isinstance(cur, float):
            v = float(v)
        kw[k] = v
    return cfg.replace(**kw) if kw else cfg


def run_cell(arch: str, cell: str, mesh_name: str, out_dir: str,
             overrides=None, tag: str = "", cut: int = None,
             cluster_size: int = None, profile: str = None,
             ccfg_over=None) -> dict:
    t_start = time.time()
    cfg = apply_overrides(registry.get(arch), overrides)
    shape = SHAPES[cell]
    multi_pod = mesh_name == "pod2"
    if mesh_name == "tiny":
        mesh = jax.make_mesh((2, 4), ("data", "model"))
    else:
        mesh = make_production_mesh(multi_pod=multi_pod)
    n_dev = mesh.size

    if profile is None:
        # production defaults: train cells use the pure-FSDP layout (batch
        # over all chips; activations and weight gathers halve with bf16
        # params + f32 masters); serving cells use TP.
        profile = "fsdp" if shape.kind == "train" else "tp"
    with pt.use_mesh(mesh, profile=profile):
        if shape.kind == "train":
            K = cluster_size or (32 if multi_pod else 16)
            if mesh_name == "tiny":
                K = 8
            if cfg.loss_chunk == 0:
                cfg = cfg.replace(loss_chunk=2048)   # chunked CE (prod default)
            if cfg.param_dtype == "float32":
                cfg = cfg.replace(param_dtype="bfloat16")
            v = cut or default_cut(cfg)
            explicit_rg = any(o.startswith("remat_group=")
                              for o in (overrides or []))
            if cfg.remat_group == 1 and cfg.pattern and not cfg.encdec \
                    and not explicit_rg:
                from repro.core.splitting import _split_cfgs
                _, srv_cfg = _split_cfgs(cfg, v)
                cfg = cfg.replace(remat_group=best_remat_group(
                    max(srv_cfg.n_periods, 1)))
            jitted, shapes = build_train(
                cfg, shape, mesh, v, K,
                microbatches=DEFAULT_MICROBATCHES.get(arch, 1),
                ccfg_over=ccfg_over)
        elif shape.kind == "prefill":
            jitted, shapes = build_prefill(cfg, shape, mesh)
        else:
            jitted, shapes = build_decode(cfg, shape, mesh,
                                          long_ctx=cell == "long_500k")
        t0 = time.time()
        lowered = jitted.lower(*shapes)
        t_lower = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0

    rec = {"arch": arch, "cell": cell, "mesh": mesh_name, "tag": tag,
           "profile": profile, "ccfg": list(ccfg_over or []),
           "n_devices": n_dev, "lower_s": round(t_lower, 2),
           "compile_s": round(t_compile, 2),
           "overrides": list(overrides or [])}
    try:
        ma = compiled.memory_analysis()
        rec["memory"] = {
            "argument_bytes_per_device": ma.argument_size_in_bytes,
            "output_bytes_per_device": ma.output_size_in_bytes,
            "temp_bytes_per_device": ma.temp_size_in_bytes,
            "alias_bytes_per_device": ma.alias_size_in_bytes,
            "peak_bytes_per_device": (ma.argument_size_in_bytes
                                      + ma.output_size_in_bytes
                                      + ma.temp_size_in_bytes
                                      - ma.alias_size_in_bytes),
        }
    except Exception as e:                      # pragma: no cover
        rec["memory"] = {"error": str(e)}
    try:
        ca = compiled.cost_analysis()
        rec["xla_cost"] = {"flops": ca.get("flops", -1.0),
                           "bytes_accessed": ca.get("bytes accessed", -1.0)}
    except Exception as e:                      # pragma: no cover
        rec["xla_cost"] = {"error": str(e)}
    parsed = hlo_analysis.report(compiled.as_text())
    rec["parsed"] = parsed
    rl = roofline_terms(parsed, n_dev, cfg, shape)
    rec["roofline"] = rl.to_dict()
    rec["total_s"] = round(time.time() - t_start, 2)

    os.makedirs(out_dir, exist_ok=True)
    suffix = f"__{tag}" if tag else ""
    fn = os.path.join(out_dir, f"{arch}__{cell}__{mesh_name}{suffix}.json")
    with open(fn, "w") as f:
        json.dump(rec, f, indent=1)
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--cell", default=None,
                    choices=list(SHAPES) + [None])
    ap.add_argument("--mesh", default="pod1",
                    choices=["pod1", "pod2", "tiny"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--override", action="append", default=[])
    ap.add_argument("--tag", default="")
    ap.add_argument("--cut", type=int, default=None)
    ap.add_argument("--cluster-size", type=int, default=None)
    ap.add_argument("--profile", default=None, choices=["tp", "fsdp"])
    ap.add_argument("--ccfg", action="append", default=[],
                    help="CPSLConfig overrides, e.g. fused_step=False")
    args = ap.parse_args()

    cells = []
    if args.all:
        for arch in registry.list_archs():
            for cell in registry.cells(arch):
                cells.append((arch, cell))
    else:
        assert args.arch and args.cell
        cells = [(args.arch, args.cell)]

    failures = []
    for arch, cell in cells:
        try:
            rec = run_cell(arch, cell, args.mesh, args.out,
                           overrides=args.override, tag=args.tag,
                           cut=args.cut, cluster_size=args.cluster_size,
                           profile=args.profile, ccfg_over=args.ccfg)
            rl = rec["roofline"]
            print(f"[OK] {arch:24s} {cell:12s} {args.mesh}: "
                  f"compile {rec['compile_s']}s "
                  f"mem/dev {rec['memory'].get('peak_bytes_per_device', -1)/1e9:.2f}GB "
                  f"compute {rl['compute_s']*1e3:.2f}ms "
                  f"mem {rl['memory_s']*1e3:.2f}ms "
                  f"coll {rl['collective_s']*1e3:.2f}ms "
                  f"-> {rl['bottleneck']}", flush=True)
        except Exception as e:
            failures.append((arch, cell, str(e)))
            print(f"[FAIL] {arch} {cell}: {e}", flush=True)
            traceback.print_exc()
    if failures:
        raise SystemExit(f"{len(failures)} cells failed")


if __name__ == "__main__":
    main()
