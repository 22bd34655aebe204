"""DeepSeek-V2-Lite's cell at a test size (``tiny-dsv2``, the launcher's
--reduced sizes of the chip-share arch): the control and the departures a
guessed model would make read ``correct`` false where the system passes,
its FLOP arithmetic against ``lm_profile`` and a count by hand, and the
MoE readers wired by hand on a traced CPU run."""
import dataclasses
import json
import shutil
import sys
import time
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import fault_readings, harness, moe_trace  # noqa: E402
from bench import program_trace, trace  # noqa: E402
from bench import run as bench_run  # noqa: E402
from bench.flops import deepseek_v2 as flops  # noqa: E402

FX = Path(__file__).resolve().parent / "fixtures"
CELL = "tiny-dsv2.quick"
SEED = 3_000_000_021
PEAKS = {"cpu": {"bf16_flops_per_s": 1e12}}
CONFIG = json.loads((ROOT / "bench" / "configs"
                     / "deepseek-v2-lite.json").read_text())
LIMITS = CONFIG["limits"]


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    root = tmp_path_factory.mktemp("bench_root")
    shutil.copytree(ROOT / "bench", root / "bench")
    for sub in ("configs", "traffic"):
        for f in (FX / sub).glob("*.json"):
            shutil.copy(f, root / "bench" / sub / f.name)
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    spec["workloads"] = [{"name": CELL, "config": "tiny-dsv2",
                          "traffic": "quick", "chips": 1, "why": "fixture"}]
    for m in spec["end_to_end"] + spec["per_layer"]:
        m.pop("workloads", None)
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return root


def test_fixture_is_the_launchers_reduced_share():
    """The fixture's sizes are those the launcher's --reduced builds."""
    from repro.configs import registry
    cfg = registry.reduce_for_smoke(registry.get("deepseek-v2-lite-16b-ep8"))
    fx = json.loads((FX / "configs" / "tiny-dsv2.json").read_text())
    m = cfg.moe
    assert (fx["hidden_size"], fx["intermediate_size"], fx["vocab_size"],
            fx["num_hidden_layers"]) == (cfg.d_model, cfg.d_ff,
                                         cfg.vocab_size, cfg.n_layers)
    assert (fx["n_routed_experts"], fx["published"]["n_routed_experts"],
            fx["num_experts_per_tok"], fx["moe_intermediate_size"],
            fx["n_shared_experts"]) == (m.held, m.n_experts, m.top_k,
                                        m.d_ff_expert, m.n_shared_experts)
    assert (fx["kv_lora_rank"], fx["qk_nope_head_dim"],
            fx["qk_rope_head_dim"], fx["v_head_dim"]) == (
        cfg.mla.kv_lora_rank, cfg.mla.qk_nope_head_dim,
        cfg.mla.qk_rope_head_dim, cfg.mla.v_head_dim)
    # its own limits, by the rule at this size: no tighter than shipped
    assert all(fx["limits"][k] >= v for k, v in LIMITS.items())


@pytest.mark.parametrize("yarn", [True, False], ids=["yarn", "plain"])
def test_mla_matches_the_references_attention(yarn):
    """The system's latent attention (YaRN frequencies, mscale^2 in the
    softmax scale) against the plain reference's, in float32 at the
    fixture's sizes, on the same weights."""
    import jax
    from repro.configs import registry
    from repro.models import common as cm
    from bench.reference import numerics
    ref = harness.load_module(ROOT / "bench" / "reference" / "deepseek_v2.py")
    fx = json.loads((FX / "configs" / "tiny-dsv2.json").read_text())
    cfg = registry.reduce_for_smoke(registry.get(fx["launch"]["arch"]))
    cfg = cfg.replace(dtype="float32")
    if not yarn:
        cfg, fx = cfg.replace(rope_scaling=None), dict(fx, rope_scaling=None)
    p = cm.mla_init(jax.random.PRNGKey(1), cfg)
    h = jax.random.normal(jax.random.PRNGKey(2), (2, 16, cfg.d_model))
    with jax.default_matmul_precision("highest"):
        got = cm.mla_apply(p, h, cfg)
        want = ref._attention(p, h, fx, numerics.REF)
    assert float(abs(got - want).max()) < 2e-5 * float(abs(want).max())


def test_control_and_a_fault_fail_where_the_system_passes(root):
    """``bench/fault_readings.py`` at a test size: the system passes the
    fixture's limits, the fp8 control reads at least ten times more and
    fails them, and so does the reference with renormalised top-k weights
    in the system's place (the chip reads the other faults, PERF.md)."""
    cell = harness.load_cell(CELL, root)
    limits = cell.config["limits"]
    r = fault_readings.readings(cell, SEED, ["renormalised_topk"])
    assert all(v <= limits[k] for k, v in r["program"].items()), r
    ratio = max(c / max(r["program"][k], 1e-9)
                for k, c in r["control"].items())
    assert ratio >= 10, r
    for name in ("control", "renormalised_topk"):
        assert any(v > limits[k] for k, v in r[name].items()), (name, r)


# -- the departures, in the system ---------------------------------------------

def _renormalised_topk(monkeypatch):
    from repro.models import common as cm
    route = cm.moe_route
    monkeypatch.setattr(cm, "moe_route", lambda p, x, m: route(
        p, x, dataclasses.replace(m, norm_topk_prob=True)))


def _plain_rope(monkeypatch):
    from repro.models import common as cm
    rope = cm.apply_rope
    monkeypatch.setattr(cm, "apply_rope", lambda x, pos, theta, scaling=None:
                        rope(x, pos, theta))
    monkeypatch.setattr(cm, "mla_softmax_gain", lambda cfg: 1.0)


def _capacity_drop(monkeypatch):
    """GShard's dispatch: capacity 1.25 in groups of 16 tokens."""
    from repro.models import common as cm
    ref = harness.load_module(ROOT / "bench" / "reference" / "deepseek_v2.py")
    route = cm.moe_route

    def dropping(p, x, m):
        probs, w, idx = route(p, x, m)
        return probs, w * ref.capacity_kept(idx, m.n_experts, m.top_k, 16,
                                            1.25), idx

    monkeypatch.setattr(cm, "moe_route", dropping)


@pytest.mark.parametrize("fault", [_renormalised_topk, _plain_rope,
                                   _capacity_drop],
                         ids=lambda f: f.__name__.strip("_"))
def test_departure_in_the_system_is_not_correct(root, monkeypatch, fault):
    fault(monkeypatch)
    result, checks = bench_run.run_cell(CELL, SEED, 0.2, False,
                                        time.monotonic(), root=root,
                                        require_chip=False, peaks=PEAKS)
    assert result["correct"] is False
    assert any(not c["ok"] for c in checks.values())


# -- FLOPs ----------------------------------------------------------------------

def test_profile_is_lm_profile_of_the_registered_arch():
    from repro.configs import registry
    from repro.core.profile import lm_profile
    want = lm_profile(registry.get(CONFIG["launch"]["arch"]), 2048)
    got = flops.profile(CONFIG, 2048)
    for k, a in got.items():
        b = getattr(want, k)
        assert np.max(np.abs(a - b) / np.maximum(np.abs(b), 1.0)) <= 1e-12, k


def test_train_flops_count_one_moe_layer_by_hand():
    S, d, H = 2048, 2048, 16
    attn_params = (d * H * 192 + d * (512 + 64) + 512 * H * (128 + 128)
                   + H * 128 * d)
    attn = 2 * S * attn_params + 2 * S * S * H * (192 + 128)
    router = 2 * S * d * 64
    held = 6 * 8 / 64 * 2 * S * 3 * d * 1408      # 0.75 expert a token
    shared = 2 * 2 * S * 3 * d * 1408
    moe_layer = attn + router + held + shared
    dense_layer = attn + 2 * S * 3 * d * 10944
    head = 2 * S * d * 12800
    want = 3 * (dense_layer + 4 * moe_layer + head)
    assert flops.train_flops_per_sample(CONFIG, S) == pytest.approx(
        want, rel=1e-15)
    assert 1.8e9 < want / S < 1.9e9            # ~1.86 GFLOP a token


def test_gmm_cost_counts_four_passes():
    f, b = flops.gmm_cost(CONFIG, routed=1000, layer_calls=2)
    assert f == 4 * 3 * 2 * 1000 * 2048 * 1408
    assert b == 4 * 2 * (3 * 8 * 2048 * 1408 * 2 + 1000 * 3 * (2048 + 1408))


# -- the MoE readers -------------------------------------------------------------

def test_moe_trace_charges_scopes_and_the_grouped_matmul():
    dev = "/device:TPU:0"
    ops = {dev: [
        ("jit(f)/jvp(server_side)/mla/dot_general", 0.0, 1.0),
        ("jit(f)/jvp(server_side)/moe_router/dot_general", 1.0, 1.5),
        ("ragged-dot-none", 1.5, 3.0),
        ("jit(f)/transpose(jvp(server_side))/moe_experts/mul", 2.5, 2.75),
        ("jit(f)/jvp(server_side)/moe_combine/dot_general", 3.0, 3.5),
        ("jit(f)/update/sub", 3.5, 4.0),
    ]}
    got = moe_trace.charge(ops, 0.0, 4.0, moe_trace.scope_of)
    assert got == pytest.approx({"mla": 1.0, "moe_router": 0.5,
                                 "moe_experts": 1.5, "moe_combine": 0.5,
                                 "other": 0.5})
    gmm = moe_trace.charge(ops, 0.0, 4.0, lambda op: moe_trace.GMM
                           if moe_trace.is_gmm(op) else "other")
    assert gmm[moe_trace.GMM] == pytest.approx(1.25)   # the mul inside
    assert moe_trace.is_gmm("jit(f)/moe_experts/ragged_dot_general")
    assert moe_trace.is_gmm("ragged-dot-metadata")
    assert not moe_trace.is_gmm("jit(f)/ragged_dot/mul")
    history = [{"counts": {"moe_routed": 600.0, "moe_load_max": 150.0}}] * 2
    moe = moe_trace.counters(history, CONFIG)
    # 2 rounds x 2 clusters x 4 MoE layers = 16 calls of 8 experts
    assert moe["layer_calls"] == 16
    assert moe["load_ratio"] == pytest.approx(150.0 / (1200.0 / 128))
    ctx = {"rounds": 2, "moe_scopes": got, "moe": moe,
           "peak_flops": 197e12, "peak_bytes_per_s": 819e9,
           "gmm": {"seconds": 1e-3, "flops": 1e11, "bytes": 1e8}}
    read = {n: harness.load_module(ROOT / "bench" / "metrics"
                                   / f"{n}.py").read(ctx)
            for n in ("moe_ms", "gmm_roofline", "moe_load_ratio")}
    assert read["moe_ms"] == pytest.approx(1e3 * 2.5 / 2)
    assert read["gmm_roofline"] == pytest.approx(100 * (1e11 / 197e12) / 1e-3)
    assert read["moe_load_ratio"] == moe["load_ratio"]


def test_moe_readers_on_a_traced_cpu_run(root, monkeypatch):
    """Through ``run_cell``, traced: the round records carry the MoE
    counters, read with no sync of their own; the device-trace readers
    read nothing on a CPU, the load ratio reads the counters."""
    from repro.train.trainer import CPSLTrainer
    histories, found = [], {}
    run = CPSLTrainer.run

    def keep_history(self, *a, **kw):
        histories.append(self.history)
        return run(self, *a, **kw)

    load = trace.load

    def keep_trace(path):
        events = load(path)
        found.update(events=events, ops=program_trace.device_ops(path))
        return events

    monkeypatch.setattr(CPSLTrainer, "run", keep_history)
    monkeypatch.setattr(trace, "load", keep_trace)
    result, _ = bench_run.run_cell(CELL, SEED, 0.2, True, time.monotonic(),
                                   root=root, require_chip=False,
                                   peaks=PEAKS)
    assert result["correct"] is True
    rounds = result["attempted"]
    history = histories[-1][-rounds:]
    assert all(h["counts"]["syncs"] == 1 for h in history)
    assert all(h["counts"]["moe_routed"] > 0 for h in history)
    cell = harness.load_cell(CELL, root)
    ctx = {"rounds": rounds, "peak_flops": 1e12, "peak_bytes_per_s": 1e11}
    ctx.update(moe_trace.context(found["events"], found["ops"], history,
                                 cell.config))
    got = {n: harness.load_module(root / "bench" / "metrics"
                                  / f"{n}.py").read(ctx)
           for n in ("moe_ms", "gmm_roofline", "moe_load_ratio")}
    assert got["moe_load_ratio"] >= 1.0
    assert got["moe_ms"] is None and got["gmm_roofline"] is None
