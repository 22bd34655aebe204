"""Trace-schema tests: parse -> emit roundtrip identity, producer
dispatch, writer/loader, and compatibility of rt traces with the sim
repricer."""
import numpy as np
import pytest

from repro.core.channel import NetworkCfg
from repro.core.profile import lenet_profile
from repro.sim.engine import recompute_trace_latencies
from repro.telemetry import (QoSRecord, RoundRecord, TraceWriter, jsonable,
                             load_trace, parse_record)


def _round_dict():
    return {"round": 2, "v": 3, "stale": False, "n_active": 4,
            "ids": [0, 1, 2, 3], "f": [1e9, 2e9], "rate": [1e6, 2e6],
            "clusters": [[0, 1], [2, 3]], "xs": [[2, 2], [2, 2]],
            "planned_latency_s": 1.5, "wall_s": 0.2, "loss": 2.1,
            "dropped": [], "source": "rt"}


def test_round_record_roundtrip_identity():
    d = _round_dict()
    rec = parse_record(d)
    assert isinstance(rec, RoundRecord)
    assert rec.to_dict() == d
    # and again: to_dict -> from_dict -> to_dict is stable
    assert parse_record(rec.to_dict()).to_dict() == d


def test_qos_record_roundtrip_and_dispatch():
    d = {"round": 1, "device": 3, "phase": "upload", "t_s": 0.01,
         "kind": "qos", "cluster": 0, "epoch": 2, "ok": True}
    rec = parse_record(d)
    assert isinstance(rec, QoSRecord)
    assert rec.to_dict() == d


def test_unknown_keys_land_in_extras_and_survive():
    d = dict(_round_dict(), custom_key={"a": 1})
    rec = parse_record(d)
    assert rec.extras == {"custom_key": {"a": 1}}
    assert rec.to_dict() == d


def test_none_fields_are_omitted():
    rec = RoundRecord(round=0, skipped="empty")
    d = rec.to_dict()
    assert d == {"round": 0, "skipped": "empty"}


def test_jsonable_numpy_and_nested():
    out = jsonable({"a": np.int64(3), "b": np.float32(0.5),
                    "c": np.arange(3), "d": (np.ones(2), "s")})
    assert out == {"a": 3, "b": 0.5, "c": [0, 1, 2], "d": [[1.0, 1.0], "s"]}
    assert isinstance(out["a"], int) and isinstance(out["b"], float)


def test_writer_appends_and_loads(tmp_path):
    path = str(tmp_path / "trace.jsonl")
    w = TraceWriter(path, fresh=True)
    w.emit(RoundRecord(round=0, wall_s=0.1))
    w.emit({"round": 0, "device": 1, "phase": "fwd", "t_s": 0.01,
            "kind": "qos", "np": np.float64(2.0)})
    lines = load_trace(path)
    assert lines == w.records and len(lines) == 2
    assert lines[1]["np"] == 2.0          # jsonable applied to raw dicts
    # fresh=True truncates
    TraceWriter(path, fresh=True)
    assert load_trace(path) == []


def test_memory_only_writer():
    w = TraceWriter(None)
    w.emit(RoundRecord(round=1))
    assert w.records == [{"round": 1}]


def test_repricer_skips_qos_and_skipped_records():
    """An rt trace (round records + interleaved QoS lines + a skipped
    round) reprices exactly its executable rounds."""
    ncfg = NetworkCfg(n_devices=2, n_subcarriers=4)
    prof = lenet_profile()
    trace = [
        {"round": 0, "v": 2, "clusters": [[0, 1]], "xs": [[2.0, 2.0]],
         "f": [1e9, 2e9], "rate": [1e6, 2e6], "wall_s": 0.5,
         "source": "rt"},
        {"round": 0, "device": 0, "phase": "fwd", "t_s": 0.1,
         "kind": "qos"},
        {"round": 1, "skipped": "empty"},
        {"round": 2, "v": 2, "clusters": [[0, 1]], "xs": [[2.0, 2.0]],
         "f": [1e9, 2e9], "rate": [1e6, 2e6], "wall_s": 0.4,
         "source": "rt"},
    ]
    lats = recompute_trace_latencies(trace, prof, ncfg, B=8, L=1)
    assert lats.shape == (2,) and (lats > 0).all()


def test_fsync_emit_is_immediately_durable(tmp_path):
    """fsync mode: each emitted line is on disk before emit returns —
    no writer-held buffer a SIGKILL could lose."""
    path = str(tmp_path / "trace.jsonl")
    w = TraceWriter(path, fresh=True, fsync=True)
    w.emit({"round": 0, "wall_s": 0.1})
    # read through a separate handle with the writer still "live"
    assert load_trace(path) == [{"round": 0, "wall_s": 0.1}]


def test_load_trace_drops_torn_final_line(tmp_path):
    """A process killed mid-append leaves a torn FINAL line; loading
    drops it with a warning, and a rewrite round-trips the survivors —
    the crash-resume truncation path."""
    import warnings
    path = str(tmp_path / "trace.jsonl")
    w = TraceWriter(path, fresh=True, fsync=True)
    w.emit({"round": 0, "loss": 2.0})
    w.emit({"round": 1, "loss": 1.5})
    with open(path, "a") as f:
        f.write('{"round": 2, "los')        # torn mid-write
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        got = load_trace(path)
    assert got == [{"round": 0, "loss": 2.0}, {"round": 1, "loss": 1.5}]
    assert any(issubclass(c.category, RuntimeWarning) for c in caught)
    # strict mode still refuses the torn tail
    with pytest.raises(ValueError, match="corrupt trace line"):
        load_trace(path, tolerate_torn_tail=False)
    # truncation round-trip: rewrite the survivors, reload bit-identical
    w2 = TraceWriter(path, fresh=False, fsync=True)
    w2.rewrite([r for r in got if r["round"] < 1])
    assert load_trace(path) == [{"round": 0, "loss": 2.0}]


def test_load_trace_midfile_corruption_raises(tmp_path):
    """A malformed line anywhere but the tail is real corruption:
    torn-tail tolerance must not mask it."""
    path = str(tmp_path / "trace.jsonl")
    with open(path, "w") as f:
        f.write('{"round": 0}\n')
        f.write('garbage not json\n')
        f.write('{"round": 1}\n')
    with pytest.raises(ValueError, match="line 2 of 3"):
        load_trace(path)


# -- in-process spans and counters -------------------------------------------

def test_spans_nest_with_parents_and_round():
    from repro import telemetry
    rec = telemetry.Recorder()
    rec.begin_round(7)
    with rec.span("round"):
        with rec.span("plan"):
            with rec.span("cluster"):
                pass
        with rec.span("step"):
            pass
    got = [(s.name, s.parent, s.round) for s in rec.spans]
    assert got == [("cluster", "plan", 7), ("plan", "round", 7),
                   ("step", "round", 7), ("round", None, 7)]
    by = {s.name: s for s in rec.spans}
    assert by["round"].start_ns <= by["plan"].start_ns \
        <= by["cluster"].start_ns <= by["cluster"].end_ns \
        <= by["plan"].end_ns <= by["step"].start_ns <= by["round"].end_ns


def test_phase_s_sums_spans_by_name():
    import time
    from repro import telemetry
    rec = telemetry.Recorder()
    rec.begin_round(0)
    for _ in range(3):
        with rec.span("step"):
            time.sleep(0.002)
    with rec.span("sync"):
        pass
    phase, _ = rec.fold()
    steps = [s.end_ns - s.start_ns for s in rec.spans if s.name == "step"]
    assert phase["step"] == sum(steps) / 1e9
    assert phase["step"] >= 0.006 and set(phase) == {"step", "sync"}


def test_counters_reset_each_round():
    from repro import telemetry
    rec = telemetry.Recorder()
    rec.begin_round(0)
    rec.count("dispatches")
    rec.count("dispatches", 2)
    rec.count("spectrum_s", 0.25)
    _, counts = rec.fold()
    assert counts["dispatches"] == 3 and counts["spectrum_s"] == 0.25
    rec.begin_round(1)
    rec.count("syncs")
    _, counts = rec.fold()
    assert counts == {"syncs": 1, "compiles": 0}
    assert rec.spans == [] and rec.round == 1


def test_compile_counter_counts_backend_compiles():
    import time
    import jax
    from repro import telemetry
    counter = telemetry.compile_counter()
    assert telemetry.compile_counter() is counter     # one per process
    # a constant no other program holds: a new program, compiled here
    f = jax.jit(lambda x: x * 3.0 + float(time.time_ns() % 997))
    x = np.ones(3, np.float32)
    n0 = counter.compiles
    f(x)
    assert counter.compiles == n0 + 1
    f(x)
    assert counter.compiles == n0 + 1


def test_span_shares_the_profiler_clock(tmp_path):
    """A span's in-memory start, less the trace's ``profile_start_time``,
    is where the trace puts its ``cpsl.<name>`` event."""
    import jax
    from jax.profiler import ProfileData
    from repro import telemetry
    rec = telemetry.Recorder()
    rec.begin_round(0)
    jax.profiler.start_trace(str(tmp_path))
    try:
        with rec.span("probe"):
            sum(range(10000))
    finally:
        jax.profiler.stop_trace()
    path = sorted(tmp_path.rglob("*.xplane.pb"))[-1]
    data = ProfileData.from_file(str(path))
    env = next(p for p in data.planes if p.name == "Task Environment")
    start = dict(env.stats)
    events = [e for p in data.planes for line in p.lines for e in line.events
              if e.name == "cpsl.probe"]
    assert len(events) == 1
    span = rec.spans[0]
    offset_ns = span.start_ns - start["profile_start_time"]
    assert abs(events[0].start_ns - offset_ns) < 1e6


def _trainer(tmp_path, fused=False, **kw):
    from repro.configs.base import CPSLConfig
    from repro.core.cpsl import CPSL
    from repro.core.splitting import make_split_model
    from repro.data.pipeline import CPSLDataset
    from repro.data.synthetic import non_iid_split, synthetic_mnist
    from repro.train.trainer import CPSLTrainer, TrainerCfg
    xtr, ytr, _, _ = synthetic_mnist(1500, 100, seed=0)
    idx = non_iid_split(ytr, n_devices=6, samples_per_device=80, seed=0)
    ccfg = CPSLConfig(cut_layer=1, n_clusters=2, cluster_size=3,
                      local_epochs=2, fused_round=fused, unroll_clients=fused)
    tcfg = TrainerCfg(ckpt_dir=str(tmp_path), rounds=2, ckpt_every=2,
                      resource_mgmt="gibbs", gibbs_iters=30, seed=5,
                      async_ckpt=False, **kw)
    return CPSLTrainer(CPSL(make_split_model("lenet", 1), ccfg),
                       CPSLDataset(xtr, ytr, idx, batch=8), lenet_profile(),
                       NetworkCfg(n_devices=6), tcfg)


def test_trainer_round_records_phases_and_counts(tmp_path):
    """A looped round: one dispatch per cluster step and FedAvg, one host
    sync, the planner's Alg. 3 calls as Gibbs' cache misses, and the
    round's spans inside its wall time."""
    import jax
    from repro import streams
    from repro.core import resource as rs
    from repro.core.channel import sample_network
    tr = _trainer(tmp_path)
    tr.run(jax.random.PRNGKey(0))
    M, K, L = 2, 3, 2
    for h in tr.history:
        rnd, counts, phase = h["round"], h["counts"], h["phase_s"]
        assert counts["dispatches"] == M * L + M
        assert counts["syncs"] == 1
        assert counts["gibbs_iters"] == 30
        assert counts["h2d_bytes"] == M * L * K * 8 * (28 * 28 * 4 + 4)
        misses = []

        def spectrum(*a):
            misses.append(a[1])
            return rs.greedy_spectrum(*a)

        net = sample_network(tr.ncfg, tr.mu_f, tr.mu_snr,
                             streams.trainer_round_rng(5, rnd))
        rs.gibbs_clustering(1, net, tr.ncfg, tr.prof,
                            tr.cpsl.ccfg.batch_per_device, L, M, K,
                            iters=30, seed=5 + rnd, spectrum_fn=spectrum)
        assert counts["spectrum_calls"] == len(misses) > 0
        assert counts["spectrum_table_calls"] == counts["spectrum_calls"]
        assert counts["spectrum_tables"] == 1
        assert counts["spectrum_s"] > 0
        assert set(phase) >= {"round", "plan", "network", "cluster",
                              "gather", "step", "fedavg", "sync"}
        inside = sum(phase[n] for n in ("plan", "gather", "step", "fedavg",
                                        "sync"))
        assert inside <= phase["round"] <= h["wall_s"]
        assert phase["network"] + phase["cluster"] <= phase["plan"]
    assert "save" in tr.history[-1]["phase_s"]        # ckpt_every=2
    assert tr.history[0]["counts"]["compiles"] > 0    # the first round
    assert tr.history[1]["counts"]["compiles"] == 0   # compiles, no more


def test_trainer_log_carries_phases_and_counts(tmp_path):
    """The operator's ``--log`` JSONL holds each round's ``phase_s`` and
    ``counts``, and they parse as ``RoundRecord`` fields."""
    import jax
    log = tmp_path / "log.jsonl"
    tr = _trainer(tmp_path / "ckpt", log_path=str(log))
    tr.run(jax.random.PRNGKey(0))
    lines = load_trace(str(log))
    assert [d["round"] for d in lines] == [0, 1]
    for d in lines:
        rec = parse_record(d)
        assert rec.extras.keys() == {"sim_latency_s"}
        assert rec.phase_s["round"] > 0 and rec.counts["syncs"] == 1


def test_fused_round_records_one_sync(tmp_path):
    """The fused round is one dispatch; its ``sync`` span is the wait for
    the device, inside ``round``."""
    import jax
    tr = _trainer(tmp_path, fused=True)
    tr.run(jax.random.PRNGKey(0))
    for h in tr.history:
        assert h["counts"]["syncs"] == 1
        assert "dispatches" not in h["counts"]
        assert h["phase_s"]["sync"] + h["phase_s"]["plan"] \
            <= h["phase_s"]["round"] <= h["wall_s"]


@pytest.mark.parametrize("custom", [False, True])
def test_spectrum_table_counters(custom):
    """One recorded Gibbs plan builds one table, which serves every Alg. 3
    call; a custom ``spectrum_fn`` builds none and is served by none."""
    from repro import telemetry
    from repro.core import resource as rs
    from repro.core.channel import device_means, sample_network
    ncfg = NetworkCfg(n_devices=12, n_subcarriers=24)
    net = sample_network(ncfg, *device_means(ncfg, 3),
                         np.random.default_rng(3))
    telemetry.begin_round(0)
    rs.gibbs_clustering(1, net, ncfg, lenet_profile(), 16, 1, 4, 3,
                        iters=40, seed=1,
                        spectrum_fn=rs.greedy_spectrum if custom else None)
    _, counts = telemetry.fold()
    assert counts["spectrum_calls"] > 4
    if custom:
        assert counts.get("spectrum_table_calls", 0) == 0
        assert counts.get("spectrum_tables", 0) == 0
    else:
        assert counts["spectrum_table_calls"] == counts["spectrum_calls"]
        assert counts["spectrum_tables"] == 1


def test_moe_scopes_and_counters():
    """The MoE layer's device ops carry its scopes (inside the split's
    ``server_side``) in the compiled program's op paths, and the looped round folds its counters into the
    round's ``counts`` at the one sync it already makes."""
    import re

    import jax
    from repro import telemetry
    from repro.configs import registry
    from repro.configs.base import CPSLConfig
    from repro.core.cpsl import CPSL
    from repro.core.splitting import make_split_model
    cfg = registry.reduce_for_smoke(registry.get("deepseek-v2-lite-16b-ep8"))
    cp = CPSL(make_split_model(cfg, 1),
              CPSLConfig(cut_layer=1, n_clusters=2, cluster_size=2,
                         batch_per_device=2))
    state = cp.init_state(jax.random.PRNGKey(0))
    b = registry.concrete_batch(jax.random.PRNGKey(1), cfg, batch=4, seq=16)
    batch = jax.tree.map(lambda t: t.reshape((2, 2) + t.shape[1:]), b)
    hlo = jax.jit(cp.fused_step_impl).lower(state, batch).compile().as_text()
    paths = set(re.findall(r'op_name="([^"]*)"', hlo))
    for scope in ("mla", "moe_router", "moe_permute", "moe_experts",
                  "moe_combine", "moe_shared"):
        assert any("server_side" in p and f"/{scope}/" in p
                   for p in paths), scope
    assert any("device_side" in p and "(mla)/" in p for p in paths)
    telemetry.begin_round(0)
    state, _ = cp.run_round(state, lambda m, l: batch)
    _, counts = telemetry.fold()
    assert counts["syncs"] == 1
    # 2 clusters x 2 MoE layers x 16 tokens x 2 sequences x 2 devices x top-2
    assert 0 < counts["moe_routed"] <= 2 * 2 * 16 * 4 * 2
    assert 0 < counts["moe_load_max"] <= 16 * 4 * 2
