"""Driver of the training cells: CPSL training through the launcher.

The trainer is built as users build it, by
``repro.launch.train.build(parse_args([...]))``, and the window times
``CPSLTrainer.run``: the looped round with the planner the traffic names,
each round starting when the one before it ended.

- Weights are the benchmark's own: the plain reference's initialisation,
  drawn from the seed in one jitted call and handed to the trainer as the
  checkpoint it resumes from. The trainer's checkpointer is replaced by
  an in-memory stand-in that keeps the latest state and writes nothing,
  and the trainer saves only at the end of a call, so no save falls in
  the window.
- Set-up runs ``ceil(4 / M)`` rounds through the same ``run`` and feed,
  recording the first four cluster steps (their batches, losses, the
  first update and the change at the fourth step's input) for the
  comparison, then ``timing_rounds`` more whose median sets the window's
  round count. The same trainer and state go on into the window.
- Where the traffic names a ``plan_pool``, every round plans one of a
  fixed set of problems (the network and Gibbs stream of rounds
  ``0 .. plan_pool - 1`` of the stream ``plan_pool_seed``), taken in an
  order drawn from the seed, and an untraced window is a whole number of
  passes over them: every seed's window plans the same problems.
- After the window and once the program's state is freed, the plain
  reference replays the first three steps, and the planner's decisions of
  the set-up rounds and of a seeded sample of the window's rounds are
  priced again.
"""
from __future__ import annotations

import functools
import gc
import json
import math
import statistics
import tempfile
import time
from pathlib import Path

import numpy as np

from bench import harness, trace as trace_mod
from bench.reference import cpsl as ref_cpsl
from bench.reference import numerics, planner as ref_planner

N_STEPS = 4          # steps recorded; the reference follows the first three


# -- building -----------------------------------------------------------------

def launcher_argv(cfg: dict, traffic: dict, seed: int, ckpt_dir: str):
    dep, launch = cfg["deployment"], cfg["launch"]
    argv = ["--ckpt-dir", ckpt_dir, "--seed", str(seed),
            "--cut", str(dep["cut"]), "--clusters", str(dep["n_clusters"]),
            "--cluster-size", str(dep["cluster_size"]),
            "--batch", str(dep["batch"]),
            "--local-epochs", str(dep["local_epochs"]),
            "--resource", traffic["resource"]]
    if "arch" in launch:
        argv += ["--arch", launch["arch"], "--seq", str(dep["seq"])]
        if launch.get("reduced"):
            argv.append("--reduced")
    else:
        argv += ["--model", launch["model"]]
    return argv


class HeldState:
    """The trainer's checkpointer, kept in memory: ``run`` resumes from
    the last payload saved, and nothing is written."""

    def __init__(self, payload=None):
        self.payload = payload

    def save(self, payload, step, block=True):
        self.payload = payload

    def restore(self, target, step=None):
        # hand the state over: a reference kept here would hold one more
        # copy of the model for the whole call
        payload, self.payload = self.payload, None
        return payload

    def wait(self):
        pass


def reference_module(cfg: dict):
    return harness.load_module(harness.BENCH / "reference"
                               / f"{cfg['reference']}.py")


def seed_key(seed: int):
    import jax
    return jax.random.fold_in(jax.random.PRNGKey(seed % 2 ** 31),
                              seed // 2 ** 31)


@functools.lru_cache(maxsize=None)
def _state_maker(cfg_json: str, template):
    """jitted key -> the trainer's round-0 state (``template``: its
    shapes, from ``init_state``) with the reference's weights."""
    import jax
    import jax.numpy as jnp
    cfg = json.loads(cfg_json)
    ref = reference_module(cfg)
    v, K = cfg["deployment"]["cut"], cfg["deployment"]["cluster_size"]

    def make(key):
        k_w, k_rng = jax.random.split(key)
        dev0, srv = ref.init(k_w, cfg, v)
        state = jax.tree.map(lambda t: jnp.zeros(t.shape, t.dtype),
                             template.tree)
        state["dev"] = jax.tree.map(
            lambda t: jnp.broadcast_to(t[None], (K,) + t.shape), dev0)
        state["srv"] = srv
        state["rng"] = k_rng
        return state

    shapes = jax.eval_shape(make, jax.random.PRNGKey(0))
    if _Shapes(shapes) != template:
        raise RuntimeError("the trainer's state is not laid out as the "
                           f"{cfg['reference']} reference's parameters")
    return jax.jit(make)


def initial_state(trainer, cfg: dict, seed: int):
    """The trainer's state at round 0 with the benchmark's weights, made in
    one jitted call; its layout must be the trainer's own."""
    import jax
    key = seed_key(seed)
    template = _Shapes(jax.eval_shape(trainer.cpsl.init_state, key))
    return _state_maker(json.dumps(cfg, sort_keys=True), template)(key)


class _Shapes:
    """A shape tree, hashable by its structure, shapes and dtypes."""

    def __init__(self, tree):
        import jax
        self.tree = tree
        self.key = (str(jax.tree.structure(tree)),
                    tuple((t.shape, str(t.dtype))
                          for t in jax.tree.leaves(tree)))

    def __hash__(self):
        return hash(self.key)

    def __eq__(self, other):
        return self.key == other.key


@functools.lru_cache(maxsize=None)
def _params_maker(cfg_json: str):
    import jax
    cfg = json.loads(cfg_json)
    ref = reference_module(cfg)
    return jax.jit(lambda k: ref.init(k, cfg, cfg["deployment"]["cut"]))


def reference_params(cfg: dict, seed: int):
    """(device-side, server-side) initial parameters of the reference,
    equal to those ``initial_state`` hands the trainer."""
    import jax
    k_w, _ = jax.random.split(seed_key(seed))
    return _params_maker(json.dumps(cfg, sort_keys=True))(k_w)


# -- recording ----------------------------------------------------------------

class Recorder:
    """Wraps the trainer's own calls: the first ``N_STEPS`` cluster steps
    (batch, FedAvg weights, loss, first update, change at the last one's
    input) and every round's plan with the network it was made for."""

    def __init__(self, trainer, cfg, seed):
        import repro.train.trainer as tr_mod
        self.trainer, self.cfg, self.seed = trainer, cfg, seed
        self.tr_mod = tr_mod
        self.steps, self.plans = [], []
        self.first = self.change = None
        self._net = None
        cpsl = trainer.cpsl
        self._orig = {"step": cpsl.cluster_step, "fedavg": cpsl.fedavg,
                      "plan": trainer._plan_round,
                      "sample": tr_mod.sample_network}
        cpsl.cluster_step = self._step
        cpsl.fedavg = self._fedavg
        trainer._plan_round = self._plan
        tr_mod.sample_network = self._sample

    def _step(self, state, batch):
        import jax
        n = len(self.steps)
        if n >= N_STEPS:
            return self._orig["step"](state, batch)
        if n == N_STEPS - 1:
            dev0, srv0 = reference_params(self.cfg, self.seed)
            K = self.cfg["deployment"]["cluster_size"]
            dev0 = jax.tree.map(
                lambda t: jax.numpy.broadcast_to(t[None], (K,) + t.shape),
                dev0)
            self.change = jax.device_get(ref_cpsl.leaf_norms(
                {"dev": state["dev"], "srv": state["srv"]},
                {"dev": dev0, "srv": srv0}))
            del dev0, srv0
        new, metrics = self._orig["step"](state, batch)
        if n == 0:
            self.first = jax.device_get(ref_cpsl.leaf_norms(
                {"dev": new["dev"], "srv": new["srv"]},
                {"dev": state["dev"], "srv": state["srv"]}))
        self.steps.append({"batch": jax.device_get(batch), "weights": None,
                           "loss": float(metrics["loss"])})
        return new, metrics

    def _fedavg(self, state, data_sizes=None):
        if self.steps and self.steps[-1]["weights"] is None \
                and len(self.steps) <= N_STEPS:
            K = self.cfg["deployment"]["cluster_size"]
            self.steps[-1]["weights"] = (
                np.ones(K, np.float32) if data_sizes is None
                else np.asarray(data_sizes, np.float32))
        return self._orig["fedavg"](state, data_sizes)

    def _sample(self, *args, **kw):
        self._net = self._orig["sample"](*args, **kw)
        return self._net

    def _plan(self, v, rnd):
        clusters, xs, lat = self._orig["plan"](v, rnd)
        self.plans.append({"round": rnd, "v": v, "f": self._net.f,
                           "rate": self._net.rate, "clusters": clusters,
                           "xs": xs, "lat": lat})
        return clusters, xs, lat

    def stop_steps(self):
        """Unwrap the step and FedAvg once the steps are recorded."""
        self.trainer.cpsl.cluster_step = self._orig["step"]
        self.trainer.cpsl.fedavg = self._orig["fedavg"]

    def close(self):
        """Unwrap everything and let go of the trainer (and its state)."""
        self.stop_steps()
        self.trainer._plan_round = self._orig["plan"]
        self.tr_mod.sample_network = self._orig["sample"]
        self.trainer = self._orig = None


# -- the comparison -----------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _reference_step(cfg_json: str, nm_name: str):
    cfg = json.loads(cfg_json)
    dep = cfg["deployment"]
    device_apply, server_loss = reference_module(cfg).make(cfg, dep["cut"])
    return ref_cpsl.make_step(device_apply, server_loss, dep["lr_device"],
                              dep["lr_server"], numerics.BY_NAME[nm_name])


def replay(cfg: dict, seed: int, steps, nm, halve: bool = False):
    """The plain reference (or a control, by ``nm``) over the recorded
    steps. ``halve`` leaves out the second half of every device's batch."""
    import jax
    dep = cfg["deployment"]
    step = _reference_step(json.dumps(cfg, sort_keys=True), nm.name)
    dev0, srv0 = reference_params(cfg, seed)
    K = dep["cluster_size"]
    dev0 = jax.tree.map(lambda t: jax.numpy.broadcast_to(
        t[None], (K,) + t.shape).astype(nm.dtype), dev0)
    srv0 = jax.tree.map(lambda t: t.astype(nm.dtype), srv0)
    feed = []
    for s in steps:
        b = s["batch"]
        if halve:
            b = jax.tree.map(lambda t: t[:, :t.shape[1] // 2], b)
        feed.append((b, s["weights"]))
    with jax.default_matmul_precision("highest"):
        return ref_cpsl.replay(step, dev0, srv0, feed)


def _leaf_gap(prog, ref, keep=None):
    prog, ref = np.asarray(prog, float), np.asarray(ref, float)
    denom = np.maximum(ref, np.median(ref))
    gap = np.abs(prog - ref) / np.where(denom > 0, denom, 1.0)
    if keep is not None:
        gap = gap[keep]
    return float(gap.max()) if gap.size else 0.0


def training_numbers(prog: dict, ref: dict) -> dict:
    """``prog``/``ref``: {"loss": [3], "first": per-leaf, "change":
    per-leaf}. The change is compared only on leaves the reference's first
    gradient moves: at least a thousandth of the median leaf's."""
    first = np.asarray(ref["first"], float)
    keep = first >= 1e-3 * np.median(first)
    lp, lr = np.asarray(prog["loss"], float), np.asarray(ref["loss"], float)
    return {"loss_gap": float(np.max(np.abs(lp - lr) / np.abs(lr))),
            "grad_gap": _leaf_gap(prog["first"], ref["first"]),
            "change_gap": _leaf_gap(prog["change"], ref["change"], keep)}


def cut_constants(cfg: dict, v: int) -> dict:
    flops = harness.load_module(harness.BENCH / "flops"
                                / f"{cfg['flops']}.py")
    dep = cfg["deployment"]
    prof = (flops.profile(cfg, dep["seq"]) if "seq" in dep
            else flops.profile(cfg))
    return {k: float(a[v - 1]) for k, a in prof.items()}


def plan_numbers(cfg: dict, traffic: dict, plans) -> dict:
    """Each plan against the paper's rules: the clusters partition the
    devices into M clusters of K (count of plans that do not); the
    spectrum is Alg. 3's (Gibbs) or the equal split (fixed), as the
    latency it gives against that rule's (widest relative gap); the
    latency the planner priced against the reference's pricing of its
    own decisions (widest relative gap)."""
    dep = cfg["deployment"]
    M, K, N = dep["n_clusters"], dep["cluster_size"], dep["n_devices"]
    B, L = dep["batch"], dep["local_epochs"]
    bad, spec_gap, price_gap = 0, 0.0, 0.0
    for p in plans:
        c = cut_constants(cfg, p["v"])
        net = {"f": p["f"], "rate": p["rate"], "kappa": dep["kappa"],
               "n_subcarriers": dep["n_subcarriers"],
               "f_server": dep["f_server"]}
        clusters = [list(map(int, cl)) for cl in p["clusters"]]
        if (len(clusters) != M or any(len(cl) != K for cl in clusters)
                or sorted(d for cl in clusters for d in cl)
                != list(range(N))):
            bad += 1
            continue
        for cl, x in zip(clusters, p["xs"]):
            rule = (ref_planner.greedy(c, net, cl, B, L)
                    if traffic["resource"] == "gibbs"
                    else ref_planner.equal_split(K, dep["n_subcarriers"]))
            want = ref_planner.cluster_latency(c, net, cl, rule, B, L)
            got = ref_planner.cluster_latency(c, net, cl, x, B, L)
            spec_gap = max(spec_gap, abs(got - want) / want)
        priced = ref_planner.round_latency(c, net, clusters, p["xs"], B, L)
        price_gap = max(price_gap, abs(p["lat"] - priced) / priced)
    return {"plan_partition": bad, "plan_spectrum_gap": spec_gap,
            "plan_price_gap": price_gap}


def checks(numbers: dict, limits: dict) -> dict:
    return {k: {"value": v, "limit": limits[k], "ok": v <= limits[k]}
            for k, v in numbers.items()}


# -- the run ------------------------------------------------------------------

def build(cell, seed: int, ckpt_dir: str):
    """The launcher's trainer for the cell, run as its configuration
    states: where the configuration names a matmul precision, JAX runs
    every dot of the program at it."""
    import jax
    from repro.launch.train import build as launch_build, parse_args
    jax.config.update("jax_default_matmul_precision",
                      cell.config.get("jax_default_matmul_precision"))
    trainer, _ = launch_build(parse_args(
        launcher_argv(cell.config, cell.traffic, seed, ckpt_dir)))
    trainer.ckpt = HeldState()
    trainer.tcfg.ckpt_every = 1 << 30
    return trainer


def pool_plans(trainer, traffic: dict, seed: int):
    """Round ``rnd`` plans the problem of round ``order[rnd % size]`` of
    the stream ``plan_pool_seed``, through the trainer's own planner;
    ``order`` is a permutation drawn from the seed."""
    size, pool_seed = traffic["plan_pool"], traffic["plan_pool_seed"]
    order = np.random.default_rng([seed, 1]).permutation(size)
    plan = trainer._plan_round

    def pooled(v, rnd):
        own, trainer.tcfg.seed = trainer.tcfg.seed, pool_seed
        try:
            return plan(v, int(order[rnd % size]))
        finally:
            trainer.tcfg.seed = own

    trainer._plan_round = pooled


def window_rounds(traffic: dict, seconds: float, wall: float,
                  whole_passes: bool) -> int:
    """Rounds to fill about ``seconds`` at ``wall`` seconds a round; with a
    plan pool and ``whole_passes``, a whole number of passes over it."""
    pool = traffic.get("plan_pool")
    if pool and whole_passes:
        return pool * max(1, round(seconds / (pool * wall)))
    return max(traffic["min_rounds"], round(seconds / wall))


def release_program():
    """Free what the system holds on the device before the reference
    runs: its state (the caller drops its references first) and, on an
    accelerator, its loaded programs, each of which keeps its scratch
    memory reserved there (the CPU reserves none)."""
    import jax
    gc.collect()
    if jax.devices()[0].platform != "cpu":
        jax.clear_caches()


def setup(cell, seed: int, ckpt_dir: str):
    """Build the trainer and drive it through the set-up rounds; returns
    (trainer, recorder, warm round seconds)."""
    dep = cell.config["deployment"]
    trainer = build(cell, seed, ckpt_dir)
    if cell.traffic.get("plan_pool"):
        pool_plans(trainer, cell.traffic, seed)
    state = initial_state(trainer, cell.config, seed)
    trainer.ckpt.payload = {"round": 0, "sim_time": 0.0, "state": state}
    del state
    # ``run`` resumes from the held state: no initial state is made in it
    trainer.cpsl.init_state = lambda key: None
    rec = Recorder(trainer, cell.config, seed)
    first = math.ceil(N_STEPS / (dep["n_clusters"] * dep["local_epochs"]))
    trainer.tcfg.rounds = first
    trainer.run(None)
    rec.stop_steps()
    trainer.tcfg.rounds = first + cell.traffic["timing_rounds"]
    trainer.run(None)
    walls = [h["wall_s"] for h in trainer.history[first:]]
    return trainer, rec, walls


def run(cell, seed: int, seconds: float, trace: bool, t_start: float,
        devs, peaks=None):
    import jax
    cfg, traffic, dep = cell.config, cell.traffic, cell.config["deployment"]
    counters = harness.CompileCounters()
    with tempfile.TemporaryDirectory() as tmp:
        trainer, rec, walls = setup(cell, seed, str(Path(tmp) / "ckpt"))
        window = traffic["trace_seconds"] if trace else seconds
        n = window_rounds(traffic, window, statistics.median(walls),
                          not trace)
        spans = harness.Spans(trace)
        orig = {}
        if trace:
            cpsl, ds = trainer.cpsl, trainer.ds
            orig = {(trainer, "_plan_round"): trainer._plan_round,
                    (ds, "cluster_batch"): ds.cluster_batch,
                    (cpsl, "cluster_step"): cpsl.cluster_step,
                    (cpsl, "fedavg"): cpsl.fedavg,
                    (cpsl, "run_round"): cpsl.run_round}
            names = {"_plan_round": "plan", "cluster_batch": "gather",
                     "cluster_step": "step", "fedavg": "fedavg",
                     "run_round": "round"}
            for (obj, attr), fn in orig.items():
                setattr(obj, attr, spans.wrap(names[attr], fn))
            from jax import profiler
            opts = profiler.ProfileOptions()
            opts.python_tracer_level = 0
            profiler.start_trace(str(Path(tmp) / "trace"),
                                 profiler_options=opts)
        h0, c0 = len(trainer.history), counters.compiles
        trainer.tcfg.rounds = trainer.history[-1]["round"] + 1 + n
        t0 = time.monotonic()
        if trace:
            with profiler.TraceAnnotation("bench.window"):
                state = trainer.run(None)
                jax.block_until_ready(state)
        else:
            state = trainer.run(None)
            jax.block_until_ready(state)
        t1 = time.monotonic()
        in_window = counters.compiles - c0
        for (obj, attr) in orig:
            setattr(obj, attr, orig[(obj, attr)])
        if trace:
            profiler.stop_trace()
        rounds = trainer.history[h0:]
        info = harness.device_info(devs)
        rec.close()
        del state, trainer
        release_program()
        reduced = None
        if trace:
            found = sorted(Path(tmp, "trace").rglob("*.xplane.pb"))
            reduced = trace_mod.reduce(trace_mod.load(str(found[-1])))
    harness.note(f"rounds in window {len(rounds)}; compiles in window "
                 f"{in_window}{' (expected 0)' if in_window else ''}; "
                 f"persistent-cache hits {counters.cache_hits}")
    setup_s = t0 - t_start
    harness.note(f"setup_s {setup_s:.3f} "
                 f"({'cold: compiled with no cache hit' if counters.cache_hits == 0 and counters.compiles else 'warm'})")

    samples = len(rounds) * dep["n_clusters"] * dep["cluster_size"] \
        * dep["batch"] * dep["local_epochs"]
    window_s = t1 - t0
    values = {"samples_per_s": samples / window_s,
              "round_ms_p90": 1e3 * float(np.percentile(
                  [r["wall_s"] for r in rounds], 90)),
              "setup_s": setup_s}

    # the comparison, with the program's state and programs freed
    plans = [p for p in rec.plans if p["round"] < rounds[0]["round"]]
    window_plans = [p for p in rec.plans if p["round"] >= rounds[0]["round"]]
    rng = np.random.default_rng(seed)
    pick = rng.choice(len(window_plans), min(traffic["plans_checked"],
                                             len(window_plans)),
                      replace=False)
    plans += [window_plans[i] for i in sorted(pick)]
    prog = {"loss": [s["loss"] for s in rec.steps[:N_STEPS - 1]],
            "first": rec.first, "change": rec.change}
    t_ref = time.monotonic()
    ref = replay(cfg, seed, rec.steps, numerics.REF)
    harness.note(f"reference replay {time.monotonic() - t_ref:.3f} s")
    numbers = training_numbers(prog, ref)
    numbers.update(plan_numbers(cfg, traffic, plans))
    result_checks = checks(numbers, cfg["limits"])

    result = {"correct": all(c["ok"] for c in result_checks.values()),
              "attempted": len(rounds), "failed": 0}
    if trace:
        # the traced window by the trace's own clock, as busy_s is
        window_s = reduced["window_s"]
        info["busy_s"] = reduced["busy_s"]
        info["window_s"] = window_s
        flops = harness.load_module(harness.BENCH / "flops"
                                    / f"{cfg['flops']}.py")
        per_sample = (flops.train_flops_per_sample(cfg, dep["seq"])
                      if "seq" in dep else flops.train_flops_per_sample(cfg))
        pk = (peaks or {}).get(info["kind"]) or harness.peak(info["kind"])
        ctx = {"rounds": len(rounds), "samples": samples,
               "window_s": window_s, "busy_s": reduced["busy_s"],
               "spans": spans.seconds, "flops_per_sample": per_sample,
               "peak_flops": pk["bf16_flops_per_s"],
               "memory_peak_bytes": info["memory_peak_bytes"]}
        metrics = {}
        for m in cell.per_layer:
            v = cell.reader(m["name"]).read(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        result["metrics"] = metrics
        result["device"] = info
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
    else:
        result["metrics"] = {m["name"]: {"value": values[m["name"]],
                                         "unit": m["unit"]}
                             for m in cell.end_to_end}
        result["device"] = info
    return result, result_checks
