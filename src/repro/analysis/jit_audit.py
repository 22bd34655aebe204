"""jit-contract audit: trace the repo's flagship compiled programs and
assert the invariants the bit-exactness and perf contracts rest on.

Four checks per target (each target is traced/lowered on tiny shapes,
never executed — the audit costs trace time, not compile time):

  JIT001  a ``donate_argnums`` argument does not actually alias: the
          lowered StableHLO carries fewer ``tf.aliasing_output``
          attributes than the donated pytree has leaves (XLA silently
          drops donation when no output matches — the round state must
          never pay a copy).
  JIT002  a host callback primitive (``*callback*``, ``infeed``,
          ``outfeed``) inside the jaxpr — the fused round / training
          curve / fleet programs are contractually host-sync-free.
  JIT003  implicit f64<->f32 ``convert_element_type`` beyond the
          target's documented allowance (0 everywhere today: the CPSL
          programs are pure f32, the fleet cost engine pure f64 under
          ``jax.enable_x64(True)`` with inputs cast at the host boundary).
  JIT004  a weak-typed aval in a ``scan``/``while`` carry — a python
          scalar leaked into carried state, which retraces the program
          whenever a caller passes a strongly-typed value (see
          ``sim/fleet.py``'s greedy loop for the fix pattern).

Audited targets (the acceptance set):

  * ``CPSL._run_round_fused``      — one donated round;
  * ``CPSL._run_training_fused``   — the R-round curve;
  * ``CPSL._run_fleet``            — E vmapped curves;
  * ``SimFleetRunner._sim``        — the two-timescale Monte-Carlo
    simulator (traced under ``jax.enable_x64(True)``, its contract dtype).

Also exported: the shared recompile-guard helpers ``cache_size`` and
``CompileCounter`` (used by ``benchmarks/bench_fleet.py`` instead of
ad-hoc ``_cache_size`` asserts).
"""

from __future__ import annotations

from typing import Iterable, List, Optional

from repro.analysis.report import Finding

__all__ = ["run", "audit_traced", "cache_size", "CompileCounter",
           "walk_jaxprs", "count_f64_casts", "callback_primitives",
           "weak_carries", "donation_aliases", "TARGET_NAMES"]

TARGET_NAMES = ("round_fused", "training_fused", "fleet", "fleet_sim")

_CALLBACK_PRIMS = {"infeed", "outfeed"}


# -- shared helpers (also the benchmarks' recompile guard) -----------------

def cache_size(jitfn) -> int:
    """Number of compiled entries in a ``jax.jit`` function's cache."""
    return int(jitfn._cache_size())


class CompileCounter:
    """Recompile guard around a block of calls to one jitted function::

        with CompileCounter(CPSL._run_training_fused, budget=1):
            cpsl.run_training_fused(...)   # may compile once
            cpsl.run_training_fused(...)   # must hit the cache

    Raises AssertionError on exit when more than ``budget`` new cache
    entries appeared (an unintended retrace/recompile)."""

    def __init__(self, jitfn, budget: int = 1, name: str = ""):
        self.jitfn = jitfn
        self.budget = int(budget)
        self.name = name or getattr(jitfn, "__name__", repr(jitfn))
        self._start: Optional[int] = None

    @property
    def new_entries(self) -> int:
        assert self._start is not None, "CompileCounter not entered"
        return cache_size(self.jitfn) - self._start

    def __enter__(self) -> "CompileCounter":
        self._start = cache_size(self.jitfn)
        return self

    def __exit__(self, etype, evalue, tb) -> None:
        if etype is None and self.new_entries > self.budget:
            raise AssertionError(
                f"{self.name}: {self.new_entries} new jit cache entries "
                f"(budget {self.budget}) — an argument signature is "
                "unstable (weak type / python scalar / dtype drift)")


# -- jaxpr walking ----------------------------------------------------------

def walk_jaxprs(jaxpr) -> Iterable:
    """Yield ``jaxpr`` and every sub-jaxpr reachable through eqn params
    (scan/while/cond bodies, closed calls, custom_* wrappers)."""
    yield jaxpr
    for eqn in jaxpr.eqns:
        for v in eqn.params.values():
            vs = v if isinstance(v, (list, tuple)) else [v]
            for x in vs:
                if hasattr(x, "eqns"):                    # Jaxpr
                    yield from walk_jaxprs(x)
                elif hasattr(x, "jaxpr") and hasattr(getattr(x, "jaxpr"),
                                                     "eqns"):
                    yield from walk_jaxprs(x.jaxpr)       # ClosedJaxpr


def callback_primitives(closed) -> List[str]:
    out = []
    for j in walk_jaxprs(closed.jaxpr):
        for eqn in j.eqns:
            n = eqn.primitive.name
            if "callback" in n or n in _CALLBACK_PRIMS:
                out.append(n)
    return sorted(set(out))


def count_f64_casts(closed) -> int:
    """f64<->f32 ``convert_element_type`` eqns anywhere in the program."""
    n = 0
    for j in walk_jaxprs(closed.jaxpr):
        for eqn in j.eqns:
            if eqn.primitive.name != "convert_element_type":
                continue
            src = str(eqn.invars[0].aval.dtype)
            dst = str(eqn.outvars[0].aval.dtype)
            if {src, dst} == {"float32", "float64"}:
                n += 1
    return n


def weak_carries(closed) -> List[str]:
    """Weak-typed avals carried by any scan/while in the program."""
    out = []
    for j in walk_jaxprs(closed.jaxpr):
        for eqn in j.eqns:
            if eqn.primitive.name == "scan":
                nc = eqn.params["num_consts"]
                ncar = eqn.params["num_carry"]
                carried = eqn.invars[nc:nc + ncar]
            elif eqn.primitive.name == "while":
                off = (eqn.params["cond_nconsts"]
                       + eqn.params["body_nconsts"])
                carried = eqn.invars[off:]
            else:
                continue
            for i, v in enumerate(carried):
                aval = getattr(v, "aval", None)
                if aval is not None and getattr(aval, "weak_type", False):
                    out.append(f"{eqn.primitive.name} carry[{i}]: {aval}")
    return out


def donation_aliases(lowered) -> int:
    """Input/output aliasing pairs XLA accepted for the lowered program
    (each donated leaf that actually aliases emits one
    ``tf.aliasing_output`` attribute in the StableHLO)."""
    return lowered.as_text().count("tf.aliasing_output")


# -- the audit ---------------------------------------------------------------

def audit_traced(name: str, traced, lowered, donated_leaves: int,
                 f64_allowance: int = 0) -> List[Finding]:
    """Apply all four checks to one traced+lowered target.
    ``donated_leaves`` is the leaf count of the donated argument pytree
    (0 when the target donates nothing — skips JIT001)."""
    findings: List[Finding] = []
    closed = traced.jaxpr

    if donated_leaves:
        n = donation_aliases(lowered)
        if n < donated_leaves:
            findings.append(Finding(
                "JIT001", name, 0,
                f"donation dropped: {n}/{donated_leaves} donated leaves "
                "alias an output (tf.aliasing_output) — the donated "
                "state pays a copy",
                detail=f"aliases<{donated_leaves}"))

    for prim in callback_primitives(closed):
        findings.append(Finding(
            "JIT002", name, 0,
            f"host callback primitive '{prim}' inside a "
            "contractually host-sync-free program", detail=prim))

    casts = count_f64_casts(closed)
    if casts > f64_allowance:
        findings.append(Finding(
            "JIT003", name, 0,
            f"{casts} implicit f64<->f32 convert_element_type eqns "
            f"(documented allowance: {f64_allowance})",
            detail=f"casts>{f64_allowance}"))

    for w in weak_carries(closed):
        findings.append(Finding(
            "JIT004", name, 0,
            f"weak-typed carried aval ({w}) — a python scalar leaked "
            "into scan/while state; callers passing strong dtypes will "
            "retrace", detail=w))
    return findings


# -- target construction (tiny shapes; trace only, never execute) -----------

def _tiny_cpsl():
    from repro.configs.base import CPSLConfig
    from repro.core.cpsl import CPSL
    from repro.data.pipeline import CPSLDataset, DeviceResidentDataset
    from repro.data.synthetic import non_iid_split, synthetic_mnist
    from repro.core.splitting import make_split_model

    M, K, B = 2, 3, 4
    clusters = [[0, 1, 2], [3, 4, 5]]
    xtr, ytr, _, _ = synthetic_mnist(400, 50, seed=0)
    idx = non_iid_split(ytr, n_devices=6, samples_per_device=60, seed=0)
    ds = CPSLDataset(xtr, ytr, idx, batch=B)
    dsd = DeviceResidentDataset.from_dataset(ds)
    ccfg = CPSLConfig(cut_layer=2, n_clusters=M, cluster_size=K,
                      local_epochs=2, batch_per_device=B,
                      unroll_clients=True)
    cp = CPSL(make_split_model("lenet", ccfg.cut_layer), ccfg)
    return cp, dsd, clusters


def _audit_round_fused() -> List[Finding]:
    import jax
    import jax.numpy as jnp
    from repro import streams

    cp, dsd, clusters = _tiny_cpsl()
    st = cp.init_state(streams.model_key(0))
    idx = jnp.asarray(dsd.round_index_table(
        clusters, 0, 0, cp.ccfg.local_epochs))
    w = jnp.asarray(dsd.cluster_weights(clusters), jnp.float32)
    fn = type(cp)._run_round_fused
    traced = fn.trace(cp, st, dsd.data, idx, w)
    return audit_traced("CPSL._run_round_fused", traced, traced.lower(),
                        donated_leaves=len(jax.tree.leaves(st)))


def _audit_training_fused() -> List[Finding]:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro import streams

    cp, dsd, clusters = _tiny_cpsl()
    st = cp.init_state(streams.model_key(0))
    R = 2
    idx = jnp.asarray(np.stack([
        dsd.round_index_table(clusters, 0, r, cp.ccfg.local_epochs)
        for r in range(R)]))
    w = jnp.asarray(dsd.cluster_weights(clusters), jnp.float32)
    fn = type(cp)._run_training_fused
    traced = fn.trace(cp, st, dsd.data, idx, w, None, None, None, None, 0)
    return audit_traced("CPSL._run_training_fused", traced,
                        traced.lower(),
                        donated_leaves=len(jax.tree.leaves(st)))


def _audit_fleet() -> List[Finding]:
    import jax
    import jax.numpy as jnp
    import numpy as np

    cp, dsd, clusters = _tiny_cpsl()
    E, R = 2, 2
    states = cp.init_fleet_state([0, 1])
    idx1 = np.stack([
        dsd.round_index_table(clusters, 0, r, cp.ccfg.local_epochs)
        for r in range(R)])
    idx = jnp.asarray(np.stack([idx1] * E))
    w1 = np.asarray(dsd.cluster_weights(clusters), np.float32)
    w = jnp.asarray(np.stack([w1] * E))
    fn = type(cp)._run_fleet
    traced = fn.trace(cp, states, dsd.data, idx, w, None, None, None,
                      None, 0)
    return audit_traced("CPSL._run_fleet", traced, traced.lower(),
                        donated_leaves=len(jax.tree.leaves(states)))


def _audit_fleet_sim() -> List[Finding]:
    import jax
    from repro.configs.base import SimFleetCfg
    from repro.core.channel import NetworkCfg
    from repro.core.profile import lenet_profile
    from repro.sim.dynamics import DynamicsCfg
    from repro.sim.fleet import SimFleetRunner

    ncfg = NetworkCfg(n_devices=8, n_subcarriers=12)
    dcfg = DynamicsCfg(rho_snr=0.9, rho_f=0.95, seed=0)
    fcfg = SimFleetCfg(rounds=5, seeds=(0, 1),
                       policies=("equal", "greedy"), cluster_sizes=(3,),
                       cuts=(2, 3), batch_per_device=16, local_epochs=1)
    runner = SimFleetRunner(lenet_profile(), ncfg, dcfg, fcfg)
    with jax.enable_x64(True):        # the cost model's contract dtype
        traced = runner._sim.trace(runner.sim_inputs())
        lowered = traced.lower()
    # _sim donates nothing (pure Monte-Carlo pricing); its contract is
    # callback-free, cast-free-under-x64, strongly-typed carries
    return audit_traced("SimFleetRunner._sim", traced, lowered,
                        donated_leaves=0)


_TARGETS = {
    "round_fused": _audit_round_fused,
    "training_fused": _audit_training_fused,
    "fleet": _audit_fleet,
    "fleet_sim": _audit_fleet_sim,
}


def run(root=None, targets=TARGET_NAMES) -> List[Finding]:
    """Audit the named targets (``root`` is accepted for interface
    symmetry with the AST passes and ignored — targets are imported)."""
    findings: List[Finding] = []
    for name in targets:
        findings.extend(_TARGETS[name]())
    return findings
