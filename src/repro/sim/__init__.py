"""repro.sim — event-driven wireless dynamics simulation for CPSL.

Layers on top of ``repro.core``:
  dynamics.py    Gauss-Markov correlated fading + compute drift, device
                 churn (arrival/departure) and per-device energy budgets —
                 generalizes the i.i.d. draws of ``core.channel``.
  batched.py     the replicated planner: lockstep multi-chain Gibbs and
                 fully batched SAA over ``core.latency.PartitionBatch``.
  controller.py  online two-timescale controller wrapping Algs. 2-4 with a
                 stale-decision fallback for mid-round departures.
  engine.py      round executor coupling controller + latency model + the
                 real ``core.cpsl`` trainer; emits JSONL traces.
  fleet.py       episode fleets: E dynamic-network episodes as ONE
                 jitted/vmapped float64 program — jnp ports of the AR(1)
                 dynamics and the eq. (15)-(25) cost model
                 (``PartitionBatchJ``), fixed-shape equal/greedy spectrum
                 policies, and ``SimFleetRunner`` pricing a seeds x
                 policy x cluster-size x cut grid in one dispatch.
"""
from repro.sim.batched import (MultiChainResult, PartitionBatch,
                               gibbs_clustering_multichain,
                               saa_cut_selection_batched)
from repro.sim.controller import Plan, TwoTimescaleController
from repro.sim.dynamics import DynamicsCfg, Event, NetworkProcess
from repro.sim.engine import SimEngine
from repro.sim.fleet import (PartitionBatchJ, SimFleetRunner,
                             fleet_trace_records, recompute_fleet_latencies)

__all__ = [
    "PartitionBatch", "MultiChainResult",
    "gibbs_clustering_multichain", "saa_cut_selection_batched",
    "Plan", "TwoTimescaleController",
    "DynamicsCfg", "Event", "NetworkProcess", "SimEngine",
    "PartitionBatchJ", "SimFleetRunner", "fleet_trace_records",
    "recompute_fleet_latencies",
]
