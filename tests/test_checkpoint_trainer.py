"""Fault tolerance: checkpoint roundtrip/atomicity/GC, failure injection +
bit-exact resume, elastic restore."""
import os
import shutil

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint.checkpointer import Checkpointer, deserialize, serialize
from repro.configs.base import CPSLConfig
from repro.core.channel import NetworkCfg
from repro.core.cpsl import CPSL
from repro.core.profile import lenet_profile
from repro.core.splitting import make_split_model
from repro.data.pipeline import CPSLDataset
from repro.data.synthetic import non_iid_split, synthetic_mnist
from repro.train.trainer import CPSLTrainer, SimulatedFailure, TrainerCfg

KEY = jax.random.PRNGKey(0)


def test_serialize_roundtrip_exact():
    tree = {"a": jnp.arange(6, dtype=jnp.int32).reshape(2, 3),
            "b": [jnp.ones((3,), jnp.bfloat16), jnp.zeros((), jnp.float32)],
            "c": {"d": jax.random.normal(KEY, (4, 5))}}
    blob = serialize(tree)
    back = deserialize(blob, jax.tree.map(jnp.zeros_like, tree))
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(back)):
        assert a.dtype == b.dtype and a.shape == b.shape
        assert jnp.array_equal(a, b)


def test_checkpointer_keep_k_and_latest(tmp_path):
    ck = Checkpointer(str(tmp_path), keep=2)
    for s in (1, 2, 3, 4):
        ck.save({"x": jnp.full((2,), s)}, step=s)
    assert ck.steps() == [3, 4]
    out = ck.restore({"x": jnp.zeros((2,))})
    assert float(out["x"][0]) == 4


def test_checkpointer_no_tmp_left(tmp_path):
    ck = Checkpointer(str(tmp_path), keep=3)
    ck.save({"x": jnp.ones((4,))}, step=7)
    assert not [f for f in os.listdir(tmp_path) if f.endswith(".tmp")]


def test_checkpoint_missing_leaf_raises(tmp_path):
    ck = Checkpointer(str(tmp_path))
    ck.save({"x": jnp.ones((2,))}, step=1)
    with pytest.raises(KeyError):
        ck.restore({"x": jnp.zeros((2,)), "y": jnp.zeros((1,))})


def _mk_trainer(ckpt_dir, rounds, fail_at=None, seed=0):
    xtr, ytr, _, _ = synthetic_mnist(1500, 100, seed=0)
    idx = non_iid_split(ytr, n_devices=6, samples_per_device=80, seed=0)
    ds = CPSLDataset(xtr, ytr, idx, batch=8)
    ccfg = CPSLConfig(cut_layer=3, n_clusters=2, cluster_size=3,
                      local_epochs=1)
    tcfg = TrainerCfg(rounds=rounds, ckpt_every=2, ckpt_dir=ckpt_dir,
                      resource_mgmt="random", gibbs_iters=10,
                      fail_at_round=fail_at, seed=seed, async_ckpt=False)
    return CPSLTrainer(CPSL(make_split_model("lenet", 3), ccfg), ds,
                       lenet_profile(), NetworkCfg(n_devices=6), tcfg)


def test_failure_resume_bit_exact(tmp_path):
    """Crash at round 3, restart, final state == uninterrupted run."""
    d1, d2 = str(tmp_path / "a"), str(tmp_path / "b")
    # uninterrupted
    tr_ref = _mk_trainer(d1, rounds=5)
    state_ref = tr_ref.run(KEY)
    # interrupted at round 3 (checkpoint exists at round 2)
    tr1 = _mk_trainer(d2, rounds=5, fail_at=3)
    with pytest.raises(SimulatedFailure):
        tr1.run(KEY)
    tr2 = _mk_trainer(d2, rounds=5)
    state_res = tr2.run(KEY)
    assert tr2.history[0]["round"] == 2      # resumed from the checkpoint
    for a, b in zip(jax.tree.leaves(state_ref["dev"]),
                    jax.tree.leaves(state_res["dev"])):
        assert jnp.array_equal(a, b)
    for a, b in zip(jax.tree.leaves(state_ref["srv"]),
                    jax.tree.leaves(state_res["srv"])):
        assert jnp.array_equal(a, b)


def test_trainer_releases_initial_state(tmp_path):
    """Once round 0 has produced a new state, the trainer holds no
    reference to the initial one: each extra copy is a whole model in
    device memory."""
    import gc
    import weakref
    tr = _mk_trainer(str(tmp_path), rounds=2)
    init_state, run_round = tr.cpsl.init_state, tr.cpsl.run_round
    refs, alive = [], []

    def init_spy(key):
        state = init_state(key)
        refs.append(weakref.ref(jax.tree.leaves(state["srv"])[0]))
        return state

    def round_spy(*args, **kwargs):
        gc.collect()
        alive.append(refs[0]() is not None)
        return run_round(*args, **kwargs)

    tr.cpsl.init_state, tr.cpsl.run_round = init_spy, round_spy
    tr.run(KEY)
    assert alive == [True, False]


def test_trainer_tracks_simulated_latency(tmp_path):
    tr = _mk_trainer(str(tmp_path), rounds=2)
    tr.run(KEY)
    assert all(h["sim_latency_s"] > 0 for h in tr.history)
    assert tr.history[1]["sim_time_s"] > tr.history[0]["sim_time_s"]


def test_elastic_restore_dtype_and_shape(tmp_path):
    """Checkpoints restore into freshly-initialized (differently-placed)
    targets — the elastic-rescale path."""
    ck = Checkpointer(str(tmp_path))
    split = make_split_model("lenet", 3)
    cp = CPSL(split, CPSLConfig(cut_layer=3, cluster_size=3))
    s1 = cp.init_state(jax.random.PRNGKey(1))
    ck.save(s1, step=1)
    s2 = cp.init_state(jax.random.PRNGKey(2))   # different values
    s2 = ck.restore(s2)
    for a, b in zip(jax.tree.leaves(s1["dev"]), jax.tree.leaves(s2["dev"])):
        assert jnp.array_equal(a, b)


def _tiny_state():
    return {"w": jnp.arange(6, dtype=jnp.float32).reshape(2, 3),
            "step": jnp.asarray(3, jnp.int32)}


def test_corrupt_latest_falls_back_to_previous(tmp_path):
    """A bit-flipped latest checkpoint fails its crc32 and restore()
    falls back to the previous keep-k entry with a warning — a damaged
    last save cannot brick a resume."""
    import warnings
    from repro.checkpoint.checkpointer import CheckpointCorrupt
    ck = Checkpointer(str(tmp_path), keep=3)
    st = _tiny_state()
    ck.save(st, step=1)
    st2 = {"w": st["w"] + 1.0, "step": jnp.asarray(4, jnp.int32)}
    ck.save(st2, step=2)
    # flip one payload bit in the newest file
    latest = os.path.join(str(tmp_path), "ckpt_0000000002")
    blob = bytearray(open(latest, "rb").read())
    blob[len(blob) // 2] ^= 0x40
    open(latest, "wb").write(bytes(blob))
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        got = ck.restore(jax.tree.map(jnp.zeros_like, st))
    assert any("falling back" in str(c.message) for c in caught)
    assert ck.restored_step == 1
    assert np.array_equal(np.asarray(got["w"]), np.asarray(st["w"]))
    # an explicitly requested corrupt step still fails loudly
    with pytest.raises(CheckpointCorrupt):
        ck.restore(jax.tree.map(jnp.zeros_like, st), step=2)


def test_all_checkpoints_corrupt_raises(tmp_path):
    """When every entry fails verification the failure is loud, not a
    silent cold start."""
    import warnings
    from repro.checkpoint.checkpointer import CheckpointCorrupt
    ck = Checkpointer(str(tmp_path), keep=2)
    st = _tiny_state()
    ck.save(st, step=1)
    ck.save(st, step=2)
    for name in ("ckpt_0000000001", "ckpt_0000000002"):
        p = os.path.join(str(tmp_path), name)
        open(p, "wb").write(b"RCK1" + b"\x00" * 16)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        with pytest.raises(CheckpointCorrupt, match="all 2 checkpoints"):
            ck.restore(jax.tree.map(jnp.zeros_like, st))


def test_truncated_checkpoint_is_corrupt(tmp_path):
    """A file cut short mid-write (crash during save) is detected as
    corruption, not decoded garbage."""
    from repro.checkpoint.checkpointer import CheckpointCorrupt
    ck = Checkpointer(str(tmp_path), keep=2)
    st = _tiny_state()
    ck.save(st, step=1)
    p = os.path.join(str(tmp_path), "ckpt_0000000001")
    blob = open(p, "rb").read()
    open(p, "wb").write(blob[: len(blob) // 2])
    with pytest.raises(CheckpointCorrupt):
        ck.restore(jax.tree.map(jnp.zeros_like, st), step=1)
