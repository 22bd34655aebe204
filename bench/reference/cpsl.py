"""Plain reference of one CPSL cluster step and of eq. (8) FedAvg
(arXiv:2204.08119, Alg. 1).

A step: each of the K devices runs its own device-side model on its own
B samples; the server runs its one model on the K*B smashed rows
concatenated in device order; one gradient of the mean loss reaches every
model; plain SGD updates the K device-side models at ``lr_device`` and the
server-side model at ``lr_server``. After the cluster's L local epochs its
K device-side models are replaced by their mean weighted by the devices'
data sizes (eq. 8).

``replay`` follows the system's first steps from the same initial
parameters, batches and FedAvg weights, and reads what the comparison
needs: each step's loss, the per-leaf norm of the first update (the first
gradient as SGD applies it) and of the change from the start to the
input of the step after the last one.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def _row(tree, k):
    return jax.tree.map(lambda t: t[k], tree)


def make_step(device_apply, server_loss, lr_device, lr_server, nm):
    """jitted (dev (K, ...), srv, batch (K, B, ...)) -> (dev, srv, loss)."""
    def total(dev, srv, batch):
        K = jax.tree.leaves(dev)[0].shape[0]
        smashed = jnp.concatenate(
            [device_apply(_row(dev, k), _row(batch, k), nm) for k in range(K)])
        flat = jax.tree.map(lambda t: t.reshape((-1,) + t.shape[2:]), batch)
        return server_loss(srv, smashed, flat, nm)

    def sgd(p, g, lr):
        return p - (lr * g.astype(jnp.float32)).astype(p.dtype)

    @jax.jit
    def step(dev, srv, batch):
        loss, (gd, gs) = jax.value_and_grad(total, argnums=(0, 1))(
            dev, srv, batch)
        dev = jax.tree.map(lambda p, g: sgd(p, g, lr_device), dev, gd)
        srv = jax.tree.map(lambda p, g: sgd(p, g, lr_server), srv, gs)
        return dev, srv, loss

    return step


@jax.jit
def fedavg(dev, weights):
    w = jnp.asarray(weights, jnp.float32)
    w = w / jnp.sum(w)

    def avg(t):
        m = jnp.tensordot(w, t.astype(jnp.float32), axes=(0, 0))
        return jnp.broadcast_to(m[None], t.shape).astype(t.dtype)

    return jax.tree.map(avg, dev)


@jax.jit
def leaf_norms(a, b):
    """Per-leaf ||a - b|| in float32, leaves in tree order."""
    return jnp.stack([jnp.linalg.norm((x.astype(jnp.float32)
                                       - y.astype(jnp.float32)).ravel())
                      for x, y in zip(jax.tree.leaves(a),
                                      jax.tree.leaves(b))])


def replay(step, dev0, srv0, steps):
    """``steps``: [(batch, fedavg weights or None), ...], in order; the
    last entry's batch is not run (its step's input is what is read).
    Returns {"loss": [..], "first": per-leaf norms of step 1's update,
    "change": per-leaf norms of (input of the last step - start)}; leaves
    are those of ``{"dev": dev, "srv": srv}``."""
    dev, srv = dev0, srv0
    losses, first = [], None
    for i, (batch, weights) in enumerate(steps[:-1]):
        new_dev, new_srv, loss = step(dev, srv, batch)
        losses.append(float(loss))
        if i == 0:
            first = leaf_norms({"dev": new_dev, "srv": new_srv},
                               {"dev": dev, "srv": srv})
        dev, srv = new_dev, new_srv
        if weights is not None:
            dev = fedavg(dev, weights)
    change = leaf_norms({"dev": dev, "srv": srv}, {"dev": dev0, "srv": srv0})
    return {"loss": losses, "first": jax.device_get(first),
            "change": jax.device_get(change)}
