"""Plain reference of the FL data plane: the paper's CNN, local SGD,
Eq. (2) FedAvg, per-BS edge aggregation and evaluation.

Straightforward jax.numpy, no kernels.  ``dtype`` float32 runs every
matrix product at the precision ``matmul`` names: ``"default"``, the
platform's default float32 products (on a TPU one bfloat16 pass with
float32 accumulation), or ``"highest"``.  The control runs the same code
in bfloat16, the precision below the configuration's.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp


def _conv3x3(x, w, b, precision):
    y = jax.lax.conv_general_dilated(
        x, w, window_strides=(1, 1), padding="SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=precision)
    return y + b


def _pool2(x):
    b, h, w, c = x.shape
    return x.reshape(b, h // 2, 2, w // 2, 2, c).max(axis=(2, 4))


def apply(params, x, precision):
    """conv3x3-relu-pool, conv3x3-relu-pool, dense-relu, dense -> logits."""
    h = _pool2(jax.nn.relu(_conv3x3(x, params["conv1"]["w"],
                                    params["conv1"]["b"], precision)))
    h = _pool2(jax.nn.relu(_conv3x3(h, params["conv2"]["w"],
                                    params["conv2"]["b"], precision)))
    h = h.reshape(h.shape[0], -1)
    h = jax.nn.relu(jnp.dot(h, params["fc1"]["w"], precision=precision)
                    + params["fc1"]["b"])
    return jnp.dot(h, params["fc2"]["w"], precision=precision) \
        + params["fc2"]["b"]


def loss(params, x, y, precision):
    logp = jax.nn.log_softmax(apply(params, x, precision))
    return -jnp.mean(jnp.take_along_axis(logp, y[:, None], axis=1))


def _precision(dtype, matmul="default"):
    return (jax.lax.Precision.HIGHEST
            if dtype == jnp.float32 and matmul == "highest"
            else jax.lax.Precision.DEFAULT)


def client_sgd(params, x, y, key, *, epochs, batch_size, lr, dtype,
               matmul="default"):
    """E epochs of minibatch SGD on one client; a fresh permutation of its
    samples each epoch, the tail that fills no batch left out."""
    prec = _precision(dtype, matmul)
    params = jax.tree.map(lambda p: p.astype(dtype), params)
    x = x.astype(dtype)
    n = x.shape[0]
    nb = n // batch_size
    grad = jax.grad(partial(loss, precision=prec))

    def epoch(p, ek):
        perm = jax.random.permutation(ek, n)[:nb * batch_size]
        xb = x[perm].reshape((nb, batch_size) + x.shape[1:])
        yb = y[perm].reshape((nb, batch_size))

        def step(q, b):
            g = grad(q, b[0], b[1])
            return jax.tree.map(lambda a, ga: a - jnp.asarray(lr, dtype) * ga,
                                q, g), None

        return jax.lax.scan(step, p, (xb, yb))[0], None

    return jax.lax.scan(epoch, params, jax.random.split(key, epochs))[0]


@partial(jax.jit, static_argnames=("epochs", "batch_size", "lr", "dtype",
                                   "per_client", "matmul"))
def fleet_sgd(params, x_clients, y_clients, keys, *, epochs, batch_size, lr,
              dtype, per_client, matmul="default"):
    """Every client's local SGD; from one model, or (``per_client``) each
    from its own.  Returns float32 leaves [N, ...]."""
    fn = partial(client_sgd, epochs=epochs, batch_size=batch_size, lr=lr,
                 dtype=dtype, matmul=matmul)
    if per_client:
        out = jax.vmap(fn)(params, x_clients, y_clients, keys)
    else:
        out = jax.vmap(lambda x, y, k: fn(params, x, y, k))(
            x_clients, y_clients, keys)
    return jax.tree.map(lambda a: a.astype(jnp.float32), out)


def _weighted_mean(weights, stacked, dtype, matmul="default"):
    """sum_i w_i x_i / sum_i w_i over the leading axis, in ``dtype``."""
    w = weights.astype(dtype)
    x = stacked.astype(dtype).reshape(stacked.shape[0], -1)
    s = jnp.dot(w, x, precision=_precision(dtype, matmul))
    return (s / jnp.sum(w)).astype(jnp.float32).reshape(stacked.shape[1:])


@partial(jax.jit, static_argnames=("dtype", "matmul"))
def fedavg(global_params, client_params, weights, dtype, matmul="default"):
    """Eq. (2): the data-weighted mean of the scheduled clients' models;
    the global model stays when nobody was scheduled."""
    total = jnp.sum(weights)
    return jax.tree.map(
        lambda g, c: jnp.where(total > 0,
                               _weighted_mean(weights, c, dtype, matmul), g),
        global_params, client_params)


@partial(jax.jit, static_argnames=("dtype", "matmul"))
def edge_fedavg(edge_params, client_params, weights_nm, dtype,
                matmul="default"):
    """Eq. (2) per BS over the [N, M] assignment weights; a BS that
    aggregated nobody keeps its edge model."""
    totals = jnp.sum(weights_nm, axis=0)

    def agg(e, c):
        per_bs = jax.vmap(lambda wk: _weighted_mean(
            jnp.where(jnp.sum(wk) > 0, wk, jnp.ones_like(wk)), c, dtype,
            matmul),
            in_axes=1)(weights_nm)
        keep = (totals > 0).reshape((-1,) + (1,) * (e.ndim - 1))
        return jnp.where(keep, per_bs, e)

    return jax.tree.map(agg, edge_params, client_params)


@partial(jax.jit, static_argnames=("matmul",))
def edge_mix(global_params, edge_params, edge_weight, matmul="default"):
    """The global model as the edges' data-weighted mixture (tier 2 of the
    hierarchy); the global model stays when the edges aggregated nothing."""
    total = jnp.sum(edge_weight)
    return jax.tree.map(
        lambda g, e: jnp.where(
            total > 0, _weighted_mean(jnp.where(total > 0, edge_weight, 1.0),
                                      e, jnp.float32, matmul), g),
        global_params, edge_params)


@partial(jax.jit, static_argnames=("dtype", "matmul"))
def accuracy(params, x, y, dtype, matmul="default"):
    logits = apply(jax.tree.map(lambda p: p.astype(dtype), params),
                   x.astype(dtype), _precision(dtype, matmul))
    return jnp.mean(jnp.argmax(logits, axis=-1) == y)
