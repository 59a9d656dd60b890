"""Client-side local training: E epochs of minibatch SGD, fully compiled.

The whole fleet's local training is ONE jitted call: ``vmap`` over clients of
a ``scan`` over (epochs x batches).  Unscheduled clients still compute (their
result is masked out at aggregation) so the compiled step is identical every
round — on TPU this is what keeps scheduling from retriggering compilation,
and the per-client compute shards over the mesh ``data`` axis.

When the wasted compute matters more than graph constancy, the round engine
gathers a static-size padded subset of scheduled clients first
(:func:`topk_selected_indices`, ``compute="selected"`` in
:class:`repro.fl.rounds.FLConfig`) and vmaps local SGD over only those rows.
"""
from __future__ import annotations

from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp

PyTree = Any


def local_sgd(loss_fn: Callable, params: PyTree, x: jnp.ndarray,
              y: jnp.ndarray, key: jax.Array, epochs: int, batch_size: int,
              lr: float) -> PyTree:
    """Run E epochs of minibatch SGD on ONE client's data. jit/vmap friendly.

    x: [n_i, ...], y: [n_i].  n_i must be a multiple of batch_size (the
    partitioner guarantees equal |D_i|; we truncate otherwise).
    """
    n = x.shape[0]
    n_batches = n // batch_size
    if n_batches == 0:
        raise ValueError(
            f"batch_size={batch_size} exceeds the {n} samples per client — "
            f"local SGD would silently train nothing; shrink batch_size or "
            f"grow n_train/shards")
    n_used = n_batches * batch_size

    grad_fn = jax.grad(loss_fn)

    def epoch_body(params, ek):
        perm = jax.random.permutation(ek, n)[:n_used]
        xb = x[perm].reshape((n_batches, batch_size) + x.shape[1:])
        yb = y[perm].reshape((n_batches, batch_size))

        def batch_body(p, xy):
            bx, by = xy
            g = grad_fn(p, bx, by)
            return jax.tree.map(lambda w, gw: w - lr * gw, p, g), None

        params, _ = jax.lax.scan(batch_body, params, (xb, yb))
        return params, None

    ekeys = jax.random.split(key, epochs)
    params, _ = jax.lax.scan(epoch_body, params, ekeys)
    return params


def resolve_cap(n: int, select_cap: int | None) -> int:
    """Static gather width for ``compute="selected"``: ``select_cap``
    clamped to the fleet size, or the full fleet when unset.  One helper so
    every engine's shape bucket keys on the same cap value."""
    return n if select_cap is None else min(int(select_cap), n)


def topk_selected_indices(selected: jnp.ndarray, cap: int) -> jnp.ndarray:
    """[cap] client indices with every selected client first (stable order).

    The static-size gather behind ``compute="selected"``: scheduled clients
    come first in original index order, unscheduled ones pad the tail (their
    aggregation weight is 0, so training them is wasted-but-harmless work).
    When ``cap`` covers all selected clients the aggregated result equals
    the full-fleet computation; when it does not, the overflow clients are
    dropped from aggregation (a documented approximation — the fleet stops
    paying the ~N/K wasted-compute tax of training everyone).
    """
    return jnp.argsort(jnp.logical_not(selected), stable=True)[:cap]


def gather_client_tree(tree: PyTree, idx: jnp.ndarray) -> PyTree:
    """Gather [cap, ...] rows from a client-batched pytree (leaves [N, ...]).

    The sparse-selected-state primitive: per-client model/optimizer state is
    gathered down to the ``topk_selected_indices`` subset BEFORE local
    training, so the learning plane never materialises [N, model]-sized
    pytrees — memory scales with the selected set, not the population.
    """
    return jax.tree.map(lambda a: a[idx], tree)


def scatter_client_tree(n: int, idx: jnp.ndarray, tree: PyTree,
                        base: PyTree | None = None) -> PyTree:
    """Scatter [cap, ...] rows back to client-indexed [N, ...] leaves.

    Inverse of :func:`gather_client_tree` for aggregation: rows land at
    their original client index (out-of-range sentinel indices drop), on
    top of ``base`` when given, zeros otherwise.  Keeping the scatter in
    client-index order is what preserves the fleet's float accumulation
    order — the bit-identity anchor of the parity tests.
    """
    if base is None:
        return jax.tree.map(
            lambda a: jnp.zeros((n,) + a.shape[1:], a.dtype)
                         .at[idx].set(a, mode="drop"), tree)
    return jax.tree.map(
        lambda b, a: b.at[idx].set(a.astype(b.dtype), mode="drop"),
        base, tree)


def fleet_local_sgd(loss_fn: Callable, global_params: PyTree,
                    x_all: jnp.ndarray, y_all: jnp.ndarray, keys: jax.Array,
                    epochs: int, batch_size: int, lr: float) -> PyTree:
    """vmap of local_sgd over the client axis.

    x_all: [N, n_i, ...]; y_all: [N, n_i]; keys: [N, 2].
    Returns a pytree whose leaves have a leading client axis [N, ...].
    """
    fn = partial(local_sgd, loss_fn, epochs=epochs, batch_size=batch_size,
                 lr=lr)
    with jax.named_scope("round.sgd"):
        return jax.vmap(lambda xx, yy, kk: fn(global_params, xx, yy, kk))(
            x_all, y_all, keys)


def fleet_local_sgd_per_client(loss_fn: Callable, init_params: PyTree,
                               x_all: jnp.ndarray, y_all: jnp.ndarray,
                               keys: jax.Array, epochs: int, batch_size: int,
                               lr: float) -> PyTree:
    """vmap of local_sgd where EACH client starts from its own params.

    The hierarchical engine's data plane: client i pulls the edge model of
    its serving BS (handover-aware — a user that moved cells trains from
    the new cell's model), so ``init_params`` leaves carry a leading client
    axis [N, ...] instead of being broadcast from one global model.
    """
    fn = partial(local_sgd, loss_fn, epochs=epochs, batch_size=batch_size,
                 lr=lr)
    with jax.named_scope("round.sgd"):
        return jax.vmap(lambda p, xx, yy, kk: fn(p, xx, yy, kk))(
            init_params, x_all, y_all, keys)
