"""Distributed pruned-FL train step (the paper's technique as a
first-class mesh feature).

Clients map onto the mesh's client axes (("data",) single-pod,
("pod","data") multi-pod): each index along those axes hosts one UE/client
shard.  Per step, every client

  1. derives its own pruning mask from its rho_i (block-structured
     magnitude pruning, computed on the fly — no per-client mask storage),
  2. computes the masked gradient of the masked model on its local batch,
  3. contributes K_i * C_i * grad to a single weighted psum implementing
     the BS aggregation rule Eq. (5),

and the global SGD update replays identically on all shards.  Model
parameters are replicated across client axes (the paper's UEs hold the
full model — it is the *pruned* copy that is cheap), matching FedSGD
semantics exactly.
"""

from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import aggregation, pruning
from repro.fleet.task import FleetTask, TransformerTask

PyTree = Any

def num_clients(mesh: Mesh, client_axes: tuple[str, ...]) -> int:
    n = 1
    for a in client_axes:
        n *= mesh.shape[a]
    return n


def make_task_train_step(task: FleetTask, mesh: Mesh,
                         client_axes: tuple[str, ...] = ("data",),
                         lr: float = 1e-2, tp_shard_params: bool = True):
    """Build the jitted distributed FL train step for any ``FleetTask``.

    The shard_map step is a consumer of the task substrate: masks come
    from ``task.tile_grid`` (per-layer grids for heterogeneous models),
    the local objective is ``task.loss``, and the Eq.-(5) aggregation /
    FedSGD update are task-agnostic.  Signature of the returned fn:
        (params, batch, rho, arrivals, k) -> (params, metrics)
      batch: task-batch pytree, every leaf (num_clients * per_client_batch,
      ...) sharded over the client axes; rho/arrivals/k: (num_clients,)
      host-computed by the trade-off optimizer + channel simulation.

    tp_shard_params: every client holds the full model *semantically*
    (FedSGD), but within a client the weights shard over the Auto tensor
    axis — set via the outer jit's in_shardings, since shard_map in_specs
    may only name the manual client axes.
    """
    caxes = client_axes if len(client_axes) > 1 else client_axes[0]

    def step(params, batch, rho, arrivals, k):
        # inside shard_map: params replicated; batch/rho/... are this
        # client's slice
        rho_i = rho[0]
        c_i = arrivals[0]
        k_i = k[0]

        masks = pruning.block_masks(params, rho_i,
                                    block=task.tile_grid(params))

        def loss_fn(p):
            return task.loss(pruning.apply_masks(p, masks), batch)

        loss, grads = jax.value_and_grad(loss_fn)(params)
        grads = pruning.apply_masks(grads, masks)
        g = aggregation.psum_aggregate(grads, k_i, c_i, client_axes)
        new_params = jax.tree.map(lambda p, gg: p - lr * gg.astype(p.dtype),
                                  params, g)
        mean_loss = jax.lax.pmean(loss, client_axes)
        achieved = pruning.achieved_rate(params, masks).reshape(1)
        return new_params, {"loss": mean_loss, "achieved_rho": achieved}

    # Hybrid manual/auto: the client axes are Manual (explicit psum for the
    # Eq. (5) aggregation), every other mesh axis (the tensor axis) stays
    # Auto so the per-client model computation is partitioned across it by
    # GSPMD + the model's logical sharding constraints.  The batch spec is
    # a pytree *prefix*: P(caxes) broadcasts over every batch leaf.
    mapped = jax.shard_map(
        step, mesh=mesh,
        in_specs=(P(), P(caxes), P(caxes), P(caxes), P(caxes)),
        out_specs=(P(), {"loss": P(), "achieved_rho": P(caxes)}),
        axis_names=set(client_axes), check_vma=False)

    if tp_shard_params and "model" in mesh.axis_names \
            and mesh.shape["model"] > 1:
        from repro.launch import shardings as SH
        params_shape = jax.eval_shape(task.init_params, jax.random.PRNGKey(0))
        p_shard = SH.param_shardings(params_shape, mesh, fsdp=False)
        cshard = NamedSharding(mesh, P(caxes))
        return jax.jit(mapped,
                       in_shardings=(p_shard, cshard, cshard,
                                     cshard, cshard),
                       out_shardings=(p_shard, None))
    return jax.jit(mapped)


def make_fl_train_step(cfg, mesh: Mesh,
                       client_axes: tuple[str, ...] = ("data",),
                       block: int = 128, lr: float = 1e-2,
                       tp_shard_params: bool = True):
    """Build the jitted distributed FL train step for an ArchConfig model.

    Thin wrapper: wraps ``cfg`` in a ``TransformerTask`` (uniform ``block``
    tile grid, matching the historical behaviour) and delegates to
    ``make_task_train_step`` — the transformer path and the fleet engine
    now consume the same task object.
    """
    task = TransformerTask(arch=cfg, block=block)
    return make_task_train_step(task, mesh, client_axes=client_axes, lr=lr,
                                tp_shard_params=tp_shard_params)


def fl_input_specs(cfg, mesh: Mesh, client_axes: tuple[str, ...],
                   per_client_batch: int, seq_len: int):
    """ShapeDtypeStructs + NamedShardings for the FL dry-run.

    Returns ``(batch, vec, shardings)`` where ``shardings`` mirrors the
    step's (batch, rho, arrivals, k) inputs: tokens and the per-client
    vectors shard over the client axes, matching ``make_fl_train_step``'s
    in_specs.
    """
    n = num_clients(mesh, client_axes)
    caxes = client_axes if len(client_axes) > 1 else client_axes[0]
    batch = {"tokens": jax.ShapeDtypeStruct((n * per_client_batch, seq_len),
                                            jnp.int32)}
    vec = jax.ShapeDtypeStruct((n,), jnp.float32)
    client_sharding = NamedSharding(mesh, P(caxes))
    shardings = ({"tokens": client_sharding}, client_sharding,
                 client_sharding, client_sharding)
    return batch, vec, shardings
