"""Pipeline (pp) and expert (ep) parallelism over the device mesh.

Completes the five-axis sharding story (dp/tp/sp from models/train.py,
pp/ep here). TPU-first design, not a translation: stages and experts are
laid out with ``shard_map`` over named mesh axes and the collectives are
explicit XLA primitives that ride ICI — ``ppermute`` moves microbatch
activations between pipeline stages (GPipe schedule) and ``all_to_all``
does MoE token dispatch/combine (GShard top-1 gating with capacity).

Both transforms are differentiable end to end (ppermute/all_to_all have
transposes), so ``jax.grad`` through a pp x ep step works — the dryrun
executes exactly that.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from nornicdb_tpu.parallel.mesh import shard_map_unchecked


# -- pipeline parallelism -------------------------------------------------
#
# Model: a stack of identical MLP blocks, one (or more) per stage. Stage
# parameters live stacked on a leading [pp] axis, sharded so each device
# along 'pp' holds only its own stage weights. The GPipe schedule runs
# n_micro + pp - 1 ticks; on every tick each stage processes the
# activation it holds, then the ring ppermutes activations forward.


def init_pipeline_params(
    rng: jax.Array, n_stages: int, width: int, scale: float = 0.02
) -> Dict[str, jnp.ndarray]:
    k1, k2 = jax.random.split(rng)
    return {
        "w1": jax.random.normal(k1, (n_stages, width, width)) * scale,
        "b1": jnp.zeros((n_stages, width)),
        "w2": jax.random.normal(k2, (n_stages, width, width)) * scale,
        "b2": jnp.zeros((n_stages, width)),
    }


def _stage_block(params: Dict[str, jnp.ndarray], x: jnp.ndarray) -> jnp.ndarray:
    """One residual MLP stage; params are this stage's [1, ...] slices."""
    h = x @ params["w1"][0] + params["b1"][0]
    h = jax.nn.gelu(h)
    return x + h @ params["w2"][0] + params["b2"][0]


def pipeline_apply(
    params: Dict[str, jnp.ndarray],
    x: jnp.ndarray,
    mesh: Mesh,
    n_microbatches: int,
    batch_axis: str = None,
) -> jnp.ndarray:
    """Run x [B, D] through the pp-staged network; B % n_microbatches == 0.

    Inside shard_map each 'pp' device sees its own stage params and the
    full microbatch stream. Tick t: stage s processes the activation
    that entered the pipe at microbatch t - s; a forward ppermute ring
    then advances activations one stage. Output microbatch i leaves the
    last stage at tick i + pp - 1 and is captured there.

    ``batch_axis`` names a second mesh axis to shard the rows of each
    microbatch over (the combined pp x ep step passes "ep") so those
    devices each process their slice instead of replicating the whole
    pipeline compute; rows must divide evenly.
    """
    pp = mesh.shape["pp"]
    batch, width = x.shape
    assert batch % n_microbatches == 0
    micro = batch // n_microbatches
    if batch_axis is not None:
        assert micro % mesh.shape[batch_axis] == 0, (
            f"microbatch rows {micro} not divisible by "
            f"{batch_axis}={mesh.shape[batch_axis]}")
    n_ticks = n_microbatches + pp - 1
    xs = x.reshape(n_microbatches, micro, width)

    def staged(local_params, xs_local):
        idx = jax.lax.axis_index("pp")
        perm = [(i, (i + 1) % pp) for i in range(pp)]
        rows_local = xs_local.shape[1]  # micro / ep when batch_axis set

        def tick(carry, t):
            held, outputs = carry
            # stage 0 ingests microbatch t (zero-padded past the end)
            feed = jnp.where(
                t < n_microbatches,
                xs_local[jnp.minimum(t, n_microbatches - 1)],
                jnp.zeros((rows_local, width), xs_local.dtype),
            )
            held = jnp.where(idx == 0, feed, held)
            out = _stage_block(local_params, held)
            # the last stage emits microbatch t - (pp - 1) at this tick
            emit_slot = t - (pp - 1)
            is_emit = jnp.logical_and(idx == pp - 1, emit_slot >= 0)
            outputs = jax.lax.cond(
                is_emit,
                lambda o: jax.lax.dynamic_update_index_in_dim(
                    o, out, jnp.maximum(emit_slot, 0), axis=0),
                lambda o: o,
                outputs,
            )
            # advance the ring: stage s's output becomes s+1's input
            held = jax.lax.ppermute(out, "pp", perm)
            return (held, outputs), None

        init = (
            jnp.zeros((rows_local, width), xs_local.dtype),
            jnp.zeros((n_microbatches, rows_local, width), xs_local.dtype),
        )
        (held, outputs), _ = jax.lax.scan(
            tick, init, jnp.arange(n_ticks))
        # only the last stage holds real outputs; psum broadcasts them
        outputs = jnp.where(idx == pp - 1, outputs, 0.0)
        return jax.lax.psum(outputs, "pp")

    data_spec = P(None, batch_axis) if batch_axis else P()
    out = shard_map_unchecked(
        staged,
        mesh=mesh,
        in_specs=(P("pp"), data_spec),
        out_specs=data_spec,
    )(params, xs)
    return out.reshape(batch, width)


# -- expert parallelism (MoE) ---------------------------------------------
#
# GShard-style top-1 routing with a fixed per-expert capacity. Tokens are
# sharded over 'ep' (data-parallel along the same axis the experts live
# on); dispatch/combine are einsums against a one-hot dispatch tensor and
# the cross-device exchange is a single all_to_all each way.


def init_moe_params(
    rng: jax.Array, n_experts: int, width: int, hidden: int,
    scale: float = 0.02,
) -> Dict[str, jnp.ndarray]:
    k1, k2, k3 = jax.random.split(rng, 3)
    return {
        "router": jax.random.normal(k1, (width, n_experts)) * scale,
        "wi": jax.random.normal(k2, (n_experts, width, hidden)) * scale,
        "wo": jax.random.normal(k3, (n_experts, hidden, width)) * scale,
    }


def moe_apply(
    params: Dict[str, jnp.ndarray],
    x: jnp.ndarray,
    mesh: Mesh,
    capacity_factor: float = 1.25,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Top-1 MoE layer: x [B, D] -> (y [B, D], aux_loss).

    B is sharded over 'ep'; expert weights are sharded over 'ep' (expert
    e lives on device e * n_local_experts). Router weights are
    replicated. aux_loss is the standard load-balancing term.
    """
    ep = mesh.shape["ep"]
    n_experts = params["wi"].shape[0]
    assert n_experts % ep == 0

    def local(params_local, x_local):
        router, wi, wo = (params_local["router"], params_local["wi"],
                          params_local["wo"])
        b_local, width = x_local.shape
        capacity = max(int(capacity_factor * b_local / n_experts), 1)

        scores = jax.nn.softmax(x_local @ router, axis=-1)  # [b, E]
        expert = jnp.argmax(scores, axis=-1)                # [b]
        gate = jnp.max(scores, axis=-1)                     # [b]
        onehot = jax.nn.one_hot(expert, n_experts, dtype=x_local.dtype)

        # position of each token within its expert's queue
        pos = jnp.cumsum(onehot, axis=0) * onehot  # [b, E]
        rank = (jnp.sum(pos, axis=-1) - 1.0).astype(jnp.int32)
        keep = rank < capacity  # overflow tokens are dropped (std MoE)
        # dispatch tensor [b, E, C]
        dispatch = (onehot[:, :, None]
                    * jax.nn.one_hot(rank, capacity,
                                     dtype=x_local.dtype)[:, None, :])
        dispatch = dispatch * keep[:, None, None].astype(x_local.dtype)

        # load-balancing aux loss (GShard eq. 4)
        density = jnp.mean(onehot, axis=0)
        density_proxy = jnp.mean(scores, axis=0)
        aux = jnp.sum(density * density_proxy) * (n_experts ** 2) / 100.0

        # [E, C, D] expert inputs, exchanged so each device holds the
        # token slots of ITS experts from EVERY device. Expert ids are
        # owner-major: e = owner * n_local + e_local.
        n_local = n_experts // ep
        slots = jnp.einsum("bec,bd->ecd", dispatch, x_local)
        slots = slots.reshape(ep, n_local, capacity, width)
        # split the owner axis; received chunks stack on a new source
        # axis at position 2: [n_local, C, src, D]
        slots = jax.lax.all_to_all(
            slots, "ep", split_axis=0, concat_axis=2, tiled=False)
        slots = jnp.moveaxis(slots, 2, 1).reshape(
            n_local, ep * capacity, width)
        h = jax.nn.gelu(jnp.einsum("ecd,edh->ech", slots, wi))
        y = jnp.einsum("ech,ehd->ecd", h, wo)
        # inverse exchange: split the source axis, stack owners at 0
        y = y.reshape(n_local, ep, capacity, width)
        y = jax.lax.all_to_all(
            y, "ep", split_axis=1, concat_axis=0, tiled=False)
        y = y.reshape(n_experts, capacity, width)
        out = jnp.einsum("bec,ecd->bd", dispatch, y) * gate[:, None]
        return out, jax.lax.pmean(aux, "ep")

    return shard_map_unchecked(
        local,
        mesh=mesh,
        in_specs=(
            {"router": P(), "wi": P("ep"), "wo": P("ep")},
            P("ep"),
        ),
        out_specs=(P("ep"), P()),
    )(params, x)


# -- combined pp x ep training step ---------------------------------------


def make_pp_ep_train_step(
    mesh: Mesh,
    width: int,
    hidden: int,
    n_microbatches: int,
    learning_rate: float = 1e-3,
):
    """A jitted train step for a pipeline of MLP stages followed by an
    expert-parallel MoE head, over a (pp, ep) mesh. Returns
    (init_params_fn, step_fn); step_fn(params, x, y) -> (params, loss).
    """
    pp = mesh.shape["pp"]
    ep = mesh.shape["ep"]

    def init_params(rng):
        r1, r2 = jax.random.split(rng)
        params = {
            "pipe": init_pipeline_params(r1, pp, width),
            "moe": init_moe_params(r2, ep, width, hidden),
        }
        shardings = {
            "pipe": jax.tree.map(
                lambda _: NamedSharding(mesh, P("pp")), params["pipe"]),
            "moe": {
                "router": NamedSharding(mesh, P()),
                "wi": NamedSharding(mesh, P("ep")),
                "wo": NamedSharding(mesh, P("ep")),
            },
        }
        return jax.device_put(params, shardings), shardings

    def loss_fn(params, x, y):
        h = pipeline_apply(params["pipe"], x, mesh, n_microbatches,
                           batch_axis="ep")
        delta, aux = moe_apply(params["moe"], h, mesh)
        out = h + delta  # residual MoE head
        mse = jnp.mean((out - y) ** 2)
        return mse + 0.01 * aux

    @functools.partial(jax.jit, donate_argnums=(0,))
    def step(params, x, y):
        loss, grads = jax.value_and_grad(loss_fn)(params, x, y)
        params = jax.tree.map(
            lambda p, g: p - learning_rate * g, params, grads)
        return params, loss

    return init_params, step


def make_pp_ep_mesh(n_devices: int, devices=None) -> Mesh:
    """Split devices into (pp, ep): pp gets 2 when possible, ep the rest."""
    pp = 2 if n_devices % 2 == 0 and n_devices >= 4 else 1
    ep = n_devices // pp
    devices = list(devices if devices is not None else jax.devices())
    arr = np.array(devices[:n_devices]).reshape(pp, ep)
    return Mesh(arr, axis_names=("pp", "ep"))
