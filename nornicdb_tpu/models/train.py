"""Contrastive training step for the encoder, sharded over a device mesh.

The reference never trains (inference-only llama.cpp); training support is
what makes the TPU embedding stack self-improving (fine-tune bge-m3-style
encoders on the graph's own co-access/link data). The step is the standard
InfoNCE in-batch-negatives objective.

Sharding design (scaling-book recipe): pick a mesh (dp, tp, sp), annotate
param shardings (encoder.param_sharding_rules) and batch shardings
(batch -> dp, sequence -> sp), jit, and let XLA insert the collectives:
- dp: gradients all-reduce over ICI,
- tp: attention-head/MLP-width partials reduce-scatter inside each layer,
- sp: sequence-sharded activations; attention gathers K/V over sp.
"""

from __future__ import annotations

from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp
import optax
from flax import struct
from flax.core import FrozenDict
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from nornicdb_tpu.models.encoder import Encoder, EncoderConfig, param_sharding_rules


class TrainState(struct.PyTreeNode):
    step: jnp.ndarray
    params: Any
    opt_state: Any
    tx: optax.GradientTransformation = struct.field(pytree_node=False)

    def apply_gradients(self, grads):
        updates, new_opt = self.tx.update(grads, self.opt_state, self.params)
        return self.replace(
            step=self.step + 1,
            params=optax.apply_updates(self.params, updates),
            opt_state=new_opt,
        )


def create_train_state(
    cfg: EncoderConfig,
    rng: jax.Array,
    learning_rate: float = 1e-4,
    seq_len: int = 64,
) -> Tuple[Encoder, TrainState]:
    model = Encoder(cfg)
    dummy = jnp.ones((2, seq_len), jnp.int32)
    params = model.init(rng, dummy)["params"]
    tx = optax.adamw(learning_rate)
    state = TrainState(
        step=jnp.zeros((), jnp.int32), params=params,
        opt_state=tx.init(params), tx=tx,
    )
    return model, state


def info_nce_loss(
    anchors: jnp.ndarray, positives: jnp.ndarray, temperature: float = 0.05
) -> jnp.ndarray:
    """Symmetric in-batch negatives: row i's positive is column i, and
    the loss runs both directions (anchor->positive and
    positive->anchor) — the asymmetric query/document window pairs mean
    each direction carries distinct gradient signal."""
    logits = anchors @ positives.T / temperature  # [B, B]
    labels = jnp.arange(logits.shape[0])
    return 0.5 * (
        jnp.mean(optax.softmax_cross_entropy_with_integer_labels(
            logits, labels))
        + jnp.mean(optax.softmax_cross_entropy_with_integer_labels(
            logits.T, labels))
    )


def contrastive_train_step(
    model: Encoder,
    state: TrainState,
    anchor_ids: jnp.ndarray,
    positive_ids: jnp.ndarray,
) -> Tuple[TrainState, jnp.ndarray]:
    """One unsharded (single-device) step; jit-cache with
    jax.jit(functools.partial(contrastive_train_step, model))."""

    def loss_fn(params):
        a = model.apply({"params": params}, anchor_ids)
        p = model.apply({"params": params}, positive_ids)
        return info_nce_loss(a, p)

    loss, grads = jax.value_and_grad(loss_fn)(state.params)
    return state.apply_gradients(grads), loss


def _param_shardings(params, cfg: EncoderConfig, mesh: Mesh):
    rule = param_sharding_rules(cfg)

    def assign(path, value):
        path_str = "/".join(str(k.key) for k in path)
        return NamedSharding(mesh, rule(path_str, value))

    return jax.tree_util.tree_map_with_path(assign, params)


def make_sharded_train_step(
    model: Encoder,
    state: TrainState,
    mesh: Mesh,
) -> Tuple[TrainState, Callable]:
    """Place ``state`` onto the mesh per the partitioning rules and return
    (sharded_state, jitted_step). The step shards batch over dp and
    sequence over sp; XLA inserts all collectives (GSPMD)."""
    import dataclasses

    cfg = model.cfg
    if cfg.mesh is not mesh:
        # attach the mesh so attention takes the ring (sp) path
        model = Encoder(dataclasses.replace(cfg, mesh=mesh))
    param_sh = _param_shardings(state.params, cfg, mesh)
    opt_sh = _opt_shardings(state, param_sh, mesh)
    state = state.replace(
        params=jax.device_put(state.params, param_sh),
        opt_state=jax.device_put(state.opt_state, opt_sh),
        step=jax.device_put(state.step, NamedSharding(mesh, P())),
    )
    data_sh = NamedSharding(mesh, P("dp", "sp"))

    def step_fn(st: TrainState, anchor_ids, positive_ids):
        return contrastive_train_step(model, st, anchor_ids, positive_ids)

    state_sh = TrainState(
        step=NamedSharding(mesh, P()),
        params=param_sh,
        opt_state=opt_sh,
        tx=state.tx,
    )
    jitted = jax.jit(
        step_fn,
        in_shardings=(state_sh, data_sh, data_sh),
        out_shardings=(state_sh, NamedSharding(mesh, P())),
    )

    def run(st, anchor_ids, positive_ids):
        # activation sharding constraints use raw PartitionSpecs, which
        # need the mesh in context at trace time
        with jax.set_mesh(mesh):
            return jitted(st, anchor_ids, positive_ids)

    return state, run


def _opt_shardings(state: TrainState, param_sh, mesh: Mesh):
    """adamw state = (ScaleByAdamState(count, mu, nu), extras): moments get
    the param shardings, scalars replicate."""

    def assign(x):
        return NamedSharding(mesh, P())

    def walk(opt_state):
        out = []
        for part in opt_state:
            if hasattr(part, "mu") and hasattr(part, "nu"):
                out.append(
                    part._replace(
                        count=NamedSharding(mesh, P()),
                        mu=param_sh,
                        nu=param_sh,
                    )
                )
            else:
                out.append(jax.tree_util.tree_map(assign, part))
        return tuple(out)

    return walk(state.opt_state)
