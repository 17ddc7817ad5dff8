"""Training checkpoint/resume: Orbax save/restore of the full TrainState.

The reference is inference-only — its ArksModel pipeline ships SERVING
checkpoints (scripts/download.py; here models/weights.py adds the Orbax
conversion).  Training is this repo's additive capability, and a trainer
without resume isn't one: this module persists the complete state (params
+ optimizer moments + step) with step-numbered retention, sharded-aware
on restore — under a mesh each host reads only the shards it owns, the
same property the serving loader has (models/weights.py:load_orbax).

Restore builds its template ABSTRACTLY (jax.eval_shape — no device
allocation; a materialized template would double peak memory at exactly
the model sizes resume matters for) and takes the checkpoint's own stored
dtype from Orbax metadata, so a bf16 run restores bf16 without the caller
restating it — resume stays bit-identical to an uninterrupted run.
"""

from __future__ import annotations

import functools
import os
from typing import Any

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from arks_tpu.models import transformer as tf
from arks_tpu.train.sft import TrainState, train_init


def make_manager(directory: str, max_to_keep: int = 3):
    """Step-numbered checkpoint directory with bounded retention."""
    import orbax.checkpoint as ocp

    return ocp.CheckpointManager(
        os.path.abspath(directory),
        options=ocp.CheckpointManagerOptions(max_to_keep=max_to_keep))


def save_train_state(manager, state: TrainState, wait: bool = True) -> int:
    """Persist ``state`` under its own step number; returns the step.

    ``wait=False`` lets the write overlap the next training steps
    (CheckpointManager serializes with any subsequent save itself); pass
    True — the default — when durability must be certain on return."""
    import orbax.checkpoint as ocp

    step = int(state.step)
    manager.save(step, args=ocp.args.StandardSave(state))
    if wait:
        manager.wait_until_finished()
    return step


def _stored_dtype(manager, step: int):
    """The checkpoint's own parameter dtype (Orbax metadata) — restoring
    into a template of a DIFFERENT dtype would silently cast the state and
    break bit-identical resume.  None when metadata is unavailable."""
    import logging

    import orbax.checkpoint as ocp

    try:
        meta = ocp.StandardCheckpointer().metadata(
            os.path.join(manager.directory, str(step), "default"))
        tree = getattr(meta.item_metadata, "tree", meta.item_metadata)
        return jax.numpy.dtype(tree["params"]["embed"].dtype)
    except (KeyError, TypeError, AttributeError, FileNotFoundError,
            ValueError) as e:
        # Loud fallback: a silently-wrong template dtype would upcast a
        # bf16 checkpoint and break bit-identical resume — if this fires,
        # pass dtype= explicitly (Orbax metadata layout likely changed).
        logging.getLogger("arks_tpu.train.checkpoint").warning(
            "could not read checkpoint dtype metadata (%s: %s); "
            "defaulting the restore template to float32 — pass dtype= "
            "explicitly if the run used another dtype", type(e).__name__, e)
        return None


def _sharded_template(abstract: TrainState, cfg, mesh) -> TrainState:
    """Attach restore shardings to an abstract state: every params-shaped
    subtree (the params themselves, optimizer moments) shards with the
    trainer's param specs; remaining leaves (step counters, schedule
    state) restore replicated on the mesh."""
    params_treedef = jax.tree.structure(abstract.params)
    pspecs = tf.param_pspecs(cfg, mesh.shape.get(tf.AXIS_MODEL, 1))

    def with_specs(subtree):
        return jax.tree.map(
            lambda s, spec: jax.ShapeDtypeStruct(
                s.shape, s.dtype, sharding=NamedSharding(mesh, spec)),
            subtree, pspecs)

    def walk(node):
        if jax.tree.structure(node) == params_treedef:
            return with_specs(node)
        if isinstance(node, dict):
            return {k: walk(v) for k, v in node.items()}
        if isinstance(node, tuple):
            mapped = [walk(c) for c in node]
            return (type(node)(*mapped) if hasattr(node, "_fields")
                    else tuple(mapped))
        if isinstance(node, list):
            return [walk(c) for c in node]
        # Leaf (ShapeDtypeStruct): replicated — a committed single-device
        # sharding here would conflict with mesh-sharded params inside the
        # jitted train step.
        return jax.ShapeDtypeStruct(node.shape, node.dtype,
                                    sharding=NamedSharding(mesh, P()))

    return walk(abstract)


def restore_train_state(manager, cfg, optimizer, mesh=None,
                        dtype: Any = None, step: int | None = None
                        ) -> TrainState:
    """Restore a TrainState (latest step by default), placed directly onto
    ``mesh`` with the trainer's shardings.  The template's tree structure
    comes from an ABSTRACT ``train_init`` (zero allocation — the optimizer
    state's structure can never drift from what the optimizer builds), its
    dtype from the checkpoint's own metadata (``dtype`` overrides)."""
    import jax.numpy as jnp
    import orbax.checkpoint as ocp

    step = manager.latest_step() if step is None else step
    if step is None:
        raise FileNotFoundError(
            f"no checkpoint steps under {manager.directory}")
    tdtype = (jnp.dtype(dtype) if dtype is not None
              else _stored_dtype(manager, step) or jnp.float32)
    abstract = jax.eval_shape(functools.partial(
        train_init, cfg, jax.random.PRNGKey(0), optimizer, None, tdtype))
    if mesh is not None:
        template = _sharded_template(abstract, cfg, mesh)
    else:
        dev = jax.devices()[0]
        template = jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(
                s.shape, s.dtype,
                sharding=jax.sharding.SingleDeviceSharding(dev)),
            abstract)
    state = manager.restore(step, args=ocp.args.StandardRestore(template))
    from arks_tpu.models.weights import check_restored_shapes
    check_restored_shapes(state, template,
                          os.path.join(str(manager.directory), str(step)))
    return state
