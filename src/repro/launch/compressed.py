"""Cross-pod int8 gradient reduction step (beyond-paper hillclimb).

On the 2x16x16 multi-pod mesh the "pod" axis is the oversubscribed DCN tier
— the paper's problem tier. This step computes gradients with GSPMD auto
partitioning *inside* each pod (data/model axes stay automatic), then
exchanges pod-partial gradients explicitly over the pod axis as int8 with
per-block scales via a ppermute ring (``repro.optim.compress``), cutting
cross-pod wire bytes ~3.9x vs bf16 all-reduce.

Trade-offs:
  * optimizer moments are pod-replicated here (zero1 off) to keep the
    manual-pod in_specs simple — the target term is collective, not memory;
  * the lowered variant quantizes without error feedback (EF changes
    numerics, not wire bytes; the EF form lives in repro.optim.compress).
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import OptimizerConfig, ShapeConfig
from repro.launch import sharding as shd
from repro.launch.steps import batch_shardings, param_shardings, _named
from repro.models.api import Model, input_specs
from repro.optim import adamw_update, init_opt_state
from repro.optim.compress import _int8_ring_all_reduce


def make_compressed_train_step(model: Model, opt_cfg: OptimizerConfig,
                               mesh: Mesh):
    """Train step with explicit int8 ring-reduction over the pod axis."""
    pod = mesh.shape["pod"]
    assert pod > 1, "compressed step targets the multi-pod mesh"
    # inside the manual-pod region, logical rules must not mention "pod"
    inner_rules = {"batch": ("data",), "ddp": ("data",)}

    def ring_leaf(g, spec):
        """Quantized pod-ring on the *device-local shard*: a nested
        shard_map binds data/model manual with the leaf's own partition
        spec, so the int8 wire payload is shard-sized (params/TP), not the
        logical tensor — without this, GSPMD gathers the full gradient to
        satisfy the blockwise-quantize reshapes."""
        def inner(gl):
            out = _int8_ring_all_reduce(gl.astype(jnp.float32), "pod", pod)
            return out.astype(g.dtype)
        inner_axes = {a for a in ("data", "model") if a in mesh.shape}
        return jax.shard_map(
            inner, mesh=shd.shard_map_mesh(), in_specs=(spec,),
            out_specs=spec, axis_names=inner_axes, check_vma=False)(g)

    def body(params, opt_state, batch):
        with shd.axis_rules(mesh, inner_rules):
            (loss, metrics), grads = jax.value_and_grad(
                model.loss, has_aux=True)(params, batch)
            pspec = model.param_spec(params)
            grads = jax.tree.map(ring_leaf, grads, pspec,
                                 is_leaf=lambda x: isinstance(x, jax.Array))
            params, opt_state, om = adamw_update(opt_cfg, params, grads,
                                                 opt_state)
            metrics = jax.tree.map(lambda x: jax.lax.pmean(x, "pod"),
                                   dict(metrics, **om))
            return params, opt_state, metrics

    def batch_spec(name, v):
        if name == "mrope_positions":
            return P(None, "pod", None)
        if v.ndim == 0:
            return P()
        return P(*(["pod"] + [None] * (v.ndim - 1)))

    def step(params, opt_state, batch):
        bspecs = {k: batch_spec(k, v) for k, v in batch.items()}
        pspec = jax.tree.map(lambda _: P(), params)
        ospec = jax.tree.map(lambda _: P(), opt_state)
        return jax.shard_map(
            body, mesh=mesh,
            in_specs=(pspec, ospec, bspecs),
            out_specs=(pspec, ospec, P()),
            axis_names={"pod"},
            check_vma=False,
        )(params, opt_state, batch)

    return step


def lower_compressed_train_step(model: Model, opt_cfg: OptimizerConfig,
                                mesh: Mesh, shape: ShapeConfig):
    """AOT-lower the compressed step (multi-pod mesh). Call under
    ``shd.axis_rules(mesh)``."""
    cfg = model.cfg
    opt_cfg = opt_cfg.__class__(**{**opt_cfg.__dict__, "zero1": False})
    aparams = model.abstract_params()
    aopt = jax.eval_shape(functools.partial(init_opt_state, opt_cfg),
                          aparams)
    pshard = param_shardings(mesh, model, aparams)
    # moments mirror the params (pod-replicated; see module docstring)
    pspec_tree = model.param_spec(aparams)
    mu_shard = jax.tree.map(lambda s: NamedSharding(mesh, s), pspec_tree,
                            is_leaf=lambda x: isinstance(x, P))
    oshard = type(aopt)(step=NamedSharding(mesh, P()), mu=mu_shard,
                        nu=jax.tree.map(lambda s: s, mu_shard))
    bspecs = input_specs(cfg, shape)
    bshard = batch_shardings(mesh, bspecs)

    step = make_compressed_train_step(model, opt_cfg, mesh)
    jitted = jax.jit(step, in_shardings=(pshard, oshard, bshard),
                     out_shardings=(pshard, oshard, None),
                     donate_argnums=(0, 1))
    return jitted.lower(aparams, aopt, bspecs), (aparams, aopt, bspecs)
