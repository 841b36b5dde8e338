"""Abstract input specs (ShapeDtypeStruct) + shardings for every
(arch x shape) cell — the dry-run stand-ins.  No device allocation happens
here: params, optimizer state, batches and KV caches are all abstract.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding

from repro.configs import ArchConfig, ShapeConfig
from repro.models import params as pm
from repro.sharding.rules import RULE_SETS, sharding_for

TRAIN_PARAM_DTYPE = jnp.float32
SERVE_PARAM_DTYPE = jnp.bfloat16

BATCH_AXES = {
    "tokens": ("batch", "seq"),
    "labels": ("batch", "seq"),
    "frames": ("batch", "seq", "act_embed"),
    "visual_embeds": ("batch", None, "act_embed"),
    "mrope_positions": (None, "batch", "seq"),
}


def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


def batch_specs(cfg: ArchConfig, shape: ShapeConfig, *, with_labels: bool):
    b, s = shape.global_batch, shape.seq_len
    out = {"tokens": _sds((b, s), jnp.int32)}
    if with_labels:
        out["labels"] = _sds((b, s), jnp.int32)
    if cfg.family == "audio":
        out["frames"] = _sds((b, s, cfg.d_model), jnp.bfloat16)
    if cfg.family == "vlm":
        out["visual_embeds"] = _sds((b, cfg.n_vis_tokens, cfg.d_model), jnp.bfloat16)
        out["mrope_positions"] = _sds((3, b, s), jnp.int32)
    return out


def batch_shardings(batch, rules, mesh):
    return {
        k: sharding_for(BATCH_AXES[k], v.shape, rules, mesh) for k, v in batch.items()
    }


def cache_abstract(model, cfg, batch: int, seq: int):
    """(abstract_tree, axes_tree) from the model's (shape, axes, dtype) cache spec."""
    leaves_spec = model.cache_spec(batch, seq)
    is_leaf = lambda x: isinstance(x, tuple) and len(x) == 3 and isinstance(x[0], tuple)
    abstract = jax.tree.map(lambda l: _sds(l[0], l[2]), leaves_spec, is_leaf=is_leaf)
    axes = jax.tree.map(lambda l: l[1], leaves_spec, is_leaf=is_leaf)
    return abstract, axes


def tree_shardings(axes_tree, abstract_tree, rules, mesh):
    # logical-axis leaves are tuples -> flatten relative to the array tree
    leaves, treedef = jax.tree.flatten(abstract_tree)
    axes_leaves = treedef.flatten_up_to(axes_tree)
    return treedef.unflatten(
        [sharding_for(ax, a.shape, rules, mesh) for ax, a in zip(axes_leaves, leaves)]
    )


def build_cell(model, cfg: ArchConfig, shape: ShapeConfig, mesh, rules_name: str | None = None):
    """Everything the dry-run needs for one cell:
    returns dict(kind, args=(abstract...), in_shardings, out_shardings, rules).
    rules_name overrides the default RULE_SETS[shape.kind] (§Perf variants)."""
    rules = RULE_SETS[rules_name or shape.kind]
    spec = model.spec()
    paxes = pm.axes_tree(spec)
    repl = NamedSharding(mesh, jax.sharding.PartitionSpec())

    if shape.kind == "train":
        params = pm.abstract(spec, TRAIN_PARAM_DTYPE)
        psh = tree_shardings(paxes, params, rules, mesh)
        opt = {
            "m": params,
            "v": params,
            "step": _sds((), jnp.int32),
        }
        osh = {"m": psh, "v": psh, "step": repl}
        batch = batch_specs(cfg, shape, with_labels=True)
        bsh = batch_shardings(batch, rules, mesh)
        step_sds = _sds((), jnp.int32)
        metrics_sh = {"loss": repl, "grad_norm": repl, "lr": repl}
        return dict(
            kind="train",
            rules=rules,
            args=(params, opt, batch, step_sds),
            in_shardings=(psh, osh, bsh, repl),
            out_shardings=(psh, osh, metrics_sh),
        )

    params = pm.abstract(spec, SERVE_PARAM_DTYPE)
    psh = tree_shardings(paxes, params, rules, mesh)

    if shape.kind == "prefill":
        batch = batch_specs(cfg, shape, with_labels=False)
        bsh = batch_shardings(batch, rules, mesh)
        cabs, caxes = cache_abstract(model, cfg, shape.global_batch, shape.seq_len)
        csh = tree_shardings(caxes, cabs, rules, mesh)
        logits_sh = sharding_for(("batch", "vocab"), (shape.global_batch, cfg.vocab_size), rules, mesh)
        return dict(
            kind="prefill",
            rules=rules,
            args=(params, batch),
            in_shardings=(psh, bsh),
            out_shardings=(logits_sh, csh),
        )

    # decode / long -> serve_step(params, caches, tokens, pos)
    cabs, caxes = cache_abstract(model, cfg, shape.global_batch, shape.seq_len)
    csh = tree_shardings(caxes, cabs, rules, mesh)
    tokens = _sds((shape.global_batch, 1), jnp.int32)
    tsh = sharding_for(("batch", None), tokens.shape, rules, mesh)
    pos = _sds((), jnp.int32)
    logits_sh = sharding_for(("batch", "vocab"), (shape.global_batch, cfg.vocab_size), rules, mesh)
    return dict(
        kind=shape.kind,
        rules=rules,
        args=(params, cabs, tokens, pos),
        in_shardings=(psh, csh, tsh, repl),
        out_shardings=(tsh, logits_sh, csh),
    )
