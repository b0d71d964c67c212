"""Step function builders: train_step (loss+grad+AdamW) and serve_step
(prefill / decode), with sharding annotations for the production mesh.

These are what the dry-run lowers: jax.jit(step, in_shardings, out_shardings)
.lower(**input_specs).compile().
"""
from __future__ import annotations


import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ShapeConfig
from repro.models.factory import Model
from repro.models import spec as S
from repro.train import optim as O


def batch_pspec(rules) -> P:
    b = rules.get("batch", "data")
    return P(b, None)


def make_train_step(model: Model, opt_cfg: O.AdamWConfig, *,
                    lilac_grad: bool = False, lilac_options=None):
    """(params, opt_state, batch) -> (params, opt_state, metrics).

    With cfg.microbatches > 1 the global batch is split on the batch axis
    and gradients are accumulated in f32 over a scan — activation memory
    scales with 1/microbatches (how the 50B+ cells fit HBM); the optimizer
    applies once per step.

    ``lilac_grad=True`` routes the per-(micro)batch value_and_grad through
    ``lilac.compile``: the *gradient* jaxpr is detected and rewritten too,
    so sparse computations in the backward pass (SpMVᵀ scatters, MoE
    scatter-grad) get harnessed exactly like the forward — and once the
    rewrite resolves, the whole value_and_grad bakes into one jitted plan
    (see docs/transforms.md).  ``lilac_options`` is an optional
    :class:`repro.lilac.CompileOptions` for that compile.
    """
    mb = max(1, model.cfg.microbatches)

    value_and_grad = jax.value_and_grad(model.loss_fn)
    if lilac_grad:
        from repro import lilac
        if lilac_options is not None:
            value_and_grad = lilac.compile(value_and_grad,
                                           options=lilac_options)
        else:
            value_and_grad = lilac.compile(value_and_grad)

    # gradient sharding hint: grads live in storage sharding (FSDP x TP).
    # Without this, the scan-backward accumulator round-trips full f32
    # weight gradients through all-gathers every layer; with it, GSPMD
    # reduce-scatters each layer's partial dW over the data axis.
    def _grad_constraint(grads):
        cfg = model.cfg
        if not cfg.spmd_constraints:
            return grads
        from repro.models import spec as S
        sizes = dict(cfg.mesh_axis_sizes)
        rules = S.MULTI_POD_RULES if "pod" in sizes else S.SINGLE_POD_RULES
        ps = jax.tree.map(
            lambda s: S.spec_to_pspec_sizes(s, sizes, rules),
            model.spec, is_leaf=lambda x: isinstance(x, S.ParamSpec))
        return jax.tree.map(
            lambda g, p: jax.lax.with_sharding_constraint(g, p), grads, ps)

    def train_step(params, opt_state, batch):
        if mb == 1:
            loss, grads = value_and_grad(params, batch)
            grads = _grad_constraint(grads)
        else:
            split = jax.tree.map(
                lambda a: a.reshape((mb, a.shape[0] // mb) + a.shape[1:]),
                batch)

            def micro(carry, mbatch):
                loss_acc, gacc = carry
                loss_i, g_i = value_and_grad(params, mbatch)
                g_i = _grad_constraint(g_i)
                gacc = jax.tree.map(
                    lambda a, g: a + g.astype(jnp.float32), gacc, g_i)
                return (loss_acc + loss_i, gacc), None

            gacc0 = _grad_constraint(jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params))
            (loss, grads), _ = jax.lax.scan(
                micro, (jnp.zeros((), jnp.float32), gacc0), split)
            loss = loss / mb
            grads = jax.tree.map(lambda g: g / mb, grads)
        new_params, new_state, metrics = O.adamw_update(
            opt_cfg, grads, opt_state, params)
        metrics = dict(metrics, loss=loss)
        return new_params, new_state, metrics

    return train_step


def make_serve_step(model: Model, kind: str):
    if kind == "prefill":
        def prefill_step(params, batch):
            logits, caches = model.prefill(params, batch)
            return logits, caches
        return prefill_step
    if kind == "decode":
        def decode_step(params, cache, tokens, pos):
            return model.decode(params, cache, tokens, pos)
        return decode_step
    raise ValueError(kind)


# ---------------------------------------------------------------------------
# Sharding trees for jit in_shardings/out_shardings
# ---------------------------------------------------------------------------

def param_shardings(model: Model, mesh: Mesh, rules):
    return S.tree_shardings(model.spec, mesh, rules)


def opt_state_shardings(model: Model, opt_cfg: O.AdamWConfig, mesh: Mesh, rules):
    ps = S.tree_pspecs(model.spec, mesh, rules)
    rep = NamedSharding(mesh, P())
    tree = {
        "step": rep,
        "mu": jax.tree.map(lambda p: NamedSharding(mesh, p), ps),
        "nu": jax.tree.map(lambda p: NamedSharding(mesh, p), ps),
        "master": jax.tree.map(lambda p: NamedSharding(mesh, p), ps),
    }
    if opt_cfg.compress_grads:
        tree["err"] = jax.tree.map(lambda p: NamedSharding(mesh, p), ps)
    return tree


def prefill_cache_shardings(model: Model, shape: ShapeConfig, mesh: Mesh,
                            rules):
    """out_shardings for the prefill-collected cache: KV tensors are
    sequence-sharded over the model axis (32k x many-layer caches would
    not fit replicated)."""
    b = rules.get("batch", "data")
    msize = mesh.shape.get("model", 1)
    with jax.set_mesh(mesh):  # prefill applies sharding constraints
        cache_abs = jax.eval_shape(
            lambda p, batch: model.prefill(p, batch)[1],
            model.abstract_params(), model.input_specs(shape))

    def shard(path, leaf):
        name = str(path[-1].key) if hasattr(path[-1], "key") else ""
        entries = [None] * len(leaf.shape)
        if len(leaf.shape) >= 2 and leaf.shape[1] == shape.global_batch:
            entries[1] = b
        if name in ("k", "v") and len(leaf.shape) == 5 \
                and leaf.shape[2] % msize == 0:
            entries[2] = "model"
        return NamedSharding(mesh, P(*entries))

    return jax.tree_util.tree_map_with_path(shard, cache_abs)


def batch_shardings(model: Model, shape: ShapeConfig, mesh: Mesh, rules):
    """Sharding tree matching input_specs(shape)."""
    b = rules.get("batch", "data")
    bsh = NamedSharding(mesh, P(b))
    tok = NamedSharding(mesh, P(b, None))
    emb = NamedSharding(mesh, P(b, None, None))
    cfg = model.cfg
    if shape.kind == "train":
        out = ({"embeds": emb} if cfg.frontend == "stub" else {"tokens": tok})
        out["labels"] = tok
        return out
    if shape.kind == "prefill":
        return {"embeds": emb} if cfg.frontend == "stub" else {"tokens": tok}
    if shape.kind == "decode":
        cache_abs = jax.eval_shape(
            lambda: model.init_cache(shape.global_batch, shape.seq_len))
        baxes = b if isinstance(b, tuple) else (b,)
        dsize = 1
        for a in baxes:
            dsize *= mesh.shape[a]
        msize = mesh.shape.get("model", 1)
        batch_ok = shape.global_batch % dsize == 0
        b_entry = b if batch_ok else None

        def cache_shard(path, leaf):
            name = str(path[-1].key) if hasattr(path[-1], "key") else ""
            ndim = len(leaf.shape)
            entries = [None] * ndim
            entries[0] = b_entry
            if name in ("k", "v"):
                # (B, S, KV, hd)
                if not batch_ok:
                    entries[1] = b          # sequence-sharded cache (SP)
                if leaf.shape[2] % msize == 0:
                    entries[2] = "model"
                elif (model.cfg.decode_cache_seq_shard
                        and leaf.shape[1] % msize == 0):
                    # MQA: kv unshardable -> ring-style sequence sharding
                    entries[1] = "model"
            elif name == "ssm":             # (B, di, N)
                if leaf.shape[1] % msize == 0:
                    entries[1] = "model"
            elif name == "conv":            # (B, K-1, di)
                if leaf.shape[2] % msize == 0:
                    entries[2] = "model"
            elif name == "s":               # rwkv (B, H, dh, dh)
                if leaf.shape[1] % msize == 0:
                    entries[1] = "model"
            return NamedSharding(mesh, P(*entries))

        tok_dec = NamedSharding(mesh, P(b_entry, None))
        return {"tokens": tok_dec,
                "pos": NamedSharding(mesh, P()),
                "cache": jax.tree_util.tree_map_with_path(
                    cache_shard, cache_abs)}
    raise ValueError(shape.kind)
