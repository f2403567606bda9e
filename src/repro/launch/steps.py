"""Step builders: train / prefill / decode, with full sharding annotations.

``build_*`` return (jitted_fn, in_shardings, arg_specs) so both the real
launcher (train.py / serve.py) and the dry-run (dryrun.py) use the SAME
partitioned programs — the dry-run lowers exactly what production runs.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.models import api
from repro.models.config import ArchConfig
from repro.models.sharding_hooks import use_sharder
from repro.launch import sharding as shd
from repro.optim import adamw_init, adamw_update
from repro.optim.adafactor import adafactor_init, adafactor_update

Array = jax.Array


def _reshape_microbatches(batch, accum: int):
    def one(x):
        if x.ndim == 0:
            return x
        b = x.shape[0]
        assert b % accum == 0, (b, accum)
        return x.reshape((accum, b // accum) + x.shape[1:])
    return jax.tree.map(one, batch)


def make_train_step(cfg: ArchConfig, mesh, *, accum: int = 1,
                    lr: float = 3e-4, remat: bool = True):
    """Returns train_step(params, opt_state, batch, step)->(params, opt, metrics).

    Gradient accumulation via lax.scan over ``accum`` microbatches; optimizer
    per cfg.optimizer (adamw | adafactor).
    """
    sharder = shd.make_activation_sharder(mesh, cfg)
    use_adafactor = cfg.optimizer == "adafactor"

    def train_step(params, opt_state, batch, step):
        with use_sharder(sharder):
            mb = _reshape_microbatches(batch, accum)

            def micro(carry, b1):
                g_acc, l_acc = carry
                (loss, _), grads = jax.value_and_grad(
                    lambda p: api.loss_fn(p, b1, cfg, remat=remat),
                    has_aux=True)(params)
                g_acc = jax.tree.map(
                    lambda a, g: a + g.astype(jnp.float32), g_acc, grads)
                return (g_acc, l_acc + loss), None

            g0 = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params)
            (grads, loss_sum), _ = jax.lax.scan(
                micro, (g0, jnp.zeros((), jnp.float32)), mb)
            grads = jax.tree.map(lambda g: g / accum, grads)
            loss = loss_sum / accum

            if use_adafactor:
                new_params, new_opt, om = adafactor_update(
                    grads, opt_state, params, lr=lr)
                om = dict(om)
            else:
                new_params, new_opt, om = adamw_update(
                    grads, opt_state, params, lr=lr)
            metrics = {"loss": loss, **om, "step": step + 1}
        return new_params, new_opt, metrics

    return train_step


def init_opt(cfg: ArchConfig, params):
    return (adafactor_init(params) if cfg.optimizer == "adafactor"
            else adamw_init(params))


def opt_specs(cfg: ArchConfig, params_spec):
    return jax.eval_shape(lambda p: init_opt(cfg, p), params_spec)


def make_prefill_step(cfg: ArchConfig, mesh):
    sharder = shd.make_activation_sharder(mesh, cfg)

    def prefill_step(params, batch):
        with use_sharder(sharder):
            return api.prefill_logits(params, batch, cfg)

    return prefill_step


def make_decode_step(cfg: ArchConfig, mesh):
    sharder = shd.make_activation_sharder(mesh, cfg)

    def serve_step(params, cache, token, pos):
        with use_sharder(sharder):
            return api.decode_step(params, cache, token, pos, cfg)

    return serve_step


# -------------------------------------------------------------- lowering ---

def lower_train(cfg: ArchConfig, shape: api.ShapeSpec, mesh, *,
                accum: int | None = None, lr: float = 3e-4,
                donate: bool = True):
    """Lower the production train_step for (cfg x shape) on ``mesh``."""
    dp = 1
    for a in shd._fsdp_axes(mesh):
        dp *= mesh.shape[a]
    accum = accum or max(1, shape.global_batch // dp)
    params_spec = api.param_specs(cfg)
    opt_spec = opt_specs(cfg, params_spec)
    batch_spec = api.input_specs(cfg, shape)

    p_sh = shd.param_shardings(params_spec, mesh, cfg)
    o_sh = shd.opt_shardings(opt_spec, params_spec, mesh, cfg)
    b_sh = shd.batch_shardings(batch_spec, mesh)
    s_sh = NamedSharding(mesh, P())

    step_fn = make_train_step(cfg, mesh, accum=accum, lr=lr)
    jitted = jax.jit(
        step_fn,
        in_shardings=(p_sh, o_sh, b_sh, s_sh),
        out_shardings=(p_sh, o_sh, None),
        donate_argnums=(0, 1) if donate else (),
    )
    args = (params_spec, opt_spec, batch_spec,
            jax.ShapeDtypeStruct((), jnp.int32))
    with mesh:
        lowered = jitted.lower(*args)
    return lowered, {"accum": accum}


def lower_prefill(cfg: ArchConfig, shape: api.ShapeSpec, mesh):
    params_spec = api.param_specs(cfg)
    batch_spec = api.input_specs(cfg, shape)
    p_sh = shd.param_shardings(params_spec, mesh, cfg)
    b_sh = shd.batch_shardings(batch_spec, mesh)
    jitted = jax.jit(make_prefill_step(cfg, mesh),
                     in_shardings=(p_sh, b_sh))
    with mesh:
        lowered = jitted.lower(params_spec, batch_spec)
    return lowered, {}


def lower_decode(cfg: ArchConfig, shape: api.ShapeSpec, mesh):
    params_spec = api.param_specs(cfg)
    cache_spec = api.cache_specs(cfg, shape.global_batch, shape.seq_len)
    tok_spec = jax.ShapeDtypeStruct((shape.global_batch, 1), jnp.int32)
    pos_spec = jax.ShapeDtypeStruct((), jnp.int32)

    p_sh = shd.param_shardings(params_spec, mesh, cfg)
    c_sh = shd.cache_shardings(cache_spec, mesh, cfg)
    t_sh = NamedSharding(
        mesh, P("data" if shape.global_batch % mesh.shape["data"] == 0
                else None, None))
    jitted = jax.jit(
        make_decode_step(cfg, mesh),
        in_shardings=(p_sh, c_sh, t_sh, NamedSharding(mesh, P())),
        out_shardings=(None, c_sh),
        donate_argnums=(1,),
    )
    with mesh:
        lowered = jitted.lower(params_spec, cache_spec, tok_spec, pos_spec)
    return lowered, {}


def lower_cell(cfg: ArchConfig, shape: api.ShapeSpec, mesh, **kw):
    if shape.kind == "train":
        return lower_train(cfg, shape, mesh, **kw)
    if shape.kind == "prefill":
        return lower_prefill(cfg, shape, mesh)
    return lower_decode(cfg, shape, mesh)


# ===========================================================================
# OPTIMIZED variant (EXPERIMENTS.md §Perf): ZeRO-1 deferred grad reduction
# (one bf16 reduce-scatter per step instead of `accum` f32 all-reduces),
# per-step weight gather (instead of per-microstep FSDP gathers), 2D-resident
# expert weights, and sequence-parallel attention for narrow-head archs.
# ===========================================================================

def _is_expert_leaf(path_str: str) -> bool:
    import re
    return bool(re.search(r"moe.*w_(in|gate|out)$", path_str))


def _moe_2d_active(cfg, mesh) -> bool:
    """D-over-data resident experts pay an h/g psum O(C*F) and an out a2a
    O(T*D); worth it only when the expert hidden F is small relative to
    d_model (kimi: F=2048 << D=7168).  For wide experts (jamba/mixtral
    F=14336) TP-inside-the-expert moves O(C*D) instead — cheaper."""
    import numpy as _np
    dp = int(_np.prod([mesh.shape[a] for a in shd._fsdp_axes(mesh)]))
    return bool(cfg.num_experts) and cfg.num_experts % mesh.shape["model"] \
        == 0 and cfg.d_model % dp == 0 and \
        (cfg.moe_d_ff or cfg.d_ff) <= cfg.d_model


def master_shardings_opt(params_spec, mesh, cfg):
    """Masters/opt-state: baseline FSDP+TP for non-experts (ZeRO-1 keeps
    optimizer state sharded over data), 2D-resident layout for experts."""
    moe_2d = _moe_2d_active(cfg, mesh)
    flat, treedef = jax.tree_util.tree_flatten_with_path(params_spec)
    out = []
    for path, leaf in flat:
        ps = shd._path_str(path)
        if _is_expert_leaf(ps) and moe_2d:
            # 2D-resident experts: the master IS the compute layout
            spec = shd.param_spec_for_opt(ps, leaf.shape, mesh, cfg)
        else:
            spec = shd.param_spec_for(ps, leaf.shape, mesh, cfg)
        out.append(jax.sharding.NamedSharding(mesh, spec))
    return jax.tree_util.tree_unflatten(treedef, out)


def _grad_reduce_plan(params_spec, mesh, cfg):
    """Per-leaf plan: ('local', None) experts — complete local grads;
    ('scatter', dim) — psum_scatter along the master's fsdp dim;
    ('psum', None) — small replicated leaves."""
    fsdp = set(shd._fsdp_axes(mesh))
    moe_2d = _moe_2d_active(cfg, mesh)
    flat, treedef = jax.tree_util.tree_flatten_with_path(params_spec)
    plans = []
    for path, leaf in flat:
        ps = shd._path_str(path)
        if _is_expert_leaf(ps) and moe_2d:
            plans.append(("local", None))
            continue
        spec = shd.param_spec_for(ps, leaf.shape, mesh, cfg)
        dim = None
        for i, part in enumerate(tuple(spec)):
            parts = part if isinstance(part, (tuple, list)) else (part,)
            if any(a in fsdp for a in parts if a):
                dim = i
                break
        plans.append(("scatter", dim) if dim is not None else ("psum", None))
    return jax.tree_util.tree_unflatten(treedef, plans)


def make_train_step_opt(cfg: ArchConfig, mesh, *, accum: int = 1,
                        lr: float = 3e-4, remat: bool = True,
                        grad_dtype=jnp.bfloat16):
    dp_axes = shd._fsdp_axes(mesh)
    params_spec = api.param_specs(cfg)
    compute_sh = shd.param_shardings_opt(params_spec, mesh, cfg)
    manual_p_specs = shd.manual_in_specs(params_spec, mesh, cfg)
    plan = _grad_reduce_plan(params_spec, mesh, cfg)
    sharder_in = shd.make_activation_sharder_opt(mesh, cfg)
    use_adafactor = cfg.optimizer == "adafactor"

    is_plan = lambda x: isinstance(x, tuple) and len(x) == 2 and \
        isinstance(x[0], str)

    def grad_out_specs(batch_spec):
        def one(pspec, pl):
            kind, dim = pl
            if kind == "local":
                return pspec  # expert grads stay data-sharded (complete)
            if kind == "scatter":
                parts = [None] * dim + [dp_axes]
                return jax.sharding.PartitionSpec(*parts)
            return jax.sharding.PartitionSpec()
        return jax.tree.map(one, manual_p_specs, plan, is_leaf=None)

    def train_step(master, opt_state, batch, step):
        # per-step gather: bf16 compute params in the TP-resident layout
        params_c = jax.tree.map(
            lambda p, s: jax.lax.with_sharding_constraint(
                p.astype(cfg.cdtype), s),
            master, compute_sh)
        mb = _reshape_microbatches(batch, accum)

        def local(params_c, mb):
            with use_sharder(sharder_in):
                def micro(carry, b1):
                    g_acc, l_acc = carry
                    (loss, _), grads = jax.value_and_grad(
                        lambda p: api.loss_fn(p, b1, cfg, remat=remat),
                        has_aux=True)(params_c)
                    g_acc = jax.tree.map(
                        lambda a, g: a + g.astype(grad_dtype), g_acc, grads)
                    return (g_acc, l_acc + loss), None

                g0 = jax.tree.map(
                    lambda p: jnp.zeros(p.shape, grad_dtype), params_c)
                (grads, loss_sum), _ = jax.lax.scan(
                    micro, (g0, jnp.zeros((), jnp.float32)), mb)

            # deferred reduction: ONE bf16 collective per leaf per step
            # (gradient compression).  NOTE: compiling this on the CPU
            # backend requires --xla_disable_hlo_passes=all-reduce-promotion
            # (an XLA CPU bug: the pass crashes cloning a bf16 all-reduce
            # whose user is a `copy`; float-normalization-bf16 legalizes the
            # op anyway).  TPU reduces bf16 natively — no flag needed.
            def reduce_leaf(g, pl):
                kind, dim = pl
                if kind == "local":
                    return g
                g = g.astype(grad_dtype)
                if kind == "scatter":
                    return jax.lax.psum_scatter(
                        g, dp_axes, scatter_dimension=dim, tiled=True)
                return jax.lax.psum(g, dp_axes)

            grads = jax.tree.map(reduce_leaf, grads, plan)
            loss = jax.lax.psum(loss_sum, dp_axes)
            return grads, loss

        n_dp = 1
        for a in dp_axes:
            n_dp *= mesh.shape[a]

        batch_manual = jax.tree.map(
            lambda x: jax.sharding.PartitionSpec(None, dp_axes)
            if hasattr(x, "ndim") and x.ndim >= 2
            else jax.sharding.PartitionSpec(), mb)
        # pad specs to full rank
        def bspec(x):
            if x.ndim == 0:
                return jax.sharding.PartitionSpec()
            return jax.sharding.PartitionSpec(
                None, dp_axes, *([None] * (x.ndim - 2)))
        batch_manual = jax.tree.map(bspec, mb)

        grads, loss_sum = jax.shard_map(
            local, mesh=mesh,
            in_specs=(manual_p_specs, batch_manual),
            out_specs=(grad_out_specs(mb), jax.sharding.PartitionSpec()),
            axis_names=set(dp_axes), check_vma=False,
        )(params_c, mb)

        grads = jax.tree.map(lambda g: g.astype(jnp.float32) / accum, grads)
        loss = loss_sum / (accum * n_dp)
        if use_adafactor:
            new_master, new_opt, _ = adafactor_update(
                grads, opt_state, master, lr=lr)
        else:
            new_master, new_opt, _ = adamw_update(
                grads, opt_state, master, lr=lr)
        return new_master, new_opt, {"loss": loss, "step": step + 1}

    return train_step


def lower_train_opt(cfg: ArchConfig, shape: api.ShapeSpec, mesh, *,
                    accum: int | None = None, lr: float = 3e-4):
    dp = 1
    for a in shd._fsdp_axes(mesh):
        dp *= mesh.shape[a]
    accum = accum or max(1, shape.global_batch // dp)
    params_spec = api.param_specs(cfg)
    opt_spec = opt_specs(cfg, params_spec)
    batch_spec = api.input_specs(cfg, shape)

    m_sh = master_shardings_opt(params_spec, mesh, cfg)
    # optimizer state follows the master layout leaf-by-leaf
    flat_m, _ = jax.tree_util.tree_flatten(m_sh)

    def opt_sh_fn(opt_spec):
        p_flat, _ = jax.tree_util.tree_flatten_with_path(params_spec)
        by_suffix = {shd._path_str(p): (l.shape, s.spec)
                     for (p, l), s in zip(p_flat, flat_m)}

        def spec_of(path, leaf):
            ps = shd._path_str(path)
            for key, (shape_, spec_) in by_suffix.items():
                if ps.endswith(key):
                    if leaf.shape == shape_:
                        return spec_
                    specs = list(tuple(spec_)) + [None] * (
                        len(shape_) - len(tuple(spec_)))
                    if leaf.shape == shape_[:-1]:
                        return jax.sharding.PartitionSpec(*specs[:-1])
                    if leaf.shape == shape_[:-2] + shape_[-1:]:
                        return jax.sharding.PartitionSpec(
                            *(specs[:-2] + specs[-1:]))
                    return jax.sharding.PartitionSpec()
            return jax.sharding.PartitionSpec()

        flat, treedef = jax.tree_util.tree_flatten_with_path(opt_spec)
        return jax.tree_util.tree_unflatten(
            treedef, [jax.sharding.NamedSharding(mesh, spec_of(p, l))
                      for p, l in flat])

    o_sh = opt_sh_fn(opt_spec)
    b_sh = shd.batch_shardings(batch_spec, mesh)
    s_sh = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())

    step_fn = make_train_step_opt(cfg, mesh, accum=accum, lr=lr)
    jitted = jax.jit(step_fn,
                     in_shardings=(m_sh, o_sh, b_sh, s_sh),
                     out_shardings=(m_sh, o_sh, None),
                     donate_argnums=(0, 1))
    args = (params_spec, opt_spec, batch_spec,
            jax.ShapeDtypeStruct((), jnp.int32))
    with mesh:
        lowered = jitted.lower(*args)
    return lowered, {"accum": accum, "variant": "optimized"}


def serve_shardings_opt(params_spec, mesh, cfg):
    """Serve-time layout: TP-resident non-expert weights (no per-layer FSDP
    gathers), expert weights keep the baseline (model, fsdp) layout — the 2D
    train layout requires the manual-mode MoE hooks, which only exist inside
    the train shard_map (measured: applying it to prefill emitted
    catastrophic per-layer collectives, MFU 0.049 -> 0.011 — refuted)."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(params_spec)
    out = []
    for path, leaf in flat:
        ps = shd._path_str(path)
        if _is_expert_leaf(ps):
            spec = shd.param_spec_for(ps, leaf.shape, mesh, cfg)
        else:
            spec = shd.param_spec_for_opt(ps, leaf.shape, mesh, cfg)
        out.append(jax.sharding.NamedSharding(mesh, spec))
    return jax.tree_util.tree_unflatten(treedef, out)


def lower_cell_opt(cfg: ArchConfig, shape: api.ShapeSpec, mesh, **kw):
    if shape.kind == "train":
        return lower_train_opt(cfg, shape, mesh, **kw)
    # prefill/decode: weight-resident layout (no FSDP gathers at serve time)
    if shape.kind == "prefill":
        params_spec = api.param_specs(cfg)
        batch_spec = api.input_specs(cfg, shape)
        p_sh = serve_shardings_opt(params_spec, mesh, cfg)
        b_sh = shd.batch_shardings(batch_spec, mesh)
        jitted = jax.jit(make_prefill_step(cfg, mesh),
                         in_shardings=(p_sh, b_sh))
        with mesh:
            lowered = jitted.lower(params_spec, batch_spec)
        return lowered, {"variant": "optimized"}
    params_spec = api.param_specs(cfg)
    cache_spec = api.cache_specs(cfg, shape.global_batch, shape.seq_len)
    tok_spec = jax.ShapeDtypeStruct((shape.global_batch, 1), jnp.int32)
    p_sh = serve_shardings_opt(params_spec, mesh, cfg)
    c_sh = shd.cache_shardings(cache_spec, mesh, cfg)
    t_sh = jax.sharding.NamedSharding(
        mesh, jax.sharding.PartitionSpec(
            "data" if shape.global_batch % mesh.shape["data"] == 0 else None,
            None))
    jitted = jax.jit(make_decode_step(cfg, mesh),
                     in_shardings=(p_sh, c_sh, t_sh,
                                   jax.sharding.NamedSharding(
                                       mesh, jax.sharding.PartitionSpec())),
                     out_shardings=(None, c_sh), donate_argnums=(1,))
    with mesh:
        lowered = jitted.lower(params_spec, cache_spec, tok_spec,
                               jax.ShapeDtypeStruct((), jnp.int32))
    return lowered, {"variant": "optimized"}
