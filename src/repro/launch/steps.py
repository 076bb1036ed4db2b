"""shard_map step builders: train / prefill / decode.

This is where the paper's technique becomes a first-class runtime feature:
``make_train_step(..., sync)`` selects how the data-parallel gradient
synchronization is executed — any backend registered with the bucket-fused
collective engine (repro.collectives): XLA psum, a faithful ring
all-reduce, the OptINC quantize->integer-reduce->Q(mean) collective, or
the two-level carry-cascade over a (pod, data) mesh.

With FSDP, gradients of weight-sharded parameters are already
reduce-scattered over 'data' by the all-gather transpose; the remaining
explicit sync (and OptINC's target) is the cross-pod axis.  The
replicated and FSDP-sharded leaf groups are bucketed separately so each
group issues O(ceil(bytes / bucket_bytes)) collective launches per step.
With ``SyncConfig.overlap`` those launches stream in gradient-readiness
order (``grad_readiness``): a bucket's collective depends only on the
leaves it fuses, so the optical fabric starts reducing the deepest
layers' gradients while the shallower layers are still differentiating.

Error-feedback residuals are explicit step state: ``step`` takes and
returns a ``sync_state`` dict ({} when feedback is off, otherwise
device-local f32 residual vectors for the two leaf groups), so the
quantization error genuinely carries across steps.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from ..collectives import SyncConfig, residual_size, sync_gradients
from ..models import lm
from ..models.config import ModelConfig
from ..models.layers import ShardCtx
from ..optim import AdamWConfig, adamw_init, adamw_update, clip_by_global_norm


def make_ctx(mesh, fsdp: bool = False, seq_shard_cache: bool = False,
             seq_parallel: bool = False, remat_groups: int = 0) -> ShardCtx:
    """ShardCtx for an existing mesh — delegates to repro.api.MeshSpec,
    the single place ShardCtx derivation lives."""
    from ..api.spec import MeshSpec  # lazy: repro.api imports this module
    return MeshSpec.from_mesh(mesh, fsdp=fsdp, seq_parallel=seq_parallel,
                              remat_groups=remat_groups
                              ).ctx(seq_shard_cache=seq_shard_cache)


def batch_specs(ctx: ShardCtx, cfg: ModelConfig, batch_shardable: bool = True):
    dp = ctx.dp_axes if batch_shardable else None
    spec = {"tokens": P(dp, None)}
    if cfg.enc_dec:
        spec["enc_frames"] = P(dp, None, None)
    return spec


def _fsdp_leaf_tree(specs, ctx: ShardCtx):
    """True for every param leaf whose spec includes the data axis (its
    gradient is already reduce-scattered over 'data' by AD)."""
    def has_data(spec):
        return ctx.data_axis in [a for a in spec if a is not None]
    return jax.tree.map(has_data, specs,
                        is_leaf=lambda x: isinstance(x, P))


def _group_sync(group, sync: SyncConfig, key, residual, readiness=None):
    """Sync one leaf group through the bucketed engine, always returning a
    residual vector of stable shape when error feedback is on (exact
    backends yield no quantization error -> zeros)."""
    if not group:
        return [], (jnp.zeros((0,), jnp.float32) if sync.error_feedback
                    else None)
    synced, new_res = sync_gradients(group, sync, key, residual,
                                     readiness=readiness)
    if sync.error_feedback and new_res is None:
        new_res = jnp.zeros((residual_size(group),), jnp.float32)
    return synced, new_res


def grad_readiness(global_indices, n_leaves: int) -> tuple:
    """Per-leaf gradient emission ranks for a leaf group (lower = that
    gradient leaves the backward earlier).  Backward differentiates the
    network back to front, so the LAST leaf of the (forward-ordered)
    param tree is ready first: leaf i is ready at rank n_leaves - 1 - i.
    This is the readiness model the streaming engine's ``launch_order``
    consumes; ranks are computed from GLOBAL leaf indices so the two
    leaf groups of ``_split_sync`` schedule against the same backward."""
    return tuple(n_leaves - 1 - i for i in global_indices)


def _split_sync(grads, fsdp_mask, ctx, sync: SyncConfig, key, sync_state):
    """Sync replicated-leaf grads over the full DP axes; FSDP-sharded leaf
    grads only over the pod axis (and rescale the AD sum to a mean).

    Each group is fused into fixed-size buckets before the collective, so
    the launch count is O(buckets), not O(leaves).  With ``sync.overlap``
    each group's buckets dispatch in gradient-readiness order
    (``grad_readiness``) instead of behind a full-pytree barrier.
    Returns ``(synced_grads, new_sync_state)``; ``sync_state`` carries
    the two groups' error-feedback residual vectors ({} when feedback is
    off).
    """
    leaves, treedef = jax.tree.flatten(grads)
    masks = jax.tree.leaves(fsdp_mask)
    rep_axes = ctx.dp_axes
    pod_axes = (ctx.pod_axis,) if ctx.pods > 1 else ()
    ef = sync.error_feedback
    sync_state = sync_state or {}
    k_rep = k_fs = None
    if key is not None:
        k_rep, k_fs = jax.random.split(key)
    rep_idx = [i for i, m in enumerate(masks) if not m]
    fs_idx = [i for i, m in enumerate(masks) if m]
    # replicated leaves: the full sync over (pod,) + data axes
    synced_rep, rep_res = _group_sync(
        [leaves[i] for i in rep_idx],
        dataclasses.replace(sync, axes=rep_axes),
        k_rep, sync_state.get("rep") if ef else None,
        readiness=grad_readiness(rep_idx, len(leaves)))
    # fsdp leaves: AD already reduce-scattered (summed) over 'data' ->
    # rescale to a mean, then sync the remaining cross-pod level.  That
    # single level is exactly a one-level OptINC, so cascade mode (which
    # needs two axes) degrades to optinc here.
    fs = [leaves[i] / ctx.dp for i in fs_idx]
    if pod_axes and fs:
        pod_mode = "optinc" if sync.mode == "cascade" else sync.mode
        synced_fs, fs_res = _group_sync(
            fs, dataclasses.replace(sync, axes=pod_axes, mode=pod_mode),
            k_fs, sync_state.get("fsdp") if ef else None,
            readiness=grad_readiness(fs_idx, len(leaves)))
    else:
        synced_fs = fs
        fs_res = (jnp.zeros((residual_size(fs),), jnp.float32) if ef
                  else None)
    out = [None] * len(leaves)
    for i, g in zip(rep_idx, synced_rep):
        out[i] = g
    for i, g in zip(fs_idx, synced_fs):
        out[i] = g
    grads = jax.tree.unflatten(treedef, out)
    new_state = {"rep": rep_res, "fsdp": fs_res} if ef else {}
    return grads, new_state


def sync_state_specs(mesh, sync: SyncConfig):
    """PartitionSpec tree for the error-feedback sync_state: each device
    owns its own residual slice, so the vectors are sharded over EVERY
    mesh axis along dim 0 ({} when feedback is off)."""
    if not sync.error_feedback:
        return {}
    all_axes = tuple(mesh.axis_names)
    return {"rep": P(all_axes), "fsdp": P(all_axes)}


def _local_leaf_sizes(cfg: ModelConfig, ctx: ShardCtx, mesh):
    """(sizes, masks): per-leaf LOCAL (inside-shard_map) element counts and
    the fsdp mask, in flat_specs leaf order."""
    specs = lm.flat_specs(cfg, ctx)
    p_sds = lm.param_shape_dtype(cfg, ctx)
    mesh_sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    is_p = lambda x: isinstance(x, P)
    spec_leaves = jax.tree.leaves(specs, is_leaf=is_p)
    sds_leaves = jax.tree.leaves(p_sds)
    masks = jax.tree.leaves(_fsdp_leaf_tree(specs, ctx))
    sizes = []
    for sds, spec in zip(sds_leaves, spec_leaves):
        n = int(sds.size)
        for entry in spec:
            for ax in ((entry,) if not isinstance(entry, tuple) else entry):
                if ax is not None:
                    n //= mesh_sizes[ax]
        sizes.append(n)
    return sizes, masks


def init_sync_state(cfg: ModelConfig, mesh, sync: SyncConfig,
                    fsdp: bool = False, error_feedback: bool = False,
                    seq_parallel: bool = False, remat_groups: int = 0):
    """Zero-initialized global sync_state matching ``sync_state_specs``.

    Residuals are per-device local quantization error, so the global
    arrays are (n_devices * local_group_size,) f32 vectors.  They are
    checkpointed alongside params/opt (``CheckpointManager.save``'s
    ``sync_state`` with the ``sync_state_specs`` sharding), so a resumed
    run restores them bit-exactly.  ``error_feedback`` merges into
    ``sync`` exactly as in ``make_train_step`` so the two calls always
    agree on the state structure.
    """
    if not (sync.error_feedback or error_feedback):
        return {}
    ctx = make_ctx(mesh, fsdp=fsdp, seq_parallel=seq_parallel,
                   remat_groups=remat_groups)
    sizes, masks = _local_leaf_sizes(cfg, ctx, mesh)
    rep = sum(s for s, m in zip(sizes, masks) if not m)
    fs = sum(s for s, m in zip(sizes, masks) if m)
    ndev = int(mesh.devices.size)
    return {"rep": jnp.zeros((ndev * rep,), jnp.float32),
            "fsdp": jnp.zeros((ndev * fs,), jnp.float32)}


def make_train_step(cfg: ModelConfig, mesh, sync: SyncConfig,
                    opt: AdamWConfig, fsdp: bool = False,
                    error_feedback: bool = False,
                    seq_parallel: bool = False, remat_groups: int = 0):
    """Returns (step_fn, in_specs, out_specs). step_fn is shard_map'd but
    NOT jit'd (callers jit / lower it).

    step(params, opt_state, sync_state, batch, key) ->
        (params, opt_state, sync_state, metrics)
    where sync_state is {} unless error feedback is on (init_sync_state).
    """
    assert not (seq_parallel and cfg.enc_dec), "SP not wired for enc-dec"
    sync = dataclasses.replace(
        sync, error_feedback=sync.error_feedback or error_feedback)
    ctx = make_ctx(mesh, fsdp=fsdp, seq_parallel=seq_parallel,
                   remat_groups=remat_groups)
    specs = lm.flat_specs(cfg, ctx)
    fsdp_mask = _fsdp_leaf_tree(specs, ctx)
    bspec = batch_specs(ctx, cfg)
    sspec = sync_state_specs(mesh, sync)

    # named scopes put each device op down to a phase in a profile; the
    # backward's ops carry ``transpose(jvp(forward))`` in their op_name
    def step(params, opt_state, sync_state, batch, key):
        def lf(p):
            with jax.named_scope("forward"):
                return lm.loss_fn(cfg, ctx, p, batch)
        (loss, aux), grads = jax.value_and_grad(lf, has_aux=True)(params)
        with jax.named_scope("grad_sync"):
            grads, sync_state = _split_sync(grads, fsdp_mask, ctx, sync, key,
                                            sync_state)
        with jax.named_scope("optimizer"):
            grads, gnorm = clip_by_global_norm(
                grads, opt.clip_norm, axis_names=(ctx.model_axis,))
            params, opt_state = adamw_update(opt, params, grads, opt_state)
        metrics = {"loss": lax.pmean(loss, ctx.dp_axes),
                   "grad_norm": gnorm}
        return params, opt_state, sync_state, metrics

    in_specs = (specs, opt_specs(specs), sspec, bspec, P())
    out_specs = (specs, opt_specs(specs), sspec,
                 {"loss": P(), "grad_norm": P()})
    fn = jax.shard_map(step, mesh=mesh, in_specs=in_specs,
                       out_specs=out_specs, check_vma=False)
    return fn, in_specs, out_specs


def opt_specs(param_specs_tree):
    return {"m": param_specs_tree, "v": param_specs_tree, "step": P()}


def make_prefill_step(cfg: ModelConfig, mesh, fsdp: bool = False,
                      seq_parallel: bool = False, remat_groups: int = 0):
    assert not (seq_parallel and cfg.enc_dec), "SP not wired for enc-dec"
    ctx = make_ctx(mesh, fsdp=fsdp, seq_parallel=seq_parallel,
                   remat_groups=remat_groups)
    specs = lm.flat_specs(cfg, ctx)
    bspec = batch_specs(ctx, cfg)

    def step(params, batch):
        return lm.prefill_step(cfg, ctx, params, batch["tokens"],
                               batch.get("enc_frames"))

    cache_spec = cache_specs(cfg, ctx)
    out_specs = (P(ctx.dp_axes, "model"), cache_spec)
    fn = jax.shard_map(step, mesh=mesh, in_specs=(specs, bspec),
                       out_specs=out_specs, check_vma=False)
    return fn, (specs, bspec), out_specs


def make_batched_prefill_step(cfg: ModelConfig, mesh, fsdp: bool = False):
    """Serving prefill over a packed (b, t) prompt batch with per-row
    valid lengths (lm.batched_prefill_step) — rows shard over the DP
    axes, so dp > 1 serving meshes keep their data axis busy during
    prefill (the decode step stays replicated over 'data')."""
    ctx = make_ctx(mesh, fsdp=fsdp)
    specs = lm.flat_specs(cfg, ctx)

    def step(params, tokens, lengths):
        return lm.batched_prefill_step(cfg, ctx, params, tokens, lengths)

    cache_spec = cache_specs(cfg, ctx)
    in_specs = (specs, P(ctx.dp_axes, None), P(ctx.dp_axes))
    out_specs = (P(ctx.dp_axes, "model"), cache_spec)
    fn = jax.shard_map(step, mesh=mesh, in_specs=in_specs,
                       out_specs=out_specs, check_vma=False)
    return fn, in_specs, out_specs


def make_decode_step(cfg: ModelConfig, mesh, fsdp: bool = False,
                     seq_shard_cache: bool = False,
                     batch_shardable: bool = True):
    ctx = make_ctx(mesh, fsdp=fsdp, seq_shard_cache=seq_shard_cache)
    specs = lm.flat_specs(cfg, ctx)
    dp = ctx.dp_axes if batch_shardable else None

    def step(params, cache, token, pos):
        return lm.decode_step(cfg, ctx, params, cache, token, pos)

    cache_spec = cache_specs(cfg, ctx, batch_shardable=batch_shardable)
    in_specs = (specs, cache_spec, P(dp, None), P())
    out_specs = (P(dp, "model"), cache_spec)
    fn = jax.shard_map(step, mesh=mesh, in_specs=in_specs,
                       out_specs=out_specs, check_vma=False)
    return fn, in_specs, out_specs


def cache_specs(cfg: ModelConfig, ctx: ShardCtx, batch_shardable: bool = True):
    """PartitionSpec tree matching lm.init_cache's structure: batch over the
    DP axes (when shardable), heads over 'model', optionally cache sequence
    over 'data' (flash-decode sequence sharding)."""
    dp = ctx.dp_axes if batch_shardable else None
    seq_ax = ctx.data_axis if ctx.seq_shard_cache else None

    def kv():
        return {"k": P(None, dp, ctx.model_axis, seq_ax, None),
                "v": P(None, dp, ctx.model_axis, seq_ax, None)}

    if cfg.ssm == "mamba2":
        out = {"mamba": {
            "ssm": P(None, dp, ctx.model_axis, None, None),
            "conv_x": P(None, dp, None, ctx.model_axis),
            "conv_bc": P(None, dp, None, None)}}
        if cfg.attn_every:
            out["attn"] = kv()
        return out
    if cfg.ssm == "xlstm":
        st = P(None, dp, ctx.model_axis, None)
        out = {"mlstm": {"c": P(None, dp, ctx.model_axis, None, None),
                         "n": st}}
        if cfg.slstm_every:
            out["slstm"] = {"h": st, "c": st, "n": st, "m": st}
        return out
    if cfg.enc_dec:
        return {"self": kv(), "cross": kv()}
    if cfg.moe and cfg.mla:
        def mla():
            return {"ckv": P(None, dp, seq_ax, None),
                    "scale": P(None, dp, seq_ax, None),
                    "krope": P(None, dp, seq_ax, None)}
        out = {"moe": mla()}
        if cfg.first_dense_layers:
            out["dense"] = mla()
        return out
    if cfg.moe:
        out = {"moe": kv()}
        if cfg.first_dense_layers:
            out["dense"] = kv()
        return out
    return {"layers": kv()}
