"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch llama3_405b \
      --shape train_4k [--multi-pod] [--sync optinc|ring|psum|cascade] \
      [--fsdp auto|on|off] [--out results/dryrun]

Each invocation compiles ONE cell in a fresh process (512 host devices) and
writes a JSON record with memory_analysis, cost_analysis, and the parsed
collective table for the roofline (§Roofline in EXPERIMENTS.md).

The cells are lowered through ``repro.api``: a RunSpec describes the
scenario and ``repro.api.build`` constructs exactly the shard_map programs
``TrainSession`` / ``ServeSession`` run, so the dry-run measures the same
code path serving and training execute.
"""
# XLA_FLAGS must be in the environment before jax initializes its backend;
# keep this mutation ahead of every jax (or repro) import.
import os

os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=512").strip()

import argparse
import json
import pathlib
import time

import jax
import jax.numpy as jnp

from repro import configs
from repro.api import MeshSpec, RunSpec, SpecError, SyncConfig, build
from repro.api.shapes import (batch_sds, cache_sds, globalize_cache_sds,
                              opt_sds, sds)
from repro.collectives import available_backends
from repro.launch import roofline
from repro.models.config import ModelConfig
from repro.optim import AdamWConfig

# archs small enough to keep parameters replicated across the data axis
NO_FSDP = {"xlstm-125m", "whisper-tiny", "paper-llama"}


def cell_spec(arch: str, multi_pod: bool, sync_mode: str,
              fsdp_opt: str = "auto", moment_dtype: str = "bfloat16",
              seq_parallel: bool = False, remat_groups: int = 0,
              bucket_bytes: int = 4 * 2 ** 20, seq_len: int = 512,
              global_batch: int = 32) -> RunSpec:
    """The production-mesh RunSpec for one dry-run cell."""
    from repro.api import DataConfig
    cfg = configs.get(arch)
    fsdp = (cfg.name not in NO_FSDP) if fsdp_opt == "auto" else fsdp_opt == "on"
    mesh = MeshSpec(pods=2 if multi_pod else 1, dp=16, tp=16, fsdp=fsdp,
                    seq_parallel=seq_parallel, remat_groups=remat_groups)
    return RunSpec(arch=arch, mesh=mesh,
                   sync=SyncConfig(mode=sync_mode, bucket_bytes=bucket_bytes),
                   optim=AdamWConfig(moment_dtype=moment_dtype),
                   data=DataConfig(vocab=0, seq_len=seq_len,
                                   global_batch=global_batch, seed=0))


def lower_cell(arch: str, shape_name: str, multi_pod: bool, sync_mode: str,
               fsdp_opt: str = "auto", moment_dtype: str = "bfloat16",
               seq_shard_long: bool = True, seq_parallel: bool = False,
               remat_groups: int = 0, bucket_bytes: int = 4 * 2 ** 20):
    from repro.models import lm
    cfg = configs.get(arch)
    cell = configs.cells(arch)[shape_name]
    if "skip" in cell:
        return {"arch": arch, "shape": shape_name,
                "mesh": "2x16x16" if multi_pod else "16x16",
                "skipped": cell["skip"]}
    spec = cell_spec(arch, multi_pod, sync_mode, fsdp_opt, moment_dtype,
                     seq_parallel, remat_groups, bucket_bytes,
                     seq_len=cell["seq_len"], global_batch=cell["global_batch"])
    mesh = spec.mesh.build()
    dp_total = spec.mesh.pods * spec.mesh.dp
    kind = cell["kind"]
    t0 = time.time()

    if kind == "train":
        spec.validate()
        step, _, _ = build.build_train_step(spec, cfg, mesh)
        ctx = spec.mesh.ctx()
        p_sds = lm.param_shape_dtype(cfg, ctx)
        mdt = jnp.bfloat16 if moment_dtype == "bfloat16" else jnp.float32
        args = (p_sds, opt_sds(p_sds, mdt), {},
                batch_sds(cfg, cell["seq_len"], cell["global_batch"]),
                jax.eval_shape(lambda: jax.random.PRNGKey(0)))
    elif kind == "prefill":
        step, _, _ = build.build_prefill_step(spec, cfg, mesh)
        ctx = spec.mesh.ctx()
        p_sds = lm.param_shape_dtype(cfg, ctx)
        args = (p_sds, batch_sds(cfg, cell["seq_len"], cell["global_batch"]))
    else:  # decode
        gb = cell["global_batch"]
        shardable = gb >= dp_total
        seq_shard = (not shardable) and seq_shard_long
        step, _, _ = build.build_decode_step(spec, cfg, mesh,
                                             seq_shard_cache=seq_shard,
                                             batch_shardable=shardable)
        ctx = spec.mesh.ctx(seq_shard_cache=seq_shard)
        p_sds = lm.param_shape_dtype(cfg, ctx)
        b_local = gb // dp_total if shardable else gb
        c_sds = cache_sds(cfg, ctx, b_local, cell["seq_len"])
        cspec = build.decode_cache_specs(spec, cfg, seq_shard_cache=seq_shard,
                                         batch_shardable=shardable)
        c_sds = globalize_cache_sds(c_sds, cspec, mesh)
        args = (p_sds, c_sds, sds((gb, 1), jnp.int32), sds((), jnp.int32))

    # donate params/opt (train) or cache (decode) so memory_analysis
    # reflects in-place updates, as a real training loop would run
    donate = (0, 1) if kind in ("train",) else ((1,) if kind == "decode" else ())
    with jax.set_mesh(mesh):
        lowered = jax.jit(step, donate_argnums=donate).lower(*args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    hlo = compiled.as_text()
    colls = roofline.parse_collectives(hlo)
    chips = mesh.devices.size
    # cost_analysis / memory_analysis report the (single) SPMD per-device
    # program — validated against an analytic matmul; use raw values
    flops = float(cost.get("flops", 0.0))
    bytes_acc = float(cost.get("bytes accessed", 0.0))
    coll_bytes = roofline.collective_wire_bytes(colls)
    terms = roofline.roofline_terms(flops, bytes_acc, coll_bytes, chips)

    rec = {
        "arch": arch, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "kind": kind, "sync": sync_mode if kind == "train" else None,
        "fsdp": spec.mesh.fsdp, "seq_parallel": seq_parallel,
        "remat_groups": remat_groups, "chips": chips,
        "run_spec": spec.to_json_dict(),
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
        "raw_stats": True,
        "memory": {  # per-device
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
            "peak_bytes": (mem.argument_size_in_bytes
                           + mem.temp_size_in_bytes
                           + mem.output_size_in_bytes
                           - mem.alias_size_in_bytes),
        },
        "flops_per_device": flops,
        "bytes_per_device": bytes_acc,
        "collectives": colls,
        "collective_wire_bytes": coll_bytes,
        "roofline": terms,
    }
    return rec


def main():
    ap = argparse.ArgumentParser(
        description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", required=True, choices=list(configs.SHAPES))
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--sync", default="optinc",
                    choices=list(available_backends()))
    ap.add_argument("--bucket-mb", type=float, default=4.0)
    ap.add_argument("--fsdp", default="auto", choices=["auto", "on", "off"])
    ap.add_argument("--moment-dtype", default="bfloat16")
    ap.add_argument("--seq-parallel", action="store_true")
    ap.add_argument("--remat-groups", type=int, default=0)
    ap.add_argument("--tag", default="")
    ap.add_argument("--out", default="results/dryrun")
    args = ap.parse_args()

    try:
        rec = lower_cell(args.arch, args.shape, args.multi_pod, args.sync,
                         args.fsdp, args.moment_dtype,
                         seq_parallel=args.seq_parallel,
                         remat_groups=args.remat_groups,
                         bucket_bytes=int(args.bucket_mb * 2 ** 20))
    except SpecError as e:
        raise SystemExit(f"error: {e}")
    out = pathlib.Path(args.out)
    out.mkdir(parents=True, exist_ok=True)
    tag = (f"{args.arch}.{args.shape}."
           f"{'2x16x16' if args.multi_pod else '16x16'}.{args.sync}"
           f"{'' if args.fsdp == 'auto' else '.' + args.fsdp}"
           f"{'' if args.moment_dtype == 'bfloat16' else '.f32mom'}"
           f"{'.sp' if args.seq_parallel else ''}"
           f"{('.rg' + str(args.remat_groups)) if args.remat_groups else ''}"
           f"{('.' + args.tag) if args.tag else ''}")
    path = out / f"{tag}.json"
    path.write_text(json.dumps(rec, indent=1))
    if rec.get("skipped"):
        print(f"SKIP {tag}: {rec['skipped']}")
    else:
        r = rec["roofline"]
        print(f"OK {tag}: compile={rec['compile_s']}s "
              f"peak={rec['memory']['peak_bytes']/2**30:.2f}GiB "
              f"compute={r['compute_s']*1e3:.2f}ms "
              f"memory={r['memory_s']*1e3:.2f}ms "
              f"coll={r['collective_s']*1e3:.2f}ms dom={r['dominant']}")


if __name__ == "__main__":
    main()
