"""TrainSession: build -> init-or-resume -> jitted step loop.

Owns everything the old ``launch/train.py`` wired by hand: mesh/ShardCtx
derivation (via MeshSpec), parameter/optimizer/sync-state initialization,
checkpoint resume with RunSpec compatibility validation, the jitted
shard_map step, and a callback stack for logging / checkpointing /
signal handling / straggler detection.

Checkpoints persist the full step state — params, optimizer moments, AND
the error-feedback ``sync_state`` residuals (with their sharding specs) —
plus the RunSpec itself in the manifest, so ``--resume`` restores a run
bit-exactly and refuses specs whose state structure doesn't match.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
from jax.profiler import TraceAnnotation

from ..checkpoint import CheckpointManager, load_checkpoint
from ..checkpoint.ckpt import latest_step, read_manifest
from ..collectives import (is_packed_residuals, pack_residuals,
                           unpack_residuals)
from ..data import SyntheticLM
from ..models import lm
from ..optim import adamw_init
from . import build
from .callbacks import default_callbacks
from .spec import RunSpec, validate_resume_compat


class TrainSession:
    """One training run of one RunSpec.

    >>> spec = RunSpec(arch="minitron_4b", smoke=True, steps=3)
    >>> session = TrainSession(spec)
    >>> history = session.run()          # list of per-step record dicts
    """

    def __init__(self, spec: RunSpec, callbacks: list | None = None):
        spec.validate()
        self.spec = spec
        self.cfg = spec.model_config()
        self.mesh = spec.mesh.build()
        self.ctx = spec.mesh.ctx()
        self.sync = spec.resolved_sync()
        self.callbacks = (list(callbacks) if callbacks is not None
                          else default_callbacks(spec))
        self.mgr = (CheckpointManager(spec.ckpt.dir, keep=spec.ckpt.keep)
                    if spec.ckpt.dir else None)
        self.data = SyntheticLM(spec.resolved_data())
        self.stop_requested = False
        self.step = 0              # next step to execute
        self.last_record = None

        self.params = lm.init_params(self.cfg, self.ctx,
                                     jax.random.PRNGKey(spec.seed))
        self.opt_state = adamw_init(spec.optim, self.params)
        self.sync_state = build.init_sync_state(spec, self.cfg, self.mesh)
        if spec.ckpt.resume:
            self._maybe_resume()

        build.warmup_photonics(spec)   # onn/mesh fidelity: resolve eagerly
        step_fn, _, _ = build.build_train_step(spec, self.cfg, self.mesh)
        self._jitted = jax.jit(step_fn, donate_argnums=(0, 1, 2))
        # per-step keys are folded from a base key, NOT split sequentially,
        # so a resumed step sees exactly the key the uninterrupted run saw
        self._base_key = jax.random.PRNGKey(spec.seed + 1)

    # ------------------------------------------------------------ control
    def request_stop(self):
        """End the loop after the current step (checkpoint included)."""
        self.stop_requested = True

    def save_checkpoint(self, step: int | None = None):
        """Persist params + optimizer + sync_state + the RunSpec manifest.
        With ``sync.sparse_residuals`` the error-feedback residuals are
        stored block-sparsely (only blocks with nonzero carry)."""
        if self.mgr is None:
            return
        step = (self.step - 1) if step is None else step
        sync_state = self.sync_state
        if self.sync.sparse_residuals and sync_state:
            sync_state = pack_residuals(sync_state)
        self.mgr.save(step, self.params, self.opt_state,
                      sync_state=sync_state,
                      extra={"run_spec": self.spec.to_json_dict(),
                             "arch": self.cfg.name, "sync": self.sync.mode})
        for cb in self.callbacks:
            cb.on_checkpoint(self, step)

    def _maybe_resume(self):
        c = self.spec.ckpt
        s = latest_step(c.dir)
        if s is None:
            return
        man = read_manifest(c.dir, s)
        saved_spec = (man.get("extra") or {}).get("run_spec")
        resharded, saved = False, None
        if saved_spec is not None:
            saved = RunSpec.from_json_dict(saved_spec)
            allow = (self.spec.elastic.allow_reshard
                     or self.spec.elastic.enabled)
            compat = validate_resume_compat(saved, self.spec,
                                            allow_reshard=allow)
            resharded = compat.verdict == "reshardable"
        p_specs, o_specs = build.param_specs(self.spec, self.cfg)
        template = {"params": self.params, "opt": self.opt_state}
        specs = {"params": p_specs, "opt": o_specs}
        sync_paths = [p for p in man["leaves"]
                      if p.split("/", 1)[0] == "sync"]
        # block-sparse residual checkpoints store sync/<name>/{idx,val,
        # shape}; either form restores regardless of the current
        # sparse_residuals flag
        sync_packed = bool(sync_paths) and all(
            p.rsplit("/", 1)[-1] in ("idx", "val", "shape")
            for p in sync_paths)
        # error-feedback residual buckets are sized by device count, so a
        # resharded resume may find them re-bucketized: restore any leaf
        # whose saved shape still matches, re-zero the rest (the carry
        # they held was an intra-step numerical refinement, not model
        # state — EXPERIMENTS.md §Elastic training)
        sync_shapes_ok = self.sync_state and sync_paths and all(
            list((man["leaves"].get(f"sync/{name}") or {}).get("shape", ()))
            == list(v.shape) for name, v in self.sync_state.items())
        if self.sync_state and sync_paths and not sync_packed:
            if sync_shapes_ok or not resharded:
                # exact resumes keep the strict path: a shape mismatch
                # without a mesh change is corruption, and
                # load_checkpoint names the offending leaf
                template["sync"] = self.sync_state
                specs["sync"] = build.sync_state_specs(self.spec, self.mesh)
            else:
                print("resharded resume: error-feedback residual buckets "
                      "changed shape; residuals re-zeroed", flush=True)
        elif self.sync_state and not sync_paths:
            print("checkpoint predates sync_state persistence; "
                  "error-feedback residuals restart from zero", flush=True)
        tree, _ = load_checkpoint(c.dir, s, template, mesh=self.mesh,
                                  specs=specs)
        self.params, self.opt_state = tree["params"], tree["opt"]
        if "sync" in tree:
            self.sync_state = tree["sync"]
        elif self.sync_state and sync_packed:
            try:
                self.sync_state = self._load_packed_sync(c.dir, s)
            except ValueError:
                if not resharded:
                    raise
                print("resharded resume: error-feedback residual buckets "
                      "changed shape; residuals re-zeroed", flush=True)
        self.step = s + 1
        note = ""
        if resharded and saved is not None:
            note = (f" (resharded {saved.mesh.shape} -> "
                    f"{self.spec.mesh.shape}; data pipeline continues at "
                    f"sample offset of step {s + 1})")
        print(f"resumed from step {s}{note}", flush=True)

    def _load_packed_sync(self, direc, step: int) -> dict:
        """Restore block-sparse error-feedback residuals: read the packed
        sync/ subtree (via repro.checkpoint — the session never touches
        the on-disk layout), expand to dense, place with the sync
        sharding."""
        from ..checkpoint.ckpt import read_subtree_arrays

        packed = read_subtree_arrays(direc, step, "sync")
        if not is_packed_residuals(packed):
            raise ValueError(
                f"checkpoint step {step} has a malformed block-sparse "
                f"sync/ subtree (entries: "
                f"{ {k: sorted(v) for k, v in packed.items()} })")
        dense = unpack_residuals(packed)
        specs = build.sync_state_specs(self.spec, self.mesh)
        state = {}
        for name, want in self.sync_state.items():
            got = dense.get(name)
            if got is None or got.shape != want.shape:
                raise ValueError(
                    f"packed sync_state {name!r} does not match the run: "
                    f"checkpoint {None if got is None else got.shape} vs "
                    f"run {want.shape}")
            sharding = jax.sharding.NamedSharding(self.mesh, specs[name])
            state[name] = jax.device_put(jnp.asarray(got), sharding)
        return state

    # ------------------------------------------------------------ the loop
    def run_step(self, step: int) -> dict:
        """Execute one training step (caller holds the mesh context).

        The host phases are profiler spans -- ``train.step`` around
        ``train.input`` (host batch and its copy to the device),
        ``train.dispatch`` (the step's key and the enqueue of the jitted
        step) and ``train.fetch`` (waiting for the loss) -- and the
        record's seconds, taken at the same boundaries."""
        t0 = time.perf_counter()
        with TraceAnnotation("train.step", step=step):
            with TraceAnnotation("train.input"):
                batch = {"tokens": jnp.asarray(self.data.batch(step))}
            t1 = time.perf_counter()
            with TraceAnnotation("train.dispatch"):
                key = jax.random.fold_in(self._base_key, step)
                (self.params, self.opt_state, self.sync_state,
                 metrics) = self._jitted(self.params, self.opt_state,
                                         self.sync_state, batch, key)
            t2 = time.perf_counter()
            with TraceAnnotation("train.fetch"):
                loss = float(metrics["loss"])
        t3 = time.perf_counter()
        return {"step": step, "loss": round(loss, 5),
                "time_s": round(t3 - t0, 4), "input_s": round(t1 - t0, 4),
                "dispatch_s": round(t2 - t1, 4), "fetch_s": round(t3 - t2, 4)}

    def run(self, n_steps: int | None = None) -> list:
        """Run to ``spec.steps`` (or ``n_steps`` more), firing callbacks.
        Returns the per-step records."""
        end = (self.spec.steps if n_steps is None
               else min(self.spec.steps, self.step + n_steps))
        history = []
        for cb in self.callbacks:
            cb.on_train_start(self)
        try:
            with jax.set_mesh(self.mesh):
                while self.step < end and not self.stop_requested:
                    record = self.run_step(self.step)
                    self.step = record["step"] + 1
                    self.last_record = record
                    for cb in self.callbacks:
                        cb.on_step_end(self, record)
                    history.append(record)
        finally:
            for cb in self.callbacks:
                cb.on_train_end(self)
        return history
