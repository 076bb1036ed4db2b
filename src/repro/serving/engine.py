"""ServeEngine: continuous-batching decode over the paged KV pool.

One jitted decode step advances EVERY active sequence by one token:
admitted sequences prefill through ONE batched prefill launch (all of a
step's admissions packed into a padded prompt batch, their KV scattered
into freshly-allocated pages), then join the packed slot batch.
Sequences finish (budget / stop token) and new arrivals are admitted
between steps, so the batch membership changes continuously — the
classic continuous-batching loop, vs. ServeSession.generate's static
batch.

Both launches bucket their dynamic dimensions to powers of two so the
jitted programs retrace O(log) times, not once per shape: the decode
batch pads to a pow2 occupancy bucket (capped at ``max_active``), the
prefill batch pads rows the same way and prompt lengths to pow2
page-aligned buckets.  Inactive pad rows carry length 0 and an all-null
page table: they scatter into / gather from the reserved null page and
their logits are discarded.

Batched prefill shards its rows over the DP axes
(steps.make_batched_prefill_step), so dp > 1 serving meshes are legal:
prefill keeps the data axis busy while the decode step — whose packed
batch is occupancy-dynamic — runs replicated over 'data' (its inputs
carry no data-axis spec, every data shard computes identical tokens).

``ServeConfig.decode_backend`` picks the decode attention path
('gather' copies pages contiguous, 'paged' attends over the pool in
place — kernels.paged_attention on TPU, bit-exact gather fallback
elsewhere); ``ServeConfig.kv_dtype`` picks the pool storage dtype.

repro.api is imported function-locally (api.spec imports
serving.config — a module-level import here would cycle).
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation
from jax.sharding import PartitionSpec as P

from ..models import lm
from . import kv_pool, reload
from .scheduler import Scheduler, Sequence


class ServeEngine:
    def __init__(self, spec, params=None):
        from ..api import build
        spec.validate()
        self.spec = spec
        self.scfg = spec.serve
        self.cfg = spec.model_config()
        if not kv_pool.supports_paged(self.cfg):
            raise NotImplementedError(
                f"paged serving covers the dense-attention families; "
                f"{self.cfg.name} (ssm/enc-dec/moe) serves through "
                f"ServeSession instead")
        self.mesh = spec.mesh.build()
        # decode-path ctx: SP/remat are train-time concerns (mirrors
        # make_decode_step, which never enables them)
        ctx = dataclasses.replace(spec.mesh.ctx(), seq_parallel=False,
                                  remat_groups=0)
        self.ctx = ctx

        if params is not None:
            self.params, self.params_step = params, None
        else:
            self.params, self.params_step = reload.resolve_params(
                spec, self.cfg, self.mesh)
        self.reloader = None
        if spec.ckpt.dir and self.scfg.reload_every > 0:
            self.reloader = reload.ParamReloader(
                spec, self.cfg, self.mesh, current_step=self.params_step)

        n_pages = self.scfg.auto_pages()
        pspec = kv_pool.pool_specs(ctx)
        with jax.set_mesh(self.mesh):
            self.pool = kv_pool.init_pool(self.cfg, ctx, n_pages,
                                          self.scfg.page_size,
                                          kv_dtype=self.scfg.kv_dtype)
        # pin the pool to its steady-state sharding (the decode step's
        # out_specs) up front: _write_prompts' jit cache keys on input
        # sharding, so a fresh-from-init pool must not look different
        # from one that has been through a decode step
        from jax.sharding import NamedSharding
        self.pool = jax.device_put(
            self.pool, jax.tree.map(lambda s: NamedSharding(self.mesh, s),
                                    pspec))
        self.sched = Scheduler(self.scfg, kv_pool.PageAllocator(n_pages))

        pre, _, _ = build.build_batched_prefill_step(spec, self.cfg,
                                                     self.mesh)
        self._prefill = jax.jit(pre)
        p_specs = lm.flat_specs(self.cfg, ctx)

        def step(params, pool, page_table, lengths, token):
            return lm.paged_decode_step(
                self.cfg, ctx, params, pool, page_table, lengths, token,
                decode_backend=self.scfg.decode_backend)

        self._decode = jax.jit(
            jax.shard_map(step, mesh=self.mesh,
                          in_specs=(p_specs, pspec, P(None, None), P(None),
                                    P(None, None)),
                          out_specs=(P(None, ctx.model_axis), pspec),
                          check_vma=False),
            donate_argnums=(1,))
        self._write_prompts = jax.jit(kv_pool.write_prompts,
                                      donate_argnums=(0,))

        self.results: dict = {}      # rid -> list of generated token ids
        self.step_count = 0
        self.max_observed_active = 0

    # -------------------------------------------------------------- intake
    def submit(self, prompt, max_new_tokens=None) -> int:
        return self.sched.submit(prompt, max_new_tokens)

    def has_work(self) -> bool:
        return self.sched.has_work()

    # ---------------------------------------------------------------- step
    def step(self):
        """Advance every active sequence by one token.  Returns the list
        of (rid, token) pairs emitted this step (prefill first-tokens of
        newly admitted sequences included).

        Each host phase is a profiler span inside ``serve.step``, which
        carries the active, queued and preempted-so-far counts as the
        step starts: ``serve.reload``, ``serve.admit``, ``serve.prefill``,
        ``serve.grow``, ``serve.pack`` (page table, lengths and tokens to
        the device), ``serve.decode`` (the enqueue of the decode step),
        ``serve.sample`` and ``serve.emit``."""
        sched = self.sched
        with TraceAnnotation("serve.step", active=len(sched.active),
                             queued=len(sched.queue),
                             preempted=sched.n_preempted):
            return self._step()

    def _step(self):
        self.step_count += 1
        if (self.reloader is not None
                and self.step_count % self.scfg.reload_every == 0):
            with TraceAnnotation("serve.reload"):
                swapped = self.reloader.poll()
            if swapped is not None:
                self.params, self.params_step = swapped
                print(f"hot-swapped params to checkpoint step "
                      f"{self.params_step}", flush=True)
        emitted = []
        with jax.set_mesh(self.mesh):
            with TraceAnnotation("serve.admit") as span:
                admitted = self.sched.admit()
                span.set_metadata(admitted=len(admitted))
            if admitted:
                emitted += self._prefill_batch(admitted)
            self._ensure_growth()
            act = self.sched.active
            self.max_observed_active = max(self.max_observed_active, len(act))
            if not act:
                return emitted
            with TraceAnnotation("serve.pack"):
                b = min(max(1, 1 << (len(act) - 1).bit_length()),
                        self.scfg.max_active)
                pt = np.zeros((b, self.scfg.max_blocks), np.int32)
                ln = np.zeros((b,), np.int32)
                tok = np.zeros((b, 1), np.int32)
                for i, seq in enumerate(act):
                    pt[i, :len(seq.pages)] = seq.pages
                    ln[i] = seq.length
                    tok[i, 0] = seq.last_token
                pt, ln, tok = map(jnp.asarray, (pt, ln, tok))
            with TraceAnnotation("serve.decode"):
                logits, self.pool = self._decode(self.params, self.pool,
                                                 pt, ln, tok)
            toks = self._sample(logits[:len(act)], act)
        with TraceAnnotation("serve.emit"):
            for seq, t in zip(list(act), toks):
                seq.length += 1
                emitted += self._push_token(seq, int(t))
        return emitted

    def _ensure_growth(self):
        """Every active sequence gets a page for its next cache entry;
        when the pool runs dry the youngest sequences are preempted
        (pages freed, request re-queued with its generated tokens) until
        the remaining ones fit."""
        with TraceAnnotation("serve.grow"):
            i = 0
            while i < len(self.sched.active):
                seq = self.sched.active[i]
                if self.sched.grow(seq):
                    i += 1
                    continue
                victim = self.sched.preempt_youngest()
                if victim is seq:  # even alone it can't grow — re-queued
                    break

    def _len_bucket(self, t: int) -> int:
        """Prompt-length bucket: pow2 rounded up to a whole number of
        pages, capped at capacity — one compiled prefill per bucket."""
        ps = self.scfg.page_size
        tb = -(-max(ps, 1 << (t - 1).bit_length()) // ps) * ps
        return min(tb, self.scfg.capacity)

    def _row_bucket(self, n: int) -> int:
        """Prefill row bucket: the decode occupancy bucketing (pow2,
        capped at max_active), rounded up to a multiple of the DP degree
        so the batch axis shards evenly under dp > 1 meshes."""
        b = min(max(1, 1 << (n - 1).bit_length()), self.scfg.max_active)
        dpt = self.spec.mesh.dp * self.spec.mesh.pods
        return -(-max(b, n) // dpt) * dpt

    def _prefill_batch(self, seqs):
        """ONE padded prefill launch for every sequence admitted this
        step: prompts (+ previously generated tokens — preemption
        resume) right-padded into a pow2 page-aligned length bucket,
        rows padded to the occupancy bucket, each row's KV scattered
        into its own pages and its first token sampled from its own
        last-position logits.  Pad rows carry length 0: write_prompts
        drops their KV and their logits are discarded."""
        with TraceAnnotation("serve.prefill") as span:
            feeds = [s.req.prompt + s.req.generated for s in seqs]
            n = len(feeds)
            tb = self._len_bucket(max(len(f) for f in feeds))
            bb = self._row_bucket(n)
            tok = np.zeros((bb, tb), np.int32)
            ln = np.zeros((bb,), np.int32)
            pt = np.zeros((bb, tb // self.scfg.page_size), np.int32)
            for i, (seq, feed) in enumerate(zip(seqs, feeds)):
                tok[i, :len(feed)] = feed
                ln[i] = len(feed)
                pt[i, :len(seq.pages)] = seq.pages
            span.set_metadata(rows=n, tokens=int(ln.sum()), padded=bb * tb)
            ln = jnp.asarray(ln)
            logits, pkv = self._prefill(self.params, jnp.asarray(tok), ln)
            self.pool = self._write_prompts(self.pool, pkv,
                                            jnp.asarray(pt), ln)
            emitted = []
            for seq, t in zip(seqs, self._sample(logits[:n], seqs)):
                emitted += self._push_token(seq, int(t))
            return emitted

    def _push_token(self, seq: Sequence, tok: int):
        seq.req.generated.append(tok)
        seq.last_token = tok
        if self._stopped(seq):
            req = self.sched.finish(seq)
            self.results[req.rid] = list(req.generated)
        return [(seq.req.rid, tok)]

    def _stopped(self, seq: Sequence) -> bool:
        req = seq.req
        return (len(req.generated) >= req.max_new_tokens
                or seq.last_token == self.scfg.stop_token
                or seq.length >= self.scfg.capacity)

    # -------------------------------------------------------------- sample
    def _sample(self, logits, seqs):
        with TraceAnnotation("serve.sample"):
            logits = logits[:, :self.cfg.vocab]
            if self.scfg.temperature == 0.0:
                return np.asarray(jnp.argmax(logits, axis=-1))
            out = []
            for row, seq in zip(logits, seqs):
                # per-(request, position) key: deterministic under preemption
                # and re-batching
                key = jax.random.fold_in(
                    jax.random.fold_in(jax.random.PRNGKey(self.spec.seed),
                                       seq.req.rid),
                    len(seq.req.generated))
                row = row / self.scfg.temperature
                if self.scfg.top_k:
                    kth = jnp.sort(row)[-self.scfg.top_k]
                    row = jnp.where(row < kth, -jnp.inf, row)
                out.append(int(jax.random.categorical(key, row)))
            return np.asarray(out)

    # --------------------------------------------------------------- drive
    def serve(self, prompts, max_new_tokens=None) -> dict:
        """Submit a batch of prompts and run the engine to drain.
        Returns {rid: np.ndarray of generated token ids}."""
        rids = [self.submit(p, max_new_tokens) for p in prompts]
        while self.has_work():
            self.step()
        return {rid: np.asarray(self.results[rid]) for rid in rids}
