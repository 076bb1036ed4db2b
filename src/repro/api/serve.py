"""ServeSession: prefill + decode + KV-cache management behind one object.

``examples/serve_decode.py`` and the dry-run decode cells previously each
re-derived mesh/ShardCtx and wired the serving steps by hand; both now go
through ``repro.api.build`` — ServeSession is the *runtime* face of that
shared path (real arrays, greedy generation), the dry-run is the
*lowering* face (abstract shapes).

Parameters come from (in order of precedence): the ``params`` argument,
the spec's checkpoint directory when ``ckpt.resume`` is set (serve a
trained run), or a fresh seeded init — the same ``serving.reload``
resolution the continuous-batching ServeEngine uses.

``generate`` runs the compiled prefill step over the whole prompt (one
forward, causal-masked) and seeds the decode cache from its KV, instead
of replaying the prompt token-by-token through the decode step — the
prompt costs one program launch instead of ``prompt_len``.  Both paths
are greedy and bit-exact with each other (tests/test_serving.py); the
replay path survives for the flash-decode seq-sharded cache layout,
whose sequence axis the prefill output is not sharded over.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..models import lm
from ..serving import reload as serving_reload
from . import build
from .spec import RunSpec


class ServeSession:
    def __init__(self, spec: RunSpec, params=None, *,
                 seq_shard_cache: bool = False, batch_shardable: bool = True):
        spec.validate()
        self.spec = spec
        self.cfg = spec.model_config()
        self.mesh = spec.mesh.build()
        self.ctx = spec.mesh.ctx(seq_shard_cache=seq_shard_cache)
        if params is not None:
            self.params, self.params_step = params, None
        else:
            self.params, self.params_step = serving_reload.resolve_params(
                spec, self.cfg, self.mesh)
        pre, _, _ = build.build_prefill_step(spec, self.cfg, self.mesh)
        dec, _, _ = build.build_decode_step(
            spec, self.cfg, self.mesh, seq_shard_cache=seq_shard_cache,
            batch_shardable=batch_shardable)
        self._prefill = jax.jit(pre)
        self._decode = jax.jit(dec, donate_argnums=(1,))
        self._seed = jax.jit(self._seed_cache, donate_argnums=(0,))

    @staticmethod
    def _seed_cache(full, pre):
        """Copy a prefill cache into a fresh full-length decode cache:
        leaves whose shapes already match (recurrent states, cross-attn
        KV) are taken as-is; KV leaves are placed at sequence offset 0."""
        def leaf(f, p):
            if f.shape == p.shape:
                return p.astype(f.dtype)
            return jax.lax.dynamic_update_slice(f, p.astype(f.dtype),
                                                (0,) * f.ndim)
        return jax.tree.map(leaf, full, pre)

    # ------------------------------------------------------------ serving
    def prefill(self, tokens, enc_frames=None):
        """(logits_at_last_position, prefill_cache) for a prompt batch."""
        feed = {"tokens": jnp.asarray(tokens)}
        if self.cfg.enc_dec:
            feed["enc_frames"] = enc_frames
        with jax.set_mesh(self.mesh):
            return self._prefill(self.params, feed)

    def new_cache(self, batch: int, max_seq: int):
        with jax.set_mesh(self.mesh):
            return lm.init_cache(self.cfg, self.ctx, batch, max_seq)

    def decode(self, cache, token, pos: int):
        """One decode step; the cache argument is donated."""
        with jax.set_mesh(self.mesh):
            return self._decode(self.params, cache, token, jnp.int32(pos))

    def engine(self):
        """A continuous-batching ServeEngine over this session's spec and
        params (paged KV pool, per-request scheduling — repro.serving)."""
        from ..serving.engine import ServeEngine
        return ServeEngine(self.spec, params=self.params)

    def generate(self, prompts, gen_len: int, max_seq: int | None = None,
                 enc_frames=None):
        """Greedy decode: compiled prefill over the prompt, decode cache
        seeded from the prefill KV, then argmax sampling one token per
        decode step.  Returns (batch, gen_len) int token ids."""
        if self.ctx.seq_shard_cache:
            # the flash-decode cache shards its sequence axis over 'data';
            # prefill output is not in that layout, so replay the prompt
            return self._generate_replay(prompts, gen_len, max_seq)
        prompts = jnp.asarray(prompts)
        batch, prompt_len = prompts.shape
        max_seq = max_seq or prompt_len + gen_len
        assert max_seq >= prompt_len + gen_len, (max_seq, prompt_len, gen_len)
        logits, pre = self.prefill(prompts, enc_frames=enc_frames)
        cache = self.new_cache(batch, max_seq)
        with jax.set_mesh(self.mesh):
            cache = self._seed(cache, pre)
            out = []
            tok = jnp.argmax(logits[:, :self.cfg.vocab], -1)[:, None]
            out.append(tok)
            for i in range(gen_len - 1):
                logits, cache = self._decode(self.params, cache, tok,
                                             jnp.int32(prompt_len + i))
                tok = jnp.argmax(logits[:, :self.cfg.vocab], -1)[:, None]
                out.append(tok)
        return jnp.concatenate(out, axis=1)

    def _generate_replay(self, prompts, gen_len: int,
                         max_seq: int | None = None):
        """Token-by-token reference path: replay the prompt through the
        decode step (same cache layout the dry-run cells lower), then
        sample argmax tokens."""
        prompts = jnp.asarray(prompts)
        batch, prompt_len = prompts.shape
        max_seq = max_seq or prompt_len + gen_len
        assert max_seq >= prompt_len + gen_len, (max_seq, prompt_len, gen_len)
        cache = self.new_cache(batch, max_seq)
        with jax.set_mesh(self.mesh):
            logits = None
            for i in range(prompt_len):
                logits, cache = self._decode(self.params, cache,
                                             prompts[:, i:i + 1], jnp.int32(i))
            out = []
            tok = jnp.argmax(logits[:, :self.cfg.vocab], -1)[:, None]
            out.append(tok)
            for i in range(gen_len - 1):
                logits, cache = self._decode(self.params, cache, tok,
                                             jnp.int32(prompt_len + i))
                tok = jnp.argmax(logits[:, :self.cfg.vocab], -1)[:, None]
                out.append(tok)
        return jnp.concatenate(out, axis=1)
