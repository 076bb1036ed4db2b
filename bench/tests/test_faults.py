"""A whole run on the CPU at a tiny size, past the chip gate: a sound
program comes out correct, and the timed path broken underneath comes
out not correct, once for each fault the cells can have."""
import json
import os
import subprocess
import sys

import jax
import pytest

from bench import common, run

TINY = {
    "name": "tiny", "family": "dense", "hidden_size": 64, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "intermediate_size": 128,
    "num_hidden_layers": 2, "vocab_size": 256, "rope_theta": 10000.0,
    "rms_norm_eps": 1e-6, "dtype": "bfloat16",
    "optimizer": common.config_file("minitron-4b-stage4")["optimizer"],
}
SEED = 2 ** 31 + 101
# at width 64 bf16 and f32 part further than at the cells' widths (the
# weight change about 4e-4 against 1e-4, the first gradient about 1e-3
# against 4e-4), so the tiny runs get limits of their own
TINY_LIMITS = {"grad_norm_gap": 2.4e-3, "update_norm_gap": 2e-3}


def train_mix(traffic: str = "train-stage-4k") -> dict:
    """A training mix cut to the tiny size."""
    return dict(common.traffic_file(traffic), seq_len=16, warmup_steps=4,
                limits=TINY_LIMITS)


def train(**kw):
    cell = {"name": "train-minitron4b-1chip", "chips": 1}
    return run.measure(cell, TINY, train_mix(), SEED, 0.2, False,
                       jax.devices()[:1])


def state_unchanged(orig):
    """``build_train_step`` whose step returns params and optimizer state
    as it was given them."""
    def broken(spec, cfg=None, mesh=None):
        fn, ins, outs = orig(spec, cfg, mesh)

        def step(params, opt_state, sync_state, batch, key):
            _, _, sync_state, metrics = fn(params, opt_state, sync_state,
                                           batch, key)
            return params, opt_state, sync_state, metrics
        return step, ins, outs
    return broken


def half_batch(orig):
    """``loss_fn`` over the first half of the rows it is given."""
    def half(cfg, ctx, params, batch, remat=True):
        rows = batch["tokens"].shape[0]
        return orig(cfg, ctx, params,
                    {"tokens": batch["tokens"][: rows // 2]}, remat)
    return half


def four_chips(fault: str):
    """The 4-chip OptINC cell's run at the tiny size on four host CPU
    devices, with ``fault`` planted: 'none', 'no_exchange' (each chip
    keeps its own gradient), 'state_unchanged' or 'half_batch'.  Run in a
    process of its own, which makes the four devices before JAX starts."""
    if fault == "no_exchange":
        from repro.collectives.backends import OptincBackend
        OptincBackend.sync = lambda self, flat, cfg, key: (flat, None)
    elif fault == "state_unchanged":
        from repro.api import build
        build.build_train_step = state_unchanged(build.build_train_step)
    elif fault == "half_batch":
        from repro.models import lm
        lm.loss_fn = half_batch(lm.loss_fn)
    cell = {"name": "train-minitron4b-optinc-4chip", "chips": 4}
    line, checks = run.measure(cell, TINY, train_mix("train-dp4-4k"), SEED,
                               0.2, False, jax.devices()[:4])
    print(json.dumps({"correct": line["correct"], "checks": checks}))


def run_four_chips(fault: str, module: str = "test_faults",
                   fn: str = "four_chips") -> dict:
    """``bench.tests.<module>.<fn>(fault)`` in a process with four host
    CPU devices; the JSON line it prints last."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([str(common.SRC),
                                           str(common.ROOT)]))
    p = subprocess.run(
        [sys.executable, "-c", f"from bench.tests.{module} import {fn}; "
         f"{fn}({fault!r})"],
        cwd=common.ROOT, env=env, capture_output=True, text=True,
        timeout=600)
    assert p.returncode == 0, p.stderr[-4000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def serve_mix() -> dict:
    """The chat mix cut to the tiny size."""
    mix = common.traffic_file("chat")
    return dict(mix, rate_per_s=40.0,
                prompt={"median": 12, "sigma": 0.6, "min": 4, "max": 30},
                output={"median": 6, "sigma": 0.5, "min": 2, "max": 10},
                drain_s=30, check_tokens=20,
                # logits at width 64 spread about a tenth as wide as at
                # the cell's 7168, and so do their gaps
                limits={"served_gap": 0.1},
                serve=dict(mix["serve"], max_seq=48, max_new_tokens=10,
                           max_active=2))


def serve():
    cell = {"name": "serve-dscoder33b-chat", "chips": 1}
    return run.measure(cell, TINY, serve_mix(), SEED, 0.5, False,
                       jax.devices()[:1])


def failed(checks):
    return sorted(k for k, c in checks.items() if not c["value"] <= c["limit"])


def test_sound_training_run_is_correct():
    line, checks = train()
    assert line["correct"], checks
    assert line["metrics"]["train_tokens_per_s"]["value"] > 0


def test_step_returning_state_unchanged_is_caught(monkeypatch):
    from repro.api import build
    monkeypatch.setattr(build, "build_train_step",
                        state_unchanged(build.build_train_step))
    line, checks = train()
    assert not line["correct"]
    assert "update_norm_gap" in failed(checks)


def test_half_batch_is_caught(monkeypatch):
    from repro.models import lm
    monkeypatch.setattr(lm, "loss_fn", half_batch(lm.loss_fn))
    line, checks = train()
    assert not line["correct"], checks


def test_sound_optinc_four_chip_run_is_correct():
    out = run_four_chips("none")
    assert out["correct"], out


def test_exchange_left_out_is_caught():
    out = run_four_chips("no_exchange")
    assert not out["correct"], out


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch"])
def test_four_chip_faults_are_caught(fault):
    out = run_four_chips(fault)
    assert not out["correct"], out


def test_sound_serving_run_is_correct():
    line, checks = serve()
    assert line["correct"], checks
    assert line["attempted"] > 0 and line["failed"] == 0


def test_altered_token_is_caught(monkeypatch):
    from repro.serving.engine import ServeEngine
    orig = ServeEngine._sample

    def altered(self, logits, seqs):
        toks = orig(self, logits, seqs).copy()
        toks[0] = (toks[0] + 1) % self.cfg.vocab
        return toks
    monkeypatch.setattr(ServeEngine, "_sample", altered)
    line, checks = serve()
    assert not line["correct"]
    assert failed(checks) == ["served_gap"]
