"""The train step's named scopes and the step record's host phases
(the spans themselves are captured and read in bench/tests/test_phases.py)."""
import re

import jax
import jax.numpy as jnp
import pytest

from repro.api import TrainSession

from test_api import tiny_spec


@pytest.fixture(scope="module")
def session():
    return TrainSession(tiny_spec(steps=3), callbacks=[])


def test_train_step_ops_carry_their_phase(session):
    """Forward ops sit under ``forward`` (``jvp(forward)`` once
    differentiated), backward ops under ``transpose(jvp(forward))``, and
    the sync and the update under ``grad_sync`` and ``optimizer``."""
    with jax.set_mesh(session.mesh):
        batch = {"tokens": jnp.asarray(session.data.batch(0))}
        lowered = session._jitted.lower(
            session.params, session.opt_state, session.sync_state, batch,
            jax.random.PRNGKey(0))
    names = re.findall(r'loc\("([^"]*)"', lowered.as_text(debug_info=True))
    for scope in ("jit(step)/jvp(forward)/", "/transpose(jvp(forward))/",
                  "jit(step)/grad_sync/", "jit(step)/optimizer/"):
        assert any(scope in n for n in names), scope


def test_step_record_splits_its_time(session):
    with jax.set_mesh(session.mesh):
        rec = session.run_step(0)
    assert set(rec) == {"step", "loss", "time_s", "input_s", "dispatch_s",
                        "fetch_s"}
    parts = rec["input_s"] + rec["dispatch_s"] + rec["fetch_s"]
    assert min(rec["input_s"], rec["dispatch_s"], rec["fetch_s"]) >= 0
    assert parts == pytest.approx(rec["time_s"], abs=2e-4)
