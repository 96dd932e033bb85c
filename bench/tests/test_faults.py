"""A run with the timed path broken underneath must come out not correct.

Each test drives the whole run of a test-size cell (set-up, window,
reference, comparison) on the CPU, with one fault planted in the program.
"""
import json
import os
import subprocess
import sys
from pathlib import Path

import jax.numpy as jnp
import pytest

import support


@pytest.fixture(scope="module")
def bench(tmp_path_factory):
    return support.make_bench(tmp_path_factory.mktemp("bench"))


@pytest.mark.parametrize("cell", ["dvs_small", "cifar_small"])
def test_sound_run_is_correct(bench, cell):
    r = support.run(bench, cell)
    assert r["correct"], r["checks"]
    assert r["checks"]["logit_err"]["value"] == 0.0
    assert r["attempted"] > 0 and r["failed"] == 0
    assert r["window"]["compiles"] == 0


def test_ring_state_left_unchanged(bench, monkeypatch):
    """The pool step returns its ring state unchanged."""
    import repro.serving.pool as pool_mod

    monkeypatch.setattr(pool_mod, "masked_push", lambda state, feats, active: state)
    assert not support.run(bench, "dvs_small")["correct"]


def test_last_frame_of_a_stream_dropped(bench, monkeypatch):
    """The batcher hands back no logits for a departing stream's last
    frame: nothing else shifts, so only the frame count can see it."""
    from repro.serving import ContinuousBatcher

    tick = ContinuousBatcher.tick

    def dropping(self):
        done = len(self.results)
        out = tick(self)
        for r in self.results[done:]:
            out.pop(r.stream_id, None)
        return out

    monkeypatch.setattr(ContinuousBatcher, "tick", dropping)
    r = support.run(bench, "dvs_small")
    assert not r["correct"]
    assert r["checks"]["logit_err"]["value"] == 0.0
    assert r["checks"]["unmatched"]["value"] > 0


def test_half_the_pool_left_out(bench, monkeypatch):
    """The second half of the pool's slots never reach the device."""
    from repro.serving.pool import SessionPool

    step = SessionPool.step_prepared

    def half(self, batch, active):
        batch = batch.copy()
        batch[len(batch) // 2:] = 0.0
        return step(self, batch, active)

    monkeypatch.setattr(SessionPool, "step_prepared", half)
    assert not support.run(bench, "dvs_small")["correct"]


def test_half_the_batch_left_out(bench, monkeypatch):
    """The forward computes half the batch and repeats it for the rest."""
    from repro.api.program import DeployedProgram

    forward = DeployedProgram.forward

    def half(self, x, backend="pallas"):
        y = forward(self, x[: len(x) // 2], backend)
        return jnp.concatenate([y, y])

    monkeypatch.setattr(DeployedProgram, "forward", half)
    assert not support.run(bench, "cifar_small")["correct"]


@pytest.mark.parametrize("cell", ["dvs_small", "cifar_small"])
def test_answer_altered_where_produced(bench, monkeypatch, cell):
    """The first row's logits are negated where the program makes them."""
    from repro.api.program import DeployedProgram

    name = "temporal_forward" if cell == "dvs_small" else "spatial_forward"
    orig = getattr(DeployedProgram, name)

    def altered(self, x, backend="pallas"):
        y = orig(self, x, backend)
        return y.at[0].multiply(-1)

    monkeypatch.setattr(DeployedProgram, name, altered)
    assert not support.run(bench, cell)["correct"]


X4 = """
import json, sys, tempfile
from pathlib import Path
sys.path[:0] = [{bench!r}, {src!r}, {tests!r}]
import support
bench = support.make_bench(Path(tempfile.mkdtemp()))
if {fault!r}:
    import jax.numpy as jnp
    from repro.serving.pool import SessionPool
    step = SessionPool.step_prepared
    def local_only(self, batch, active):
        logits = step(self, batch, active)
        q = len(logits) // 4
        return jnp.tile(logits[:q], (4, 1))
    SessionPool.step_prepared = local_only
r = support.run(bench, "dvs_small_x4")
print(json.dumps({{"correct": r["correct"], "count": r["device"]["count"]}}))
"""


@pytest.mark.parametrize("fault", [False, True], ids=["sound", "exchange_left_out"])
def test_four_devices(fault):
    """On four CPU devices: the sharded pool is correct, and a gather of
    the logits that leaves out the other devices' slots is not."""
    here = Path(__file__).resolve().parent
    code = X4.format(bench=str(here.parent), src=str(here.parents[1] / "src"),
                     tests=str(here), fault=fault)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-c", code], capture_output=True, text=True,
                       env=env, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    r = json.loads(p.stdout.strip().splitlines()[-1])
    assert r["count"] == 4
    assert r["correct"] is (not fault)
