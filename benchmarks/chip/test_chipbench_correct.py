"""What decides ``correct``: a sound run passes; the control (the program's
own bf16 storage path, one precision below the configuration's fp32) and
each fault planted under the timed path fail. Tiny frames on the CPU, with
the harness's look for a chip skipped and the rest of a run driven."""
import os
import sys

import jax
import jax.numpy as jnp
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [HERE, os.path.join(os.path.dirname(os.path.dirname(HERE)), "src")]

import harness  # noqa: E402
from repro.plan import BGPlan  # noqa: E402

SEED = 2**31 + 99
SMALL = {"config": {"height": 48, "width": 64},
         "traffic": {"pool_frames": 4, "outstanding": 4, "engine": {"max_batch": 2}}}
CELLS = {
    "paper1080-batch": {},
    "paper1080-live": {"traffic": {"fps": 20}},
    "video1080-streams": {"traffic": {"streams": 2, "fps": 10, "phases": [0.2, 0.7],
                                      "engine": {"max_batch": 2}}},
}


def run(cell, extra=None):
    over = harness._merge(harness._merge(SMALL, CELLS[cell]), extra)
    return harness.run(cell, SEED, 0.6, False, overrides=over,
                       devices=jax.devices(), log=lambda m: None)


def patch_call(monkeypatch, wrap):
    orig = BGPlan.__call__

    def call(self, frames, carry=None, alpha=None):
        return wrap(orig, self, frames, carry, alpha)

    monkeypatch.setattr(BGPlan, "__call__", call)


def altered(orig, self, frames, carry, alpha):
    """Every answer altered where it is produced: one intensity level up."""
    out = orig(self, frames, carry, alpha)
    if self.temporal:
        return jnp.clip(out[0] + 1.0, 0, 255), out[1]
    return jnp.clip(out + 1.0, 0, 255)


def half_batch(orig, self, frames, carry, alpha):
    """Half of each batch left out: its answers are copies of the rest."""
    frames = jnp.asarray(frames)
    n = frames.shape[0]
    if n < 2:
        return orig(self, frames, carry, alpha)
    h = (n + 1) // 2
    if self.temporal:
        out, new = orig(self, frames[:h], carry[:h], alpha[:h])
        return (jnp.concatenate([out, out[: n - h]]),
                jnp.concatenate([new, new[: n - h]]))
    out = orig(self, frames[:h])
    return jnp.concatenate([out, out[: n - h]])


def one_row(orig, self, frames, carry, alpha):
    """One row of every answer altered where it is produced: a fault too
    small for the share of differing pixels to see."""
    out = orig(self, frames, carry, alpha)
    if self.temporal:
        return out[0].at[:, 5].set(0.0), out[1]
    return out.at[:, 5].set(0.0)


def state_unchanged(orig, self, frames, carry, alpha):
    """The temporal step returns its carry as it came in."""
    out = orig(self, frames, carry, alpha)
    if self.temporal:
        return out[0], carry
    return out


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_sound_run_is_correct(cell):
    res = run(cell)
    assert res["correct"], res["checks"]
    assert res["checks"]["compared_frames"]["value"] >= 1
    assert list(res)[-1] == "checks"


@pytest.mark.parametrize("cell", ["paper1080-batch", "video1080-streams"])
def test_control_bf16_storage_fails(cell):
    res = run(cell, {"config": {"precision": "bf16"}})
    assert not res["correct"]
    assert res["checks"]["off_share_worst"]["value"] > res["checks"]["off_share_worst"]["limit"]


# a window long enough that packs of both streams form
FULL_PACKS = {"traffic": {"engine": {"batch_window_ms": 150.0}}}
FAULTS = [
    ("paper1080-batch", altered, None), ("paper1080-live", altered, None),
    ("video1080-streams", altered, None), ("paper1080-batch", half_batch, None),
    ("video1080-streams", half_batch, FULL_PACKS),
    ("video1080-streams", state_unchanged, None),
    ("paper1080-batch", one_row, None), ("video1080-streams", one_row, None),
]


@pytest.mark.parametrize("cell,fault,extra", FAULTS,
                         ids=[f"{c}-{f.__name__}" for c, f, _ in FAULTS])
def test_fault_under_the_timed_path_fails(monkeypatch, cell, fault, extra):
    if extra is not None:
        assert run(cell, extra)["correct"]  # sound with the same settings
    patch_call(monkeypatch, fault)
    res = run(cell, extra)
    assert not res["correct"], res["checks"]


def test_one_altered_row_is_caught_by_the_largest_difference(monkeypatch):
    """A row is 1/48 of a small test frame; at 1080p it is 1/1080, under the
    limit on the share, so the largest difference has to catch it."""
    patch_call(monkeypatch, one_row)
    res = run("paper1080-batch", {"config": {"limits": {"off_share_worst": 0.03}}})
    checks = res["checks"]
    assert checks["off_share_worst"]["value"] <= checks["off_share_worst"]["limit"]
    assert checks["max_abs_diff"]["value"] > checks["max_abs_diff"]["limit"]
    assert not res["correct"]
