"""The answer's dtype follows its own request's frame.

A frame of 8-bit integer levels gets its answer as ``uint8``: the plan's
quantized float32 levels, cast in the engine's ``bg.split`` program
(``device_stages.answer_dtypes``), so the client copies a quarter of the
bytes. Every other frame gets the plan's float32 answer. The values are the
same either way; a batch that mixes the two hands each request its own; the
output guard reads the float32 values before the cast, so a non-finite
answer is refused, never handed out as zeros; packer carries keep their
storage dtype; ``EngineStats.level_answers`` counts the ``uint8`` answers.
The mesh plan's side of the contract is in tests/test_mesh_engine.py.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import BGConfig, add_gaussian_noise
from repro.data import synthetic_video
from repro.kernels.bg_fused import is_level_dtype
from repro.plan import BGPlan
from repro.reliability import Fault, FaultInjector, FaultPlan, NonFiniteOutput
from repro.serving import AsyncFrameEngine, EngineStats
from repro.serving.device_stages import answer_dtypes, split_rows
from repro.video import MultiStreamPacker

CFG = BGConfig(r=4, sigma_s=4.0, sigma_r=60.0)
H, W = 32, 48
U8 = np.dtype(np.uint8)
F32 = np.dtype(np.float32)
KINDS = {"u8": np.uint8, "i8": np.int8, "f32": np.float32, "f64": np.float64}


def _plan(backend="fused", **kw):
    return BGPlan(cfg=CFG, backend=backend, quantize_output=True,
                  interpret=True, **kw)


def _levels(n, seed):
    """``n`` noisy frames of 8-bit levels in [0, 127], so that every
    kind of ``KINDS`` holds them exactly."""
    vid = synthetic_video(seed, n, H, W, motion=1.0)
    return [
        (np.clip(np.rint(np.asarray(add_gaussian_noise(vid[t], 30.0, seed=seed + t))),
                 0, 255) // 2).astype(np.uint8)
        for t in range(n)
    ]


def _want_dtype(kind):
    return U8 if kind in ("u8", "i8") else F32


@pytest.mark.parametrize("kinds", [
    ("u8", "u8", "u8"), ("f32", "f32"), ("u8", "f32", "u8"), ("f32", "u8"),
    ("i8", "f64"),
])
def test_engine_answer_dtype_follows_its_own_frame(kinds):
    frames = [f.astype(KINDS[k]) for f, k in zip(_levels(len(kinds), seed=3), kinds)]
    plan = _plan("fused_streamed")
    want = np.asarray(plan(jnp.asarray(np.stack(frames).astype(np.float32))))
    with AsyncFrameEngine(plan=plan, max_batch=len(kinds),
                          batch_window_ms=60_000.0) as eng:
        outs = [f.result(timeout=300) for f in [eng.submit(x) for x in frames]]
        st = eng.stats()
    assert st.dispatches == 1 and st.completed == len(kinds)
    for out, kind, row in zip(outs, kinds, want):
        assert np.dtype(out.dtype) == _want_dtype(kind), kind
        np.testing.assert_array_equal(np.asarray(out), row)
    levels = sum(_want_dtype(k) == U8 for k in kinds)
    assert st.level_answers == st.as_dict()["level_answers"] == levels


def test_answer_dtypes_rule():
    u8, f32 = np.zeros((2, 2), np.uint8), np.zeros((2, 2), np.float32)
    assert answer_dtypes(_plan(), [u8, f32, u8]) == (U8, None, U8)
    assert answer_dtypes(_plan(), [f32, f32]) is None
    # an answer that is not whole levels a uint8 holds stays float32
    assert answer_dtypes(BGPlan(cfg=CFG, backend="fused", quantize_output=False,
                                interpret=True), [u8]) is None
    for top in (1023.0, 200.5):
        cfg = BGConfig(r=4, sigma_s=4.0, sigma_r=60.0, intensity_max=top)
        assert answer_dtypes(BGPlan(cfg=cfg, backend="fused", interpret=True),
                             [u8]) is None
    cfg = BGConfig(r=4, sigma_s=4.0, sigma_r=60.0, intensity_max=200.0)
    assert answer_dtypes(BGPlan(cfg=cfg, backend="fused", interpret=True),
                         [u8]) == (U8,)
    # the split casts exactly the rows it is told to, in one program
    x = jnp.asarray([[[0.0, 255.0]], [[7.0, 128.0]], [[1.0, 2.0]]])
    rows = split_rows(x, 2, (U8, None))
    assert [np.dtype(r.dtype) for r in rows] == [U8, F32]
    np.testing.assert_array_equal(np.asarray(rows[0]), [[0, 255]])
    np.testing.assert_array_equal(np.asarray(rows[1]), [[7.0, 128.0]])


@pytest.mark.parametrize("kinds", [("u8", "u8", "u8"), ("u8", "f32", "u8")])
@pytest.mark.parametrize("alpha", [0.0, 0.6], ids=["cold", "warm"])
def test_packer_answer_dtype_follows_its_own_frame(alpha, kinds):
    sids = ("a", "b", "c")
    per_stream = {s: _levels(2, seed=11 * i) for i, s in enumerate(sids)}
    packers, outs = {}, {}
    for side in ("float32", "mixed"):
        packer = MultiStreamPacker(plan=_plan())
        for s in sids:
            packer.open(s, alpha=alpha)
        outs[side] = [
            packer.pack({
                s: per_stream[s][t].astype(np.float32 if side == "float32"
                                           else KINDS[k])
                for s, k in zip(sids, kinds)})
            for t in range(2)  # the second round blends a warm carry
        ]
        packers[side] = packer
    for t in range(2):
        for s, k in zip(sids, kinds):
            got, ref = outs["mixed"][t][s], outs["float32"][t][s]
            assert np.dtype(got.dtype) == _want_dtype(k)
            assert np.dtype(ref.dtype) == F32
            np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))
    for s in sids:  # carries stay in the plan's storage dtype
        c, ref = packers["mixed"].sessions[s].carry, packers["float32"].sessions[s].carry
        if alpha == 0.0:
            assert c is None and ref is None
            continue
        assert np.dtype(c.dtype) == np.dtype(packers["mixed"].plan.storage_dtype) == F32
        np.testing.assert_array_equal(np.asarray(c), np.asarray(ref))
    levels = 2 * sum(k == "u8" for k in kinds)
    assert packers["mixed"].level_answers == levels
    assert packers["float32"].level_answers == 0
    # through the engine, which counts the answers it hands out
    packer = packers["mixed"]
    with AsyncFrameEngine(packer=packer, max_batch=3, batch_window_ms=60_000.0) as eng:
        futs = [eng.submit(per_stream[s][0].astype(KINDS[k]), stream_id=s)
                for s, k in zip(sids, kinds)]
        got = [f.result(timeout=300) for f in futs]
        st = eng.stats()
    assert [np.dtype(g.dtype) for g in got] == [_want_dtype(k) for k in kinds]
    assert st.level_answers == levels // 2
    assert packer.level_answers == levels + levels // 2


def test_level_answers_merge_and_default():
    parts = [EngineStats(1, 1, 1, 0, 0, 0, 1.0, 1.0, 1.0, level_answers=k)
             for k in (3, 4)]
    assert EngineStats.merge(parts).level_answers == 7
    old = parts[0].as_dict()
    del old["level_answers"]  # a worker that predates the field
    assert EngineStats(**old).level_answers == 0


def test_nonfinite_answer_is_refused_before_the_uint8_cast(monkeypatch):
    """A plan that emits NaN in the first row of every 8-bit batch: that
    request fails with ``NonFiniteOutput`` (a NaN cast to uint8 would read
    as a silent level), its batch-mates are handed out as uint8, and only
    they are counted."""
    call = BGPlan.__call__

    def nan_for_levels(self, frames, *args, **kw):
        out = call(self, frames, *args, **kw)
        return out.at[0].set(jnp.nan) if is_level_dtype(frames.dtype) else out

    monkeypatch.setattr(BGPlan, "__call__", nan_for_levels)
    frames = _levels(3, seed=17)
    plan = _plan("fused_streamed")
    with AsyncFrameEngine(plan=plan, max_batch=3, batch_window_ms=60_000.0) as eng:
        futs = [eng.submit(f) for f in frames]
        with pytest.raises(NonFiniteOutput):
            futs[0].result(timeout=300)
        outs = [f.result(timeout=300) for f in futs[1:]]
        st = eng.stats()
    want = np.asarray(call(plan, jnp.asarray(np.stack(frames).astype(np.float32))))
    for out, row in zip(outs, want[1:]):
        assert np.dtype(out.dtype) == U8
        np.testing.assert_array_equal(np.asarray(out), row)
    assert st.failed == 1 and st.completed == 2 and st.level_answers == 2


@pytest.mark.parametrize("kind", ["u8", "f32"])
def test_corrupt_carry_on_a_stream_is_quarantined_not_zeroed(kind):
    """A NaN written into a warm stream's carry after its first pack blends
    to a finite all-zero frame (the kernel's normalization maps a NaN
    weight to 0), which the output guard cannot see: the carry guard
    refuses that answer and quarantines the stream, the other stream is
    answered in its frame's dtype, and the poisoned stream serves clean
    answers again on its next frame."""
    sids = ("a", "b")
    per_stream = {s: _levels(3, seed=23 + i) for i, s in enumerate(sids)}
    packer = MultiStreamPacker(plan=_plan())
    for s in sids:
        packer.open(s, alpha=0.6)
    inj = FaultInjector(FaultPlan(faults=(
        Fault(kind="corrupt_carry", stream_id="a", dispatch=0, mode="nan"),)))
    outcomes = {}
    with AsyncFrameEngine(packer=packer, max_batch=2, batch_window_ms=60_000.0) as eng:
        eng.fault_injector = inj
        for t in range(3):
            futs = {s: eng.submit(per_stream[s][t].astype(KINDS[kind]), stream_id=s)
                    for s in sids}
            for s, f in futs.items():
                try:
                    out = f.result(timeout=300)
                except NonFiniteOutput:
                    outcomes[(s, t)] = "refused"
                    continue
                assert np.dtype(out.dtype) == _want_dtype(kind)
                assert np.asarray(out).any(), (s, t)  # levels, not zeros
                outcomes[(s, t)] = "ok"
        st = eng.stats()
    assert inj.fired == [1]
    assert outcomes == {("a", 0): "ok", ("b", 0): "ok", ("a", 1): "refused",
                        ("b", 1): "ok", ("a", 2): "ok", ("b", 2): "ok"}
    assert st.carry_resets == 1 and st.failed == 1
    assert st.completed == 5
    assert st.level_answers == (5 if kind == "u8" else 0)
