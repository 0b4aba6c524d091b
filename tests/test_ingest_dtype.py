"""Host ingest by dtype: a host frame of a narrow dtype crosses to the device
as submitted and is widened there (``device_stages.ingest_rows`` before
``stack_rows``); wider dtypes and batches that mix host dtypes are converted
to float32 on the host. Both paths give the same bits, compile the same
bounded set of ``stack_rows`` programs, and ``EngineStats.host_casts``
counts the frames that took the host path."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import BGConfig, add_gaussian_noise
from repro.data import synthetic_video
from repro.plan import BGPlan
from repro.serving import AsyncFrameEngine
from repro.serving.device_stages import ingest_rows, stack_rows
from repro.video import MultiStreamPacker

CFG = BGConfig(r=4, sigma_s=4.0, sigma_r=60.0)
H, W = 32, 48


def _plan():
    return BGPlan(cfg=CFG, backend="fused", quantize_output=True, interpret=True)


def _frames_u8(n, seed=0):
    vid = synthetic_video(seed, n, H, W, motion=1.0)
    return [
        np.clip(np.rint(np.asarray(add_gaussian_noise(vid[t], 30.0, seed=seed + t))),
                0, 255).astype(np.uint8)
        for t in range(n)
    ]


@pytest.mark.parametrize("dtype,travels", [
    (np.uint8, True), (np.int8, True), (np.uint16, True), (np.int16, True),
    (np.float16, True), (jnp.bfloat16, True), (np.float32, True),
    (np.float64, False), (np.int32, False), (np.int64, False),
])
def test_ingest_dtype_rule(dtype, travels):
    base = np.arange(3 * 5 * 7).reshape(3, 5, 7) % 100 - 20 * (
        np.dtype(dtype).kind == "i")
    frames = [base[i].astype(dtype) for i in range(3)]
    rows, casts = ingest_rows(frames)
    want = np.dtype(dtype) if travels else np.dtype(np.float32)
    assert [r.dtype for r in rows] == [want] * 3
    assert casts == (0 if travels else 3)
    if travels:  # sent as submitted: no host copy at all
        assert all(r is f for r, f in zip(rows, frames))
    # widened on the device or on the host, the stack holds the same bits
    out = np.asarray(stack_rows(rows, jnp.float32))
    assert out.dtype == np.float32
    np.testing.assert_array_equal(
        out, np.stack([np.asarray(f, np.float32) for f in frames]))
    # a device array beside the host frames passes as it is, uncounted
    dev = jnp.ones((5, 7), jnp.float32)
    rows, casts = ingest_rows([frames[0], dev])
    assert rows[1] is dev and casts == (0 if travels else 1)


def test_mixed_host_dtypes_take_the_host_path_for_every_row():
    u8 = np.full((5, 7), 200, np.uint8)
    f32 = np.full((5, 7), 3.0, np.float32)
    for batch in ([u8, f32, u8], [f32, u8]):
        rows, casts = ingest_rows(batch)
        assert [r.dtype for r in rows] == [np.dtype(np.float32)] * len(batch)
        assert casts == len(batch)
        np.testing.assert_array_equal(
            np.asarray(stack_rows(rows, jnp.float32)),
            np.stack([np.asarray(b, np.float32) for b in batch]))
    # non-native byte order cannot be handed to JAX as it is
    rows, casts = ingest_rows([u8.astype(">u2")])
    assert rows[0].dtype == np.float32 and casts == 1


def test_stack_rows_compiles_once_per_batch_length_for_uint8():
    h, w = 11, 13  # a shape no other test stacks
    rng = np.random.default_rng(0)
    before = stack_rows._cache_size()
    for _ in range(3):
        for n in (1, 2, 3):
            frames = [rng.integers(0, 256, (h, w), dtype=np.uint8) for _ in range(n)]
            rows, casts = ingest_rows(frames)
            assert casts == 0
            stack_rows(rows, jnp.float32)
    assert stack_rows._cache_size() - before == 3
    # mixed batches all fall back to the float32 program of their length
    u8 = np.zeros((h, w), np.uint8)
    f32 = np.zeros((h, w), np.float32)
    for batch in ([u8, f32], [f32, u8], [f32, f32], [u8.astype(np.int16), u8]):
        stack_rows(ingest_rows(batch)[0], jnp.float32)
    assert stack_rows._cache_size() - before == 4


def test_engine_uint8_answer_is_bit_identical_to_float32():
    frames = _frames_u8(4, seed=5)
    outs = {}
    for dtype in (np.uint8, np.float32):
        with AsyncFrameEngine(plan=_plan(), max_batch=4, batch_window_ms=50.0) as eng:
            futs = [eng.submit(f.astype(dtype)) for f in frames]
            outs[dtype] = [np.asarray(f.result(timeout=300)) for f in futs]
            st = eng.stats()
            assert st.completed == 4 and st.host_casts == 0
            if dtype is np.uint8:
                wide = [eng.submit(f.astype(np.float64)) for f in frames[:3]]
                wide_outs = [np.asarray(f.result(timeout=300)) for f in wide]
                st = eng.stats()
                assert st.host_casts == 3 and st.as_dict()["host_casts"] == 3
    # each answer in its frame's dtype (device_stages.answer_dtypes), the
    # same levels either way
    for a, b in zip(outs[np.uint8], outs[np.float32]):
        assert a.dtype == np.uint8 and b.dtype == np.float32
        np.testing.assert_array_equal(a, b)
    for a, b in zip(wide_outs, outs[np.uint8][:3]):
        assert a.dtype == np.float32
        np.testing.assert_array_equal(a, b)


def test_packer_uint8_pack_is_bit_identical_to_float32_warm():
    sids = ("a", "b", "c")
    per_stream = {s: _frames_u8(2, seed=7 * i) for i, s in enumerate(sids)}
    packers = {}
    outs = {}
    for dtype in (np.uint8, np.float32):
        packer = MultiStreamPacker(plan=_plan())
        for s in sids:
            packer.open(s, alpha=0.6)
        outs[dtype] = [
            packer.pack({s: per_stream[s][t].astype(dtype) for s in sids})
            for t in range(2)  # the second round blends a warm carry
        ]
        assert packer.host_casts == 0
        packers[dtype] = packer
    for t in range(2):
        for s in sids:
            np.testing.assert_array_equal(np.asarray(outs[np.uint8][t][s]),
                                          np.asarray(outs[np.float32][t][s]))
    for s in sids:
        c8 = packers[np.uint8].sessions[s].carry
        c32 = packers[np.float32].sessions[s].carry
        assert c8 is not None
        np.testing.assert_array_equal(np.asarray(c8), np.asarray(c32))
    packer = packers[np.uint8]
    packer.pack({s: per_stream[s][0].astype(np.float64) for s in sids})
    assert packer.host_casts == len(sids)
    with AsyncFrameEngine(packer=packer, max_batch=3, batch_window_ms=50.0) as eng:
        futs = [eng.submit(per_stream[s][1], stream_id=s) for s in sids]
        [f.result(timeout=300) for f in futs]
        assert eng.stats().host_casts == len(sids)
        futs = [eng.submit(per_stream[s][1].astype(np.int64), stream_id=s) for s in sids]
        [f.result(timeout=300) for f in futs]
        assert eng.stats().host_casts == 2 * len(sids)


def test_device_arrays_are_not_counted():
    # admission checks would bring the frames to the host; without them the
    # engine sees device arrays, which pass to stack_rows as they are
    frames = [jax.device_put(f) for f in _frames_u8(2, seed=9)]
    with AsyncFrameEngine(plan=_plan(), max_batch=2, batch_window_ms=50.0,
                          admission_checks=False) as eng:
        [f.result(timeout=300) for f in [eng.submit(x) for x in frames]]
        assert eng.stats().host_casts == 0
