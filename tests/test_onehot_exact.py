"""The fused kernel's one-hot contractions in one bf16 MXU pass, exactly.

Grid creation (GC) runs its one-hot contraction on bf16 operands when the
frames arrive as 8-bit integer levels, and at ``Precision.HIGHEST`` for
fractional float frames; trilinear interpolation (TI) gathers each fp32
plane value as three bf16 parts (``bg_fused.ti_gather``). Both are exact:
8-bit frames give the bits that the same values give as float32, and the
three-part gather gives the bits of the ``HIGHEST`` gather. The engine and
the video packer hand the plan 8-bit rows as they are, and
``EngineStats.level_dispatches`` counts those launches.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import BGConfig, add_gaussian_noise
from repro.data import synthetic_video
from repro.kernels.bg_fused import bg_fused_kernel_call, is_level_dtype, ti_gather
from repro.kernels.common import grid_shape, ti_col_onehots
from repro.plan import BGPlan
from repro.serving import AsyncFrameEngine, EngineStats
from repro.video import MultiStreamPacker, carry_shape

H, W = 36, 52
CFGS = {
    4: BGConfig(r=4, sigma_s=4.0, sigma_r=30.0),
    12: BGConfig(r=12, sigma_s=8.0, sigma_r=70.0),
}


def _frames_u8(n, seed=0, h=H, w=W):
    vid = synthetic_video(seed, n, h, w, motion=1.0)
    return np.stack([
        np.clip(np.rint(np.asarray(add_gaussian_noise(vid[t], 30.0, seed=seed + t))),
                0, 255).astype(np.uint8)
        for t in range(n)
    ])


def _carry(b, cfg, seed):
    """A warm-looking carry: non-negative homogeneous grids with weights."""
    rng = np.random.default_rng(seed)
    shape = (b,) + carry_shape(H, W, cfg)
    c = rng.uniform(0.0, 4.0, shape).astype(np.float32)
    c[..., 1] *= rng.uniform(0.0, 255.0, shape[:-1]).astype(np.float32)
    return jnp.asarray(c)


@pytest.mark.parametrize("b", [1, 4, 7])
@pytest.mark.parametrize("r", [4, 12])
@pytest.mark.parametrize("path", ["fused", "fused_streamed", "temporal"])
def test_levels_match_float32_bitwise(path, r, b):
    """8-bit frames (GC in one bf16 pass) give the unquantized output, and
    in the temporal kernel the new carry, bit for bit as the same values
    given as float32 (GC at HIGHEST)."""
    cfg = CFGS[r]
    x8 = jnp.asarray(_frames_u8(b, seed=10 * r + b))
    x32 = x8.astype(jnp.float32)
    kw = dict(cfg=cfg, interpret=True, batch_tile=3)
    if path != "temporal":
        kw["stream_input"] = path == "fused_streamed"
        a, f = bg_fused_kernel_call(x8, **kw), bg_fused_kernel_call(x32, **kw)
        assert a.dtype == f.dtype == jnp.float32
        np.testing.assert_array_equal(np.asarray(a), np.asarray(f))
        return
    carry = _carry(b, cfg, seed=r + b)
    # alpha 0 everywhere, then warm rows (a > 0, the first among them)
    # beside cold ones, within one geometry
    for alpha in (np.zeros(b, np.float32),
                  np.where(np.arange(b) % 2 == 0, 0.6, 0.0).astype(np.float32)):
        a = bg_fused_kernel_call(x8, carry=carry, alpha=jnp.asarray(alpha), **kw)
        f = bg_fused_kernel_call(x32, carry=carry, alpha=jnp.asarray(alpha), **kw)
        for got, want in zip(a, f):
            np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def _planes(seed, shape=(2, 3, 5, 17)):
    """fp32 planes over the grid's value range, with zeros, values below
    1e-3, values just below powers of two, and one exact bf16 value."""
    rng = np.random.default_rng(seed)
    p = (rng.uniform(0.0, 255.0, shape) * rng.uniform(0.0, 1.0, shape) ** 4)
    flat = p.reshape(-1)
    n = flat.size
    flat[: n // 8] = 0.0
    flat[n // 8: n // 4] = rng.uniform(1e-7, 1e-3, n // 4 - n // 8)
    k = rng.integers(-12, 8, n // 8)
    flat[n // 4: n // 4 + n // 8] = np.nextafter(
        np.exp2(k).astype(np.float32), np.float32(0))
    flat[-1] = 1.5
    return p.astype(np.float32)


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_ti_three_part_gather_equals_highest(seed):
    w, r = 62, 4
    gy = 17
    oh0, oh1, _ = ti_col_onehots(w, gy, r)
    y_oh = np.stack([oh0, oh1])
    planes = jnp.asarray(_planes(seed, (2, 3, 5, gy)))
    want = jnp.einsum(
        "pbzg,cwg->pbzcw", planes, jnp.asarray(y_oh),
        preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,
    )
    got = ti_gather(planes, jnp.asarray(y_oh, jnp.bfloat16), 3)
    assert got.shape == want.shape == (2, 3, 5, 2, w)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # values that are already bf16 (bf16 storage) take one part
    bf = planes.astype(jnp.bfloat16).astype(jnp.float32)
    want_bf = jnp.einsum(
        "pbzg,cwg->pbzcw", bf, jnp.asarray(y_oh),
        preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,
    )
    got_bf = ti_gather(bf, jnp.asarray(y_oh, jnp.bfloat16), 1)
    np.testing.assert_array_equal(np.asarray(got_bf), np.asarray(want_bf))


def test_three_bf16_parts_rebuild_fp32_exactly():
    """The split behind ti_gather, on a million values spread over the
    exponents a grid plane holds: (hi + mid) + lo == x, bit for bit."""
    rng = np.random.default_rng(3)
    x = (rng.uniform(1.0, 2.0, 1_000_000)
         * np.exp2(rng.integers(-20, 9, 1_000_000))).astype(np.float32)
    x[:1000] = 0.0
    rest, parts = x, []
    for _ in range(3):
        part = rest.astype(jnp.bfloat16).astype(np.float32)
        parts.append(part)
        rest = rest - part
    np.testing.assert_array_equal((parts[0] + parts[1]) + parts[2], x)


def _dots(jaxpr):
    """Every dot_general in ``jaxpr``, inside the Pallas kernel too."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            found.append(eqn)
        for v in eqn.params.values():
            for sub in v if isinstance(v, (list, tuple)) else (v,):
                if isinstance(sub, jax.extend.core.ClosedJaxpr):
                    found += _dots(sub.jaxpr)
                elif isinstance(sub, jax.extend.core.Jaxpr):
                    found += _dots(sub)
    return found


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.uint8])
def test_gc_precision_follows_the_frames_dtype(dtype):
    """Fractional float frames keep GC at HIGHEST on fp32 operands; 8-bit
    levels give GC bf16 operands at the default precision. TI is one bf16
    pass either way."""
    cfg = CFGS[4]
    x = jax.ShapeDtypeStruct((2, H, W), dtype)
    jaxpr = jax.make_jaxpr(lambda im: bg_fused_kernel_call(
        im, cfg, interpret=True, batch_tile=2))(x).jaxpr
    dots = _dots(jaxpr)
    gy = grid_shape(H, W, cfg)[1]
    gc = [e for e in dots if e.invars[1].aval.shape == (W, gy)]
    ti = [e for e in dots if e.invars[1].aval.shape == (2, W, gy)]
    assert len(gc) == len(ti) == 1 and len(dots) == 2
    levels = dtype == jnp.uint8
    assert is_level_dtype(dtype) == levels
    want = jnp.bfloat16 if levels else jnp.float32
    assert [v.aval.dtype for v in gc[0].invars] == [want, want]
    hi = jax.lax.Precision.HIGHEST
    precision = gc[0].params["precision"]
    assert (precision is not None and hi in precision) == (not levels)
    assert [v.aval.dtype for v in ti[0].invars] == [jnp.bfloat16] * 2
    # fp32 storage: three bf16 parts of each of the 2 planes of 2 frames
    gz = grid_shape(H, W, cfg)[2]
    assert ti[0].invars[0].aval.shape == (3 * 2 * 2 * gz, gy)
    assert ti[0].params["precision"] is None or hi not in ti[0].params["precision"]
    for e in dots:
        assert e.params["preferred_element_type"] == jnp.float32


@pytest.mark.parametrize("dtype,levels", [
    (np.uint8, True), (np.int8, True), (np.uint16, False), (np.int16, False),
    (np.int32, False), (np.float16, False), (jnp.bfloat16, False),
    (np.float32, False), (np.bool_, False),
])
def test_is_level_dtype(dtype, levels):
    assert is_level_dtype(dtype) is levels


def _recording(monkeypatch):
    """Record the dtype of every batch that reaches a plan."""
    seen = []
    call = BGPlan.__call__

    def record(self, frames, *args, **kw):
        seen.append(np.dtype(frames.dtype))
        return call(self, frames, *args, **kw)

    monkeypatch.setattr(BGPlan, "__call__", record)
    return seen


def test_engine_hands_the_plan_uint8_rows_and_counts_them(monkeypatch):
    seen = _recording(monkeypatch)
    plan = BGPlan(cfg=CFGS[4], backend="fused_streamed", quantize_output=True,
                  interpret=True)
    frames = list(_frames_u8(5, seed=21))
    with AsyncFrameEngine(plan=plan, max_batch=3, batch_window_ms=50.0) as eng:
        [f.result(timeout=300) for f in [eng.submit(x) for x in frames]]
        st = eng.stats()
        assert seen and set(seen) == {np.dtype(np.uint8)}
        assert st.level_dispatches == st.dispatches == len(seen)
        assert st.as_dict()["level_dispatches"] == st.dispatches
        # float32 frames take GC's HIGHEST path and are not counted
        [f.result(timeout=300) for f in
         [eng.submit(x.astype(np.float32)) for x in frames[:2]]]
        after = eng.stats()
        assert seen[-1] == np.dtype(np.float32)
        assert after.dispatches > st.dispatches
        assert after.level_dispatches == st.level_dispatches


def test_packer_hands_the_plan_uint8_rows_and_counts_them(monkeypatch):
    seen = _recording(monkeypatch)
    plan = BGPlan(cfg=CFGS[4], backend="fused", quantize_output=True,
                  interpret=True)
    packer = MultiStreamPacker(plan=plan)
    sids = ("a", "b")
    for s in sids:
        packer.open(s, alpha=0.6)
    frames = {s: _frames_u8(2, seed=30 + i) for i, s in enumerate(sids)}
    with AsyncFrameEngine(packer=packer, max_batch=2, batch_window_ms=50.0) as eng:
        for t in range(2):  # a first pack, then a warm one
            [f.result(timeout=300) for f in
             [eng.submit(frames[s][t], stream_id=s) for s in sids]]
        st = eng.stats()
    assert seen and set(seen) == {np.dtype(np.uint8)}
    assert packer.level_dispatches == st.dispatches == 2
    assert st.level_dispatches == 2


def test_level_dispatches_merge_and_default():
    parts = [
        EngineStats(1, 1, 1, 0, 0, 0, 1.0, 1.0, 1.0, level_dispatches=k)
        for k in (2, 5)
    ]
    assert EngineStats.merge(parts).level_dispatches == 7
    d = parts[0].as_dict()
    d.pop("level_dispatches")
    assert EngineStats(**d).level_dispatches == 0
