"""Fused GC||GF||TI Pallas kernel — the paper's macro-pipeline on a TPU,
batched over frames.

The FPGA's headline trick (Fig. 4) is that grid creation of stripe x, the
Gaussian filter of plane x-1 and the trilinear slice of stripe x-2 run
*concurrently* over a working set of three raw planes + two blurred planes +
an r-line buffer. Here the same dataflow becomes a single `pallas_call` whose
sequential grid dimension is the stripe index and whose VMEM scratch is
exactly that working set:

  step s:   GC(stripe s)  ->  completes raw plane s        (scratch R*)
            GF(plane s-1) <-  raw planes s-2, s-1, s       (scratch B1)
            TI(stripe s-2) <- blurred planes s-2, s-1      (line buf S*)

Throughput path — the `(batch, stripe)` grid layout
---------------------------------------------------
`bg_fused_kernel_call` accepts a single `(h, w)` frame or a `(b, h, w)` batch.
Batches run through a 2-D grid `(num_batch_tiles, n_stripes + 2)`; the stripe
dimension is minor (innermost), so for each batch tile the kernel sweeps all
stripes before advancing to the next tile. Each step's block covers
`batch_tile` frames, i.e. every per-step tensor gains a leading frame axis and
the GC / TI contractions become larger, MXU-friendlier matmuls:

  * GC: the `(bt, r, w, gz)` one-hot z-reduction for *both* homogeneous
    channels and *all* stripe rows is a single `(bt*2*r*gz, w) x (w, gy)`
    contraction (one dot instead of four), followed by a static row-split sum
    onto planes s / s+1.
  * TI: the four per-corner y-gather matmuls collapse into one
    `(2*bt*gz, gy) x (gy, 2*w)` contraction against the stacked floor/ceil
    column one-hots; the x/y lerp weights are folded before the z contraction.

Per-batch scratch reset: the working set in VMEM persists across grid steps,
so the kernel re-zeroes all six scratch buffers at stripe 0 of every batch
tile (`pl.when(s == 0)`) — frames in different tiles never mix, and a batch
never round-trips the grid through HBM. Constant operands (column one-hots,
interpolation fractions) are passed once and shared by every frame, unlike an
outer `vmap`, which would replicate them per frame.

Streaming input path — explicit double-buffered HBM->VMEM DMA
-------------------------------------------------------------
``stream_input=True`` replaces Pallas's automatic input pipelining with an
explicit two-slot DMA pipeline: the image stays in HBM (`pl.ANY` operand,
laid out `(tiles, stripes, bt, rp, w)`, each stripe zero-padded from r to a
whole number rp of sublane tiles so every DMA window is tile-aligned) and
the kernel prefetches stripe s+1
into slot `(s+1) % 2` with `pltpu.make_async_copy` while computing stripe s
from slot `s % 2`. The validity mask is not streamed at all — it is
synthesized in-kernel from the frame/row counters (the FPGA's counter logic),
so the stream path reads *half* the HBM bytes of the default path and its
input VMEM footprint is exactly `2 * bt * r * w` floats, independent of the
automatic-pipelining heuristics. VMEM slot accounting per batch tile:

  default:  2x img block + 2x msk block + 2x out block   (auto pipelining)
  stream:   2x img slot  +           0 + 2x out block    (manual DMA)

This is ROADMAP's "double-buffered HBM->VMEM streaming" item: full-HD/4K
stripe blocks whose doubled (img + msk) blocks would blow the automatic
budget still run, because the only input VMEM the kernel asks for is the two
slots it manages itself. Both paths share the same compute body
(`_pipeline_step`) and are bit-identical (asserted in tests).

HBM traffic is therefore one image read + one image write + nothing else —
the grid never leaves VMEM, which is the paper's "low memory footprint"
property translated to the TPU memory hierarchy. Output stripes are written
through the revisited output block (last write wins for the warm-up steps).

Temporal path — the in-kernel grid EMA (video warm path)
--------------------------------------------------------
``carry=`` + ``alpha=`` grow the same kernel into the one-kernel *video*
warm path: the per-stream temporal state is the blurred homogeneous grid
(``(b, gx, gy, gz, 2)``, see ``repro.video.temporal``), and the recursive
EMA ``G_t = (1 - a) * blur(create(f_t)) + a * G_{t-1}`` is applied plane by
plane inside the macro-pipeline, in VMEM, right where GF finishes each
plane:

  step s:   GC(stripe s)    ->  raw plane s complete
            GF(plane s-1)   ->  B = blurred homogeneous plane s-1
            EMA(plane s-1)  ->  B' = (1-a)*B + a*C[s-1]   (C = carry operand)
                                C'[s-1] <- B'             (carry output)
            TI(stripe s-2)  <-  normalize(B') planes s-2, s-1

so the grid still never round-trips HBM mid-frame: the warm path keeps the
one-image-read/one-image-write traffic and adds only the grid-sized carry
(two to three orders of magnitude smaller than the frame) as an extra
input + output. ``alpha`` is a per-frame ``(bt, 1, 1, 1)`` VMEM block, so
one dispatch freely mixes warm streams (``a > 0``), cold streams and
first-frame streams (``a == 0``) — an ``a == 0`` frame's blend is the exact
float identity ``1.0*B + 0.0*C == B``, making its output *bit-identical* to
the non-temporal path no matter which streams share the batch (asserted in
tests/test_temporal_fused.py). When ``h % r == 0`` the temporal grid runs
one extra drain step so the last carry plane (``gx - 1``, which TI never
reads but the EMA recursion must still advance) is produced; TI output
writes are masked off for that step.

Block layout (what Mosaic accepts): frames travel as ``(b, n, r, w)`` —
the ``(b, h, w)`` array split into n stripes of r rows — and every image,
mask and output block is ``(bt, <stripe>, r, w)`` with the stripe axis
squeezed, so the last two block dims are whole array dims for any r (Mosaic
requires the second-minor block dim to be a multiple of 8 or the whole dim).
Every constant operand is built at the rank the kernel uses it at, and the
GF blur shifts with zero slabs instead of a scatter.

Paper normalization mode (eq. 4) only; r*gz is bounded (see common.py), so
per-step temporaries are O(bt*r*gz*w) — a few MB for full-HD frames at the
default batch tile.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .common import (
    BGConfig,
    conv3_axis,
    default_interpret,
    gc_col_onehot,
    gc_row_split,
    grid_shape,
    taps_np,
    ti_col_onehots,
)

__all__ = ["bg_fused_kernel_call", "bg_fused_impl", "DEFAULT_BATCH_TILE"]

# Frames per grid step. Bounded so the per-step working set (one-hot
# z-reductions + two raw-plane stripes per frame) stays well under VMEM for
# full-HD rows; raise per-call via `batch_tile=` on smaller frames.
DEFAULT_BATCH_TILE = 4


def _pipeline_step(
    px,
    msk,
    col_oh,
    y_oh,
    yf,
    xf,
    out_ref,
    r2_s,
    r1_s,
    apart_s,
    b1_s,
    s2_s,
    s1_s,
    *,
    taps,
    inv_rs,
    gz,
    split,
    blend=None,
    ti_valid=None,
):
    """One macro-pipeline advance: GC(s) || GF(s-1) || TI(s-2).

    ``px``/``msk`` are the current (bt, r, w) stripe block however it was
    acquired (blocked operand or DMA slot) — everything downstream is
    identical between the two input paths, which is what makes them
    bit-equivalent.

    ``blend`` is the temporal hook: a ``(carry_plane, a, carry_out_ref)``
    triple that EMA-blends the freshly blurred homogeneous plane with the
    carry plane (``B' = (1-a)*B + a*C``) before TI normalizes it, and stores
    the blended plane as the new carry. The blend runs on *every* step —
    the temporal path's extra drain step exists precisely to blend the last
    carry plane after TI is done. ``ti_valid`` masks the TI output write on
    that drain step (``None`` = always write, keeping the non-temporal
    jaxpr unchanged).

    Storage precision: the scratch refs' dtype is the plan's *storage*
    dtype (fp32 or bf16 — ``bg_fused_impl`` allocates them). Every scratch
    read upcasts to fp32, every write downcasts to the ref dtype, the big
    per-step stacks (GC one-hot z-stack, TI z-weights, the stacked blurred
    planes) are materialized in the storage dtype, and both contractions
    pin ``preferred_element_type=float32`` — bf16 operands, fp32
    accumulation. On fp32 scratch every one of these casts is a same-dtype
    no-op, so the fp32 jaxpr is byte-for-byte the pre-precision one.
    """
    sdt = r2_s.dtype  # the storage dtype (scratch allocation decides)
    # ---- GC: one dense one-hot z-reduction for all frames, rows and both
    # homogeneous channels at once, then a static row split onto planes
    # s / s+1 (rows [0, split) land on plane s, the rest on s+1). The one-hot
    # is materialized with w minor so the column contraction needs no
    # transposition of the large operand.
    zbin = jnp.floor(px * inv_rs + 0.5).astype(jnp.int32)
    zi = jax.lax.broadcasted_iota(jnp.int32, zbin.shape[:2] + (gz, zbin.shape[2]), 2)
    eq = zbin[:, :, None, :] == zi  # (bt, r, gz, w)
    # select (mask, masked-intensity) directly through the one-hot predicate:
    # cheaper than materializing the 0/1 one-hot and multiplying twice
    ohz = jnp.where(eq, msk[:, :, None, :], 0.0)
    both = jnp.stack(
        [ohz, jnp.where(eq, (px * msk)[:, :, None, :], 0.0)], axis=1
    ).astype(sdt)  # (bt, 2, r, gz, w) — storage dtype (the dominant stack)
    zgi = jnp.einsum(
        "bcizw,wg->bcizg", both, col_oh,
        preferred_element_type=jnp.float32,
        # fp32 storage: HIGHEST keeps fp32 intensities exact on the MXU;
        # bf16 operands are exact at the default (and refuse HIGHEST)
        precision=jax.lax.Precision.HIGHEST if sdt == jnp.float32 else None,
    )  # one matmul, not four; fp32 accumulation
    contrib_cur = zgi[:, :, :split].sum(axis=2)  # (bt, 2, gz, gy) -> plane s
    contrib_next = zgi[:, :, split:].sum(axis=2)  # -> plane s+1

    r2 = r2_s[...].astype(jnp.float32)
    r1 = r1_s[...].astype(jnp.float32)
    r0 = apart_s[...].astype(jnp.float32) + contrib_cur  # raw plane s complete

    # ---- GF of plane s-1 (both homogeneous channels, one pass)
    mix = taps[0] * r2 + taps[1] * r1 + taps[2] * r0  # x axis (stripe index)
    mix = conv3_axis(mix, taps, 2)  # z axis (scratch layout (bt, 2, gz, gy))
    mix = conv3_axis(mix, taps, 3)  # y axis
    if blend is not None:
        # ---- temporal EMA of the blurred homogeneous plane, in VMEM.
        # a == 0 frames reduce to the exact float identity 1*mix + 0*carry
        # == mix (all operands are finite and non-negative, with or without
        # FMA contraction), which is what makes the cold rows of a mixed pack
        # bit-identical to the non-temporal kernel.
        # For a > 0 the compiler may contract the blend into an FMA
        # differently per dispatch geometry (batch tile, mesh shard, and in
        # interpret mode per XLA fusion), so warm rows are reproducible only
        # within one geometry: across geometries the carry and the
        # unquantized image agree to a few ulp, and the quantized image
        # agrees bitwise except where a value sits on a rounding edge.
        carry_plane, a, carry_out_ref = blend
        mix = (1.0 - a) * mix + a * carry_plane
        carry_out_ref[0, 0] = mix.astype(carry_out_ref.dtype)
    b_new = jnp.where(
        mix[:, 0] > 1e-12, mix[:, 1] / jnp.maximum(mix[:, 0], 1e-12), 0.0
    )  # (bt, gz, gy)

    # ---- TI of stripe s-2 against blurred planes s-2 (b1) and s-1 (b_new)
    spx = s2_s[...].astype(jnp.float32)  # (bt, r, w)
    fz = spx * inv_rs
    z0 = jnp.floor(fz).astype(jnp.int32)
    zfr = fz - z0.astype(jnp.float32)
    zi2 = jax.lax.broadcasted_iota(jnp.int32, z0.shape[:2] + (gz, z0.shape[2]), 2)
    wz = (
        jnp.where(z0[:, :, None, :] == zi2, 1.0, 0.0) * (1.0 - zfr)[:, :, None, :]
        + jnp.where((z0 + 1)[:, :, None, :] == zi2, 1.0, 0.0) * zfr[:, :, None, :]
    ).astype(sdt)  # (bt, r, gz, w) — storage dtype (the other big stack)
    # Both planes are rounded to the storage dtype (plane s-1 is read back
    # from storage on the next step) but the stack itself is fp32: Mosaic
    # cannot fold a packed bf16 (2, bt, gz, gy) into matmul rows when gz is
    # not a multiple of 16, and a one-hot gather of bf16 values is exact in
    # fp32, so this keeps the bf16 numerics bit for bit.
    planes = jnp.stack(
        [b1_s[...].astype(jnp.float32), b_new.astype(sdt).astype(jnp.float32)],
        axis=0,
    )  # (2, bt, gz, gy)
    # all four y-corner gathers in one contraction over gy (minor on both
    # operands: no transposition of the planes). HIGHEST keeps the fp32
    # plane values exact on the MXU (the default would round them to bf16).
    gathered = jnp.einsum(
        "pbzg,cwg->pbzcw", planes, y_oh,
        preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,
    )  # (2, bt, gz, 2, w)
    # fold the x/y lerp weights before the z contraction (linearity)
    wy = gathered[:, :, :, 0] * (1.0 - yf) + gathered[:, :, :, 1] * yf
    q = (
        wy[0][:, None] * (1.0 - xf) + wy[1][:, None] * xf
    )  # (bt, r, gz, w); xf is (1, r, 1, 1)
    sliced = jnp.sum(wz.astype(jnp.float32) * q, axis=2)
    if ti_valid is None:
        out_ref[...] = sliced.astype(out_ref.dtype)
    else:
        # temporal drain step (h % r == 0 only): the revisited out block
        # keeps its previous (correct) content when the write is skipped
        @pl.when(ti_valid)
        def _write():
            out_ref[...] = sliced.astype(out_ref.dtype)

    # ---- rotate the working set (the macro-pipeline advance)
    r2_s[...] = r1.astype(sdt)
    r1_s[...] = r0.astype(sdt)
    apart_s[...] = contrib_next.astype(sdt)
    b1_s[...] = b_new.astype(sdt)
    s2_s[...] = s1_s[...]
    s1_s[...] = px.astype(sdt)


def _reset_working_set(r2_s, r1_s, apart_s, b1_s, s2_s, s1_s):
    # Fresh working set at stripe 0 of every batch tile: scratch persists
    # across grid steps, and without this reset frames of tile t would
    # blend into the warm-up stripes of tile t+1.
    r2_s[...] = jnp.zeros_like(r2_s)
    r1_s[...] = jnp.zeros_like(r1_s)
    apart_s[...] = jnp.zeros_like(apart_s)
    b1_s[...] = jnp.zeros_like(b1_s)
    s2_s[...] = jnp.zeros_like(s2_s)
    s1_s[...] = jnp.zeros_like(s1_s)


def _kernel(
    img_ref,
    msk_ref,
    col_ref,
    yoh_ref,
    yf_ref,
    xf_ref,
    *rest,
    taps,
    inv_rs,
    gz,
    split,
    n_stripes,
    temporal=False,
):
    s = pl.program_id(1)  # stripe index (minor grid dim; program_id(0) = tile)
    if temporal:
        # extra operands: carry plane (blocked (1, 1, bt, 2, gz, gy)) and the
        # per-frame alpha, a (bt, 1, 1, 1) VMEM block already at the rank it
        # broadcasts at; extra output: the blended plane written back as the
        # new carry.
        carry_ref, alpha_ref, out_ref, carry_out_ref, *scratch = rest
        blend = (carry_ref[0, 0].astype(jnp.float32), alpha_ref[...], carry_out_ref)
        ti_valid = s < n_stripes + 2  # mask TI on the extra carry drain step
    else:
        out_ref, *scratch = rest
        blend = None
        ti_valid = None
    r2_s, r1_s, apart_s, b1_s, s2_s, s1_s = scratch

    @pl.when(s == 0)
    def _init():
        _reset_working_set(r2_s, r1_s, apart_s, b1_s, s2_s, s1_s)

    px = img_ref[...].astype(jnp.float32)  # (bt, r, w)
    live = jnp.where(s < n_stripes, 1.0, 0.0)
    msk = msk_ref[...].astype(jnp.float32) * live
    _pipeline_step(
        px,
        msk,
        col_ref[...],
        yoh_ref[...],
        yf_ref[...],
        xf_ref[...],
        out_ref,
        r2_s,
        r1_s,
        apart_s,
        b1_s,
        s2_s,
        s1_s,
        taps=taps,
        inv_rs=inv_rs,
        gz=gz,
        split=split,
        blend=blend,
        ti_valid=ti_valid,
    )


def _stream_kernel(
    img_hbm,
    col_ref,
    yoh_ref,
    yf_ref,
    xf_ref,
    out_ref,
    r2_s,
    r1_s,
    apart_s,
    b1_s,
    s2_s,
    s1_s,
    px_slots,
    dma_sems,
    *,
    taps,
    inv_rs,
    gz,
    split,
    n_stripes,
    bt,
    r,
    b,
    h,
):
    """Double-buffered variant: ``img_hbm`` is the full (nb, n, bt, rp, w)
    image in HBM (stripes zero-padded from r to rp rows); stripe blocks are
    DMA'd into the two ``px_slots`` with the next stripe in flight while the
    current one computes."""
    bi = pl.program_id(0)
    s = pl.program_id(1)
    slot = jax.lax.rem(s, 2)
    # steps s >= n_stripes are TI drain steps: re-fetch the last stripe (its
    # pixels are dead — masked out of GC, never read back by TI)
    sidx = jnp.minimum(s, n_stripes - 1)

    def stripe_dma(step, slot_idx):
        return pltpu.make_async_copy(
            img_hbm.at[bi, jnp.minimum(step, n_stripes - 1)],
            px_slots.at[slot_idx],
            dma_sems.at[slot_idx],
        )

    @pl.when(s == 0)
    def _init():
        _reset_working_set(r2_s, r1_s, apart_s, b1_s, s2_s, s1_s)
        # tile warm-up: nothing in flight yet, fetch stripe 0 synchronously
        stripe_dma(0, 0).start()

    stripe_dma(s, slot).wait()

    @pl.when(s + 1 < n_stripes + 2)
    def _prefetch():
        # overlap: stripe s+1 streams in while stripe s computes below
        stripe_dma(s + 1, jax.lax.rem(s + 1, 2)).start()

    px = px_slots[slot, :, :r, :].astype(jnp.float32)  # drop the row padding
    # The validity mask is never streamed: synthesize it from the frame/row
    # counters (padding frames of the last tile and padding rows of the last
    # stripe are 0, drain steps are 0 via `live`) — identical values to the
    # default path's msk operand.
    live = jnp.where(s < n_stripes, 1.0, 0.0)
    fidx = jax.lax.broadcasted_iota(jnp.int32, (bt, r, px.shape[2]), 0)
    ridx = jax.lax.broadcasted_iota(jnp.int32, (bt, r, px.shape[2]), 1)
    msk = jnp.where((bi * bt + fidx < b) & (sidx * r + ridx < h), 1.0, 0.0) * live
    _pipeline_step(
        px,
        msk,
        col_ref[...],
        yoh_ref[...],
        yf_ref[...],
        xf_ref[...],
        out_ref,
        r2_s,
        r1_s,
        apart_s,
        b1_s,
        s2_s,
        s1_s,
        taps=taps,
        inv_rs=inv_rs,
        gz=gz,
        split=split,
    )


def bg_fused_impl(
    image: jnp.ndarray,
    cfg: BGConfig,
    interpret: bool | None = None,
    batch_tile: int | None = None,
    stream_input: bool = False,
    carry: jnp.ndarray | None = None,
    alpha: jnp.ndarray | None = None,
    precision: str = "fp32",
):
    """Fused BG pipeline, single frame or batch, optionally temporal.

    (h, w) -> (h, w); (b, h, w) -> (b, h, w), in the storage dtype (float32
    for ``precision="fp32"``, bfloat16 for ``"bf16"`` — the plan layer
    upcasts image output back to float32). A single frame is exactly the
    b == 1 batch (same kernel, bit-identical output). Matches ref.ref_fused
    per frame (paper normalization, unquantized).

    ``precision="bf16"`` flips every storage surface — padded input, mask,
    the six scratch buffers, the DMA stripe slots, the temporal carry blocks
    and both outputs — to bfloat16 while the compute body accumulates fp32
    (see ``_pipeline_step``); the fp32 path's jaxpr is unchanged.

    ``batch_tile`` caps frames per grid step (clamped to b; default
    ``DEFAULT_BATCH_TILE``). Batches not divisible by the tile are padded
    with zero frames that are masked out of GC and dropped from the output.

    ``stream_input=True`` keeps the image in HBM and double-buffers stripe
    blocks into VMEM with explicit async copies (prefetching stripe s+1 while
    computing stripe s) instead of relying on Pallas's automatic input
    pipelining — see the module docstring. Bit-identical to the default path.

    ``carry`` + ``alpha`` select the temporal path (see module docstring):
    ``carry`` is the ``(b, gx, gy, gz, 2)`` stacked blurred-grid EMA state
    (one row per frame/stream), ``alpha`` the length-``b`` per-frame blend
    weights; the call then returns ``(out, new_carry)`` instead of ``out``.
    Frames with ``alpha == 0`` are bit-identical to the non-temporal call,
    and their new-carry row is exactly the frame's own blurred grid.
    """
    if interpret is None:
        interpret = default_interpret()
    if precision not in ("fp32", "bf16"):
        raise ValueError(
            f"precision must be 'fp32' or 'bf16', got {precision!r}"
        )
    sdt = jnp.bfloat16 if precision == "bf16" else jnp.float32
    if batch_tile is not None and (
        isinstance(batch_tile, bool)
        or not isinstance(batch_tile, int)
        or batch_tile < 1
    ):
        # reject here too (not only at BGPlan construction): a fractional or
        # non-positive tile would otherwise surface as an opaque Pallas grid
        # error deep inside the lowering
        raise ValueError(
            f"batch_tile must be a positive int or None, got {batch_tile!r}"
        )
    temporal = carry is not None
    if temporal and stream_input:
        raise ValueError("stream_input does not compose with a temporal carry")
    if temporal != (alpha is not None):
        raise ValueError("temporal path needs both carry= and alpha= (or neither)")
    squeeze = image.ndim == 2
    if squeeze:
        image = image[None]
        if temporal:
            carry = carry[None]
            alpha = jnp.reshape(alpha, (1,))
    b, h, w = image.shape
    r = cfg.r
    gx, gy, gz = grid_shape(h, w, cfg)
    n = -(-h // r)
    hp = n * r
    bt = DEFAULT_BATCH_TILE if batch_tile is None else batch_tile
    bt = max(1, min(bt, b))
    nb = -(-b // bt)
    bp = nb * bt
    # (bp, hp, w) -> (bp, n, r, w): every block is then (bt, <stripe>, r, w)
    # with the stripe axis squeezed, so the last two block dims are whole
    # array dims — what Mosaic requires for any r, not only multiples of 8.
    # named stages for a profiler trace: bg.relayout before the kernel,
    # bg.kernel, bg.trim after it (the module docstring's layout notes)
    relayout = functools.partial(jax.named_scope, "bg.relayout")
    with relayout():
        img_p = jnp.pad(
            image.astype(jnp.float32), ((0, bp - b), (0, hp - h), (0, 0))
        ).astype(sdt).reshape(bp, n, r, w)

    oh0, oh1, yf = ti_col_onehots(w, gy, r)
    taps = tuple(float(t) for t in taps_np(cfg))
    const = lambda shape: pl.BlockSpec(shape, lambda bi, s: tuple(0 for _ in shape))
    stripe_spec = lambda stripe: pl.BlockSpec(
        (bt, None, r, w), lambda bi, s: (bi, stripe(s), 0, 0)
    )
    in_stripe = lambda s: jnp.minimum(s, n - 1)  # GC reads stripe s
    out_stripe = lambda s: jnp.clip(s - 2, 0, n - 1)  # TI writes stripe s-2
    # the GC one-hot travels in the storage dtype (its 0/1 entries are exact
    # in bf16); the TI one-hots meet fp32 planes and the lerp fractions stay
    # fp32. Every constant is built at the rank the kernel uses it at.
    with relayout():
        consts = (
            jnp.asarray(gc_col_onehot(w, gy, r)).astype(sdt),
            jnp.asarray(np.stack([oh0, oh1])),
            jnp.asarray(yf)[None],
            jnp.asarray((np.arange(r) / r).astype(np.float32)).reshape(1, r, 1, 1),
        )
    const_specs = [
        const((w, gy)), const((2, w, gy)), const((1, w)), const((1, r, 1, 1))
    ]
    scratch = [
        pltpu.VMEM((bt, 2, gz, gy), sdt),  # raw plane s-2
        pltpu.VMEM((bt, 2, gz, gy), sdt),  # raw plane s-1
        pltpu.VMEM((bt, 2, gz, gy), sdt),  # partial plane s(+1)
        pltpu.VMEM((bt, gz, gy), sdt),  # blurred plane s-2
        pltpu.VMEM((bt, r, w), sdt),  # line buffer stripe s-2
        pltpu.VMEM((bt, r, w), sdt),  # line buffer stripe s-1
    ]
    out_shape = jax.ShapeDtypeStruct((bp, n, r, w), sdt)
    kw = dict(taps=taps, inv_rs=1.0 / cfg.range_scale, gz=gz,
              split=gc_row_split(r), n_stripes=n)

    if temporal:
        if carry.shape != (b, gx, gy, gz, 2):
            raise ValueError(
                f"carry shape {carry.shape} != {(b, gx, gy, gz, 2)} for "
                f"{(b, h, w)} frames"
            )
        if alpha.shape != (b,):
            raise ValueError(f"alpha shape {alpha.shape} != ({b},)")
        # (b, gx, gy, gz, 2) -> (nb, gx, bt, 2, gz, gy): plane-major with the
        # kernel's scratch layout minor, so one block index names the whole
        # (bt, 2, gz, gy) plane the EMA touches at step s.
        with relayout():
            carry_p = jnp.pad(
                carry.astype(sdt), ((0, bp - b),) + ((0, 0),) * 4
            )
            ck = carry_p.transpose(1, 0, 4, 3, 2)  # (gx, bp, 2, gz, gy)
            ck = ck.reshape(gx, nb, bt, 2, gz, gy).swapaxes(0, 1)
            alpha_p = jnp.pad(alpha.astype(jnp.float32), (0, bp - b)).reshape(
                bp, 1, 1, 1
            )
            msk_p = jnp.pad(
                jnp.ones((b, h, w), sdt), ((0, bp - b), (0, hp - h), (0, 0))
            ).reshape(bp, n, r, w)
        # blurred plane p completes (and its carry blend lands) at step
        # s = p + 1, so emitting all gx carry planes takes gx + 1 steps:
        # for ragged h that is the usual n + 2, for h % r == 0 it is one
        # extra drain step whose TI write is masked off in the kernel.
        plane_idx = lambda bi, s: (bi, jnp.clip(s - 1, 0, gx - 1), 0, 0, 0, 0)
        carry_spec = pl.BlockSpec((1, 1, bt, 2, gz, gy), plane_idx)
        with jax.named_scope("bg.kernel"):
            out, ck_new = pl.pallas_call(
                functools.partial(_kernel, temporal=True, **kw),
                grid=(nb, gx + 1),
                in_specs=[stripe_spec(in_stripe), stripe_spec(in_stripe)]
                + const_specs
                + [
                    carry_spec,
                    pl.BlockSpec((bt, 1, 1, 1), lambda bi, s: (bi, 0, 0, 0)),
                ],
                out_specs=[stripe_spec(out_stripe), carry_spec],
                out_shape=[
                    out_shape,
                    jax.ShapeDtypeStruct((nb, gx, bt, 2, gz, gy), sdt),
                ],
                scratch_shapes=scratch,
                interpret=interpret,
                name="bg_fused_temporal",
            )(img_p, msk_p, *consts, ck, alpha_p)
        with jax.named_scope("bg.trim"):
            new_carry = (
                ck_new.swapaxes(0, 1)
                .reshape(gx, bp, 2, gz, gy)
                .transpose(1, 0, 4, 3, 2)[:b]
            )
            out = out.reshape(bp, hp, w)[:b, :h]
            if squeeze:
                return out[0], new_carry[0]
            return out, new_carry

    if stream_input:
        # (bp, n, r, w) -> (nb, n, bt, rp, w): tile/stripe major, so one DMA
        # descriptor (.at[tile, stripe]) names a whole (bt, rp, w) block.
        # Stripes are zero-padded to rp rows, a whole number of the storage
        # dtype's sublane tiles: Mosaic refuses a DMA window over a tiled
        # array whose second-minor dim is not tile-aligned, and HBM holds
        # the padded rows physically either way.
        tile_rows = 32 // jnp.dtype(sdt).itemsize  # 8 fp32, 16 bf16
        rp = -(-r // tile_rows) * tile_rows
        with relayout():
            img_t = jnp.pad(
                img_p.reshape(nb, bt, n, r, w).swapaxes(1, 2),
                ((0, 0),) * 3 + ((0, rp - r), (0, 0)),
            )
        with jax.named_scope("bg.kernel"):
            out = pl.pallas_call(
                functools.partial(_stream_kernel, bt=bt, r=r, b=b, h=h, **kw),
                grid=(nb, n + 2),
                in_specs=[pl.BlockSpec(memory_space=pl.ANY)] + const_specs,
                out_specs=stripe_spec(out_stripe),
                out_shape=out_shape,
                scratch_shapes=scratch
                + [
                    pltpu.VMEM((2, bt, rp, w), sdt),  # DMA stripe slots
                    pltpu.SemaphoreType.DMA((2,)),  # per-slot completion
                ],
                interpret=interpret,
                name="bg_fused_streamed",
            )(img_t, *consts)
    else:
        with relayout():
            msk_p = jnp.pad(
                jnp.ones((b, h, w), sdt), ((0, bp - b), (0, hp - h), (0, 0))
            ).reshape(bp, n, r, w)
        with jax.named_scope("bg.kernel"):
            out = pl.pallas_call(
                functools.partial(_kernel, **kw),
                grid=(nb, n + 2),
                in_specs=[stripe_spec(in_stripe), stripe_spec(in_stripe)]
                + const_specs,
                out_specs=stripe_spec(out_stripe),
                out_shape=out_shape,
                scratch_shapes=scratch,
                interpret=interpret,
                name="bg_fused",
            )(img_p, msk_p, *consts)
    with jax.named_scope("bg.trim"):
        out = out.reshape(bp, hp, w)[:b, :h]
        return out[0] if squeeze else out


# The public jitted entry point. ``bg_fused_impl`` stays importable unjitted
# so the plan layer (repro.plan) can trace it inside its own single
# compiled executable — a nested pjit call costs ~10% extra dispatch time
# per micro-batch in interpret mode, measured at the video-gate shape.
bg_fused_kernel_call = functools.partial(
    jax.jit,
    static_argnames=(
        "cfg", "interpret", "batch_tile", "stream_input", "precision"
    ),
)(bg_fused_impl)
