"""Per-stream sessions + the multi-stream packer.

A video service handles N concurrent streams, each an ordered frame sequence
with its own temporal state. The throughput lever from PR 1/2 is batching —
one dispatch per micro-batch — so the packer turns "one frame from each live
stream" into exactly that: frames stack on a leading stream axis, the
per-stream blurred-grid carries stack into one ``(n, gx, gy, gz, 2)`` array,
and a per-stream alpha vector lets warm streams (``a_s``), cold streams and
first-frame streams (forced ``a = 0``) share the dispatch. Temporal state
never crosses streams: row i of the stacked carry is read and written only
by stream i (asserted in tests/test_video.py).

Every pack is **one dispatch**. The temporal EMA now runs inside the fused
kernel (``bg_fused_kernel_call(carry=, alpha=)``), where an ``a == 0`` row's
blend is the exact float identity — so cold streams stay bit-identical to
the per-frame fused service *no matter which warm streams share the
micro-batch* (batch composition is timing-dependent under the async engine),
without the two-dispatch cold/warm split this packer needed while the warm
path lived on the staged jnp pipeline. A pack whose streams are all cold
(no session holds a carry, every alpha is 0) short-circuits to the carry-free
per-frame path and never materializes temporal state at all. The packer
holds one :class:`repro.plan.BGPlan` (from ``plan_for(..., temporal=True)``)
and derives each pack's temporal or per-frame form and tile from it.

Reliability (PR 6): :meth:`MultiStreamPacker.pack_guarded` is ``pack`` plus
a :class:`repro.reliability.DispatchGuard` — lazy per-row ``jnp.isfinite``
flags over the pack's outputs and advanced carries, launched with the
dispatch and realized by the engine at completion. A bad carry row is the
EMA-poisoning signature (one NaN frame contaminates the stream's history
forever); :meth:`MultiStreamPacker.quarantine` is the cure — reset the
stream's carry to cold so the next pack re-warms it through the standard
first-frame effective-alpha-0 path. ``pack_guarded(plan=...)`` dispatches an
alternate plan (a fallback-ladder rung) without rebinding the packer.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Hashable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.bilateral_grid import BGConfig

from .temporal import carry_shape, temporal_denoise

__all__ = ["StreamSession", "MultiStreamPacker"]


@dataclasses.dataclass
class StreamSession:
    """State of one live video stream.

    ``carry`` is ``None`` until the stream's first temporal frame has been
    packed (and stays ``None`` forever for ``alpha == 0`` streams — the
    per-frame path needs no history).
    """

    sid: Hashable
    alpha: float = 0.0
    carry: Optional[jnp.ndarray] = None
    frames_seen: int = 0

    def __post_init__(self):
        if not 0.0 <= self.alpha < 1.0:
            raise ValueError(f"stream {self.sid!r}: alpha must be in [0, 1)")


class MultiStreamPacker:
    """Batches one frame per live stream into a single temporal dispatch.

    Dispatch is plan-driven: construct with a ``repro.plan.BGPlan`` (e.g.
    from ``repro.plan.plan_for(..., temporal=True)``, which auto-tunes the
    fused-kernel batch tile from frame geometry) and the packer asks the
    plan for its tile per pack.
    """

    def __init__(self, plan):
        if plan.backend == "fused_streamed":
            # rejected once, here, instead of failing the first warm pack's
            # as_temporal(True) mid-service: the manual-DMA input path does
            # not compose with the temporal carry, and pack composition
            # (cold vs warm) is timing-dependent under the async engine
            raise ValueError(
                "MultiStreamPacker needs a temporal-capable plan; "
                "backend='fused_streamed' cannot carry the grid EMA — use "
                "plan_for(..., temporal=True) (backend='fused')"
            )
        self.plan = plan
        self.sessions: Dict[Hashable, StreamSession] = {}
        self.carry_resets = 0    # lifetime count of quarantined carries
        self.carry_restores = 0  # lifetime count of snapshot-restored carries
        self.host_casts = 0      # lifetime count of frames converted on the host
        self.level_dispatches = 0  # lifetime count of packs stacked as 8-bit levels
        self.level_answers = 0  # lifetime count of answers split off as uint8

    @property
    def cfg(self) -> BGConfig:
        return self.plan.cfg

    # ------------------------------------------------------------- streams
    def open(self, sid: Hashable, alpha: float = 0.0) -> StreamSession:
        if sid in self.sessions:
            raise ValueError(f"stream {sid!r} already open")
        sess = StreamSession(sid=sid, alpha=float(alpha))
        self.sessions[sid] = sess
        return sess

    def close(self, sid: Hashable) -> None:
        self.sessions.pop(sid)

    def live(self) -> int:
        return len(self.sessions)

    def quarantine(self, sid: Hashable) -> bool:
        """Reset one stream's temporal carry to cold (the PR-3 machinery:
        ``carry=None`` forces effective alpha 0 on the stream's next pack,
        i.e. a standard first-frame warm-up). The cure for a poisoned carry
        — a NaN frame blended into the EMA otherwise contaminates every
        later frame of the stream. Returns True when a carry was actually
        dropped (and counts it in ``carry_resets``); an already-cold or
        unknown stream is a no-op."""
        sess = self.sessions.get(sid)
        if sess is None or sess.carry is None:
            return False
        sess.carry = None
        self.carry_resets += 1
        return True

    # ------------------------------------------------------------ snapshots
    def export_carries(self) -> Dict[Hashable, tuple]:
        """Snapshot every warm stream's temporal state as host data:
        ``{sid: (carry ndarray, alpha, frames_seen)}``. The returned carries
        are materialized numpy copies — safe to ship across a process
        boundary and immune to later in-place session mutation. Cold
        streams are omitted (there is nothing to restore; re-opening cold
        is already lossless)."""
        out: Dict[Hashable, tuple] = {}
        for sid, sess in list(self.sessions.items()):
            if sess.carry is None:
                continue
            out[sid] = (
                # the plan's np storage dtype (fp32 or bf16): a bf16 carry
                # ships as bf16 bytes — half the snapshot wire — and stays
                # bit-exact within the precision mode
                np.asarray(sess.carry, self.plan.np_storage_dtype),
                sess.alpha,
                sess.frames_seen,
            )
        return out

    def restore_carry(
        self,
        sid: Hashable,
        carry,
        *,
        alpha: Optional[float] = None,
        frames_seen: Optional[int] = None,
    ) -> None:
        """Install a snapshotted carry onto an open (cold) stream —
        **all-or-nothing**: every validation runs before any session field
        is assigned, so a bad snapshot (wrong geometry, non-finite values,
        unknown stream) leaves the session exactly as it was (cold), never
        half-restored. The carry must match this packer's grid geometry
        ``(gx, gy, gz, 2)``; a carry produced under a different plan
        geometry is a caller bug (the router checks plan hashes first)."""
        sess = self.sessions.get(sid)
        if sess is None:
            raise KeyError(f"stream {sid!r} not open")
        # within a precision mode this conversion is the identity (bit-exact
        # restore); across modes it is the storage rounding the plan's own
        # kernel would apply on the next blend anyway
        arr = np.asarray(carry, self.plan.np_storage_dtype)
        if arr.ndim != 4 or arr.shape[-1] != 2:
            raise ValueError(
                f"stream {sid!r}: carry must be (gx, gy, gz, 2), "
                f"got shape {arr.shape}"
            )
        if not np.isfinite(arr.astype(np.float32)).all():
            raise ValueError(
                f"stream {sid!r}: refusing to restore a non-finite carry"
            )
        if alpha is not None and not 0.0 <= float(alpha) < 1.0:
            raise ValueError(
                f"stream {sid!r}: restored alpha must be in [0, 1)"
            )
        # validation complete — commit atomically from here down
        sess.carry = jnp.asarray(arr)
        if alpha is not None:
            sess.alpha = float(alpha)
        if frames_seen is not None:
            sess.frames_seen = int(frames_seen)
        self.carry_restores += 1

    # ---------------------------------------------------------------- pack
    def pack(self, frames: Dict[Hashable, jnp.ndarray], *, plan=None) -> Dict[Hashable, jnp.ndarray]:
        """Denoise one frame from each given stream in one batched dispatch.

        ``frames`` maps stream id -> (h, w) frame; every id must be open and
        appear at most once (the temporal recursion is strictly one frame per
        stream per pack — the serving engine defers same-stream repeats to
        the next micro-batch). All frames of a pack share one (h, w): the
        batch axis of the fused kernel (and the stacked carry) needs a single
        static frame shape. Returns stream id -> denoised frame (``uint8``
        levels for a frame of 8-bit integer levels, else float32:
        ``repro.serving.device_stages.answer_dtypes``) and advances each
        stream's carry/counter. ``plan=`` dispatches an alternate base
        plan (a fallback-ladder rung) for this pack only.
        """
        results, _ = self.pack_guarded(frames, plan=plan)
        return results

    def pack_guarded(
        self,
        frames: Dict[Hashable, jnp.ndarray],
        *,
        plan=None,
        carry_limit: Optional[float] = None,
    ):
        """:meth:`pack` plus a ``DispatchGuard`` of lazy finite-flags.

        Returns ``(results, guard)``: ``guard.out_ok`` holds per-row output
        finite flags in ``guard.order`` (the pack's sorted stream-id order)
        and ``guard.carry_ok`` per-stream carry health flags (finite and
        ``|carry| < carry_limit``) for ``guard.carry_sids`` — the streams
        whose temporal carry advanced this pack. Both read the pack's float32
        output and the carries before any answer is cast. The flags are tiny
        ``jnp.isfinite`` reductions launched with the dispatch (they ride
        the same async dataflow; nothing here synchronizes) — the engine
        realizes them with the outputs and quarantines bad carries.

        The pack runs in a ``packer.pack`` profiler span; its device work is
        the named programs of ``repro.serving.device_stages`` (``bg.ingest``,
        ``bg.split``), the guards (``bg.guard``) and the plan executable.
        """
        with jax.profiler.TraceAnnotation("packer.pack", n=len(frames)):
            return self._pack_guarded(frames, plan, carry_limit)

    def _pack_guarded(self, frames, plan, carry_limit):
        from repro.reliability.guards import (
            DEFAULT_CARRY_LIMIT,
            DispatchGuard,
            carry_ok_rows,
            finite_rows,
        )
        from repro.kernels.bg_fused import is_level_dtype
        from repro.serving.device_stages import (
            answer_dtypes, ingest_rows, row_dtype, split_rows, stack_rows,
        )

        if carry_limit is None:
            carry_limit = DEFAULT_CARRY_LIMIT
        if not frames:
            return {}, DispatchGuard()
        missing = [s for s in frames if s not in self.sessions]
        if missing:
            raise KeyError(f"streams not open: {missing!r}")
        sids = sorted(frames, key=repr)
        base = self.plan if plan is None else plan
        dtypes = answer_dtypes(base, [frames[s] for s in sids])
        rows, casts = ingest_rows([frames[s] for s in sids])
        self.host_casts += casts
        shapes = {r.shape for r in rows}
        if len(shapes) != 1 or len(next(iter(shapes))) != 2:
            raise ValueError(f"pack needs equal (h, w) frames, got {sorted(shapes)}")
        sessions = {s: self.sessions[s] for s in sids}
        batch = stack_rows(rows, row_dtype(rows))
        warm = [s for s in sids if sessions[s].alpha > 0.0]
        results = {}
        # the packer asks the plan for this pack's tile (the plan's own
        # auto-tuned value, or the kernel default when the plan defers,
        # clamped to the per-device shard, exactly the clamp the kernel
        # would apply — an explicit plan decision instead of an implicit
        # kernel one)
        plan = base.with_tile(base.tile_for(len(sids)))
        carry_sids = ()
        carry_ok = None

        if not warm:
            # all-cold pack: the carry-free per-frame fused path — nothing
            # temporal is materialized anywhere (temporal_denoise contract)
            out, _ = temporal_denoise(batch, alpha=0.0, plan=plan)
            results = dict(zip(sids, split_rows(out, len(sids), dtypes)))
        else:
            # ONE dispatch for the whole pack: the fused kernel's in-kernel
            # EMA takes a per-stream alpha row, and a == 0 rows (cold
            # streams, first temporal frames) are bit-identical to the
            # per-frame path, so cold and warm streams mix freely.
            h, w = batch.shape[1:]
            if any(sessions[s].carry is None for s in sids):
                zero = jnp.zeros(carry_shape(h, w, self.cfg), plan.storage_dtype)
            carry = stack_rows(
                [zero if sessions[s].carry is None else sessions[s].carry
                 for s in sids],
                plan.storage_dtype,
            )
            # first temporal frame of a stream: no history, blend weight 0
            alpha = np.asarray(
                [sessions[s].alpha if sessions[s].carry is not None else 0.0
                 for s in sids],
                np.float32,
            )
            out, new_carry = temporal_denoise(
                batch, carry=carry, alpha=alpha, plan=plan
            )
            warm_rows = [i for i, s in enumerate(sids) if sessions[s].alpha > 0.0]
            outs = split_rows(out, len(sids), dtypes)
            carries = split_rows(new_carry, len(sids))  # the storage dtype
            for i, s in enumerate(sids):
                results[s] = outs[i]
                if sessions[s].alpha > 0.0:
                    # cold sessions stay carry-free (the per-frame path
                    # needs no history); warm sessions advance their EMA
                    sessions[s].carry = carries[i]
            carry_sids = tuple(sids[i] for i in warm_rows)
            warm_carry = (new_carry if len(warm_rows) == len(sids)
                          else new_carry[jnp.asarray(warm_rows)])
            carry_ok = carry_ok_rows(warm_carry, carry_limit)
        for s in sids:
            sessions[s].frames_seen += 1
        if is_level_dtype(batch.dtype):
            self.level_dispatches += 1
        if dtypes is not None:
            self.level_answers += sum(d is not None for d in dtypes)
        guard = DispatchGuard(
            out_ok=finite_rows(out),
            order=tuple(sids),
            carry_sids=carry_sids,
            carry_ok=carry_ok,
        )
        return results, guard
