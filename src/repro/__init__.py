"""repro: TPU-native bilateral grid (Hashimoto & Takamaeda-Yamazaki 2021)
+ multi-pod JAX LM training/serving framework.

Dispatch-decision table (the plan layer, ``repro.plan``)
--------------------------------------------------------
Every bilateral-grid dispatch takes a compiled :class:`repro.plan.BGPlan`
and calls it: the frame engine (``serving.AsyncFrameEngine(plan=)``), the
video packer and ``video.temporal.temporal_denoise``, the data-pipeline
frontends, the fleet and ``launch.serve``; a caller denoises a stack of
frames with ``plan(frames)``. Which backend fires for which geometry:

  geometry / intent                     backend (plan_for auto-selection)
  -----------------------------------   ---------------------------------
  default service dispatch              "fused" — one GC||GF||TI Pallas
                                        macro-pipeline kernel, grid in VMEM
  16*r*w bytes > 256 KiB (full-HD at    "fused_streamed" — fused kernel +
  paper radii r >= 12, 4K)              explicit 2-slot HBM->VMEM input DMA
                                        (auto-pipelined blocks over budget)
  temporal video pack (alpha > 0)       "fused" + temporal=True (in-kernel
                                        grid-EMA; never input-streamed)
  numerical oracle / gradients          "reference" (vmapped jnp pipeline;
                                        + temporal=True = staged EMA oracle)
  memory-profile studies                "streaming" (lax.scan stripe
                                        pipeline, Fig. 4 dataflow)
  unfused perf baseline (bench only)    "staged" (three Pallas kernels,
                                        grid round-trips HBM)
  >1 local device                       any of fused/fused_streamed/
                                        streaming + mesh (1-D batch-axis
                                        shard_map, zero collectives)

  precision / intent                    storage dtype (BGPlan.precision)
  -----------------------------------   ---------------------------------
  default (precision=None/"fp32")       fp32 end to end — numerics are
                                        never reduced silently
  precision="bf16" (pinned) or          bf16 *storage* (stripes, line
  precision="auto" (model-ranked on     buffers, grid planes, carries, DMA
  the fused/reference family)           blocks, snapshot wire) with fp32
                                        accumulation in every GC/GF/TI
                                        contraction — halves step bytes,
                                        ~doubles the VMEM-feasible tile

Auto-tuning kicks in inside :func:`repro.plan.plan_for`: ``batch_tile`` is
the largest tile whose per-step working set fits the documented VMEM-budget
model (capped at ``ceil(n_frames / mesh_size)``), ``stream_input`` flips on
per the byte threshold above, and ``precision="auto"`` lets the roofline
rank bf16 candidates against fp32. See the ``repro.plan`` module docstring
for the model's term-by-term derivation.
"""

__version__ = "1.1.0"
