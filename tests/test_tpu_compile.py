"""Compile the fused kernel's plans for a TPU v5e at real sizes, no chip needed.

The TPU compiler is installed on CPU hosts and compiles for a described
``v5e:2x2`` topology, so block-shape, lowering and VMEM refusals surface here
instead of on the chip. Nothing runs: these tests prove that Mosaic accepts
each kernel path (``interpret=False``), not that it is correct or fast.

The topology is described inside a module fixture, never at import: only one
process may load the TPU library, and under pytest-xdist every worker imports
this file. Keep every such compile in this one file, so they all go to the
worker that loaded the library.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec, SingleDeviceSharding

from repro.core import BGConfig
from repro.plan import plan_for
from repro.video import carry_shape

HD = (1080, 1920)
UHD = (2160, 3840)


def _cfg(r):
    # Table I of the paper: sigma_s=8, sigma_r=70 at every radius
    return BGConfig(r=r, sigma_s=8.0, sigma_r=70.0)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a described-topology compile is written to a persistent cache but can
    # never be read back without a chip: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        desc = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler on this host
        jax.config.update("jax_enable_compilation_cache", was)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(plan, n, hw, sharding, dtype=jnp.float32):
    """Lower + compile the plan's executable for ``n`` frames of ``hw``."""
    h, w = hw
    frames = jax.ShapeDtypeStruct((n, h, w), dtype, sharding=sharding)
    args = [frames]
    if plan.temporal:
        args.append(jax.ShapeDtypeStruct(
            (n,) + carry_shape(h, w, plan.cfg), plan.storage_dtype,
            sharding=sharding,
        ))
        args.append(jax.ShapeDtypeStruct((n,), jnp.float32, sharding=sharding))
    compiled = plan.executable().lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()  # the Mosaic kernel itself
    return compiled


@pytest.mark.parametrize(
    "name,hw,r,precision,kw,backend",
    [
        # the paper's setting: plan_for streams the input at r=12 full HD
        ("paper_streamed_fp32", HD, 12, "fp32", {}, "fused_streamed"),
        # Table I's smallest radius on the blocked path, bf16 storage
        ("r4_fused_bf16", HD, 4, "bf16", {"backend": "fused"}, "fused"),
        # 4K UHD at the largest radius, streamed, bf16 storage
        ("uhd_r16_streamed_bf16", UHD, 16, "bf16", {"stream_input": True},
         "fused_streamed"),
        # the paper cells' plan on 8-bit frames as the engine stacks them:
        # grid creation on bf16 operands in one MXU pass ("frames" is the
        # frames' dtype, not a plan_for argument)
        ("paper_streamed_bt3_uint8", HD, 12, "fp32",
         {"batch_tile": 3, "frames": jnp.uint8}, "fused_streamed"),
    ],
)
def test_frame_plan_compiles_for_v5e(one_chip, name, hw, r, precision, kw,
                                     backend):
    kw = dict(kw)
    dtype = kw.pop("frames", jnp.float32)
    plan = plan_for(
        _cfg(r), *hw, n_frames=8, precision=precision, interpret=False,
        sharded=False, cache=False, **kw,
    )
    assert plan.backend == backend and plan.precision == precision
    if "batch_tile" in kw:
        assert plan.batch_tile == kw["batch_tile"]
    _compile(plan, 8, hw, one_chip, dtype)


def test_temporal_plan_compiles_for_v5e(one_chip):
    """The in-kernel grid EMA at the paper's setting, four streams per tile."""
    plan = plan_for(
        _cfg(12), *HD, n_frames=4, temporal=True, batch_tile=4,
        interpret=False, sharded=False, cache=False,
    )
    assert plan.temporal and plan.batch_tile == 4
    _compile(plan, 4, HD, one_chip)


def test_temporal_level_plan_compiles_for_v5e(one_chip):
    """The video cell's plan (temporal, three streams per tile) on 8-bit
    frames: grid creation on bf16 operands beside the grid EMA."""
    plan = plan_for(
        _cfg(12), *HD, n_frames=3, temporal=True, batch_tile=3,
        interpret=False, sharded=False, cache=False,
    )
    assert plan.temporal and plan.batch_tile == 3
    _compile(plan, 3, HD, one_chip, jnp.uint8)


@pytest.mark.parametrize(
    "n,dtype",
    [
        (16, jnp.float32),  # a full batch, already float32 and sharded
        # a ragged batch as the engine places it: 8-bit rows padded with
        # zero rows to 8 (device_stages.place_rows)
        (6, jnp.uint8),
    ],
)
def test_four_device_mesh_plan_compiles_for_v5e(topo, n, dtype):
    """The batch-axis mesh plan over four chips: one kernel per device and no
    collective (frames are independent), in the executable and in the
    engine's guard over its answer, which stays sharded by batch."""
    from repro.reliability.guards import _guard_programs

    mesh = Mesh(
        np.array(topo.devices[:4]), ("batch",),
        axis_types=(jax.sharding.AxisType.Auto,),
    )
    plan = plan_for(
        _cfg(12), *HD, n_frames=n, mesh=mesh, interpret=False, cache=False
    )
    assert plan.mesh_size == 4
    by_batch = NamedSharding(mesh, PartitionSpec("batch"))
    placed = -(-n // 4) * 4
    compiled = _compile(plan, placed, HD, by_batch, dtype)
    assert compiled.output_shardings.is_equivalent_to(by_batch, 3)
    answer = jax.ShapeDtypeStruct((placed,) + HD, jnp.float32, sharding=by_batch)
    guard = _guard_programs()[0].lower(answer).compile()
    for text in (compiled.as_text(), guard.as_text()):
        for coll in ("all-reduce", "all-gather", "all-to-all", "collective-permute"):
            assert coll not in text, coll
