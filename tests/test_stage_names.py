"""Stable device names: the plan executable is named by its backend, its
stages and the engine's own device programs carry ``bg.*`` name scopes in
their op metadata, and each Pallas kernel has its own name. Lowered on the
CPU (the names are the same on a TPU)."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.bilateral_grid import BGConfig
from repro.plan import BGPlan
from repro.reliability import carry_ok_rows, finite_rows
from repro.serving.device_stages import split_rows, stack_rows
from repro.video.temporal import carry_shape

CFG = BGConfig(r=4, sigma_s=4.0, sigma_r=60.0)
H, W, B = 32, 48, 2


def _lowered(plan):
    x = jax.ShapeDtypeStruct((B, H, W), jnp.float32)
    if not plan.temporal:
        return plan.executable().lower(x)
    c = jax.ShapeDtypeStruct((B,) + carry_shape(H, W, CFG), jnp.float32)
    return plan.executable().lower(x, c, jax.ShapeDtypeStruct((B,), jnp.float32))


def _scopes(text):
    return set(re.findall(r"bg\.[a-z]+", text))


@pytest.mark.parametrize("backend,temporal,kernel", [
    ("fused_streamed", False, "bg_fused_streamed"),
    ("fused", False, "bg_fused"),
    ("fused", True, "bg_fused_temporal"),
])
def test_plan_executable_is_named_and_scoped(backend, temporal, kernel):
    plan = BGPlan(cfg=CFG, backend=backend, quantize_output=True,
                  interpret=True, temporal=temporal)
    low = _lowered(plan)
    module = "jit_bg_" + backend + ("_temporal" if temporal else "")
    assert low.as_text().startswith(f"module @{module} ")
    debug = low.as_text(debug_info=True)
    assert {"bg.relayout", "bg.kernel", "bg.trim", "bg.quantize"} <= _scopes(debug)
    assert re.search(rf"\b{kernel}\b", debug)  # the pallas_call's own name
    # the compiled module keeps the scopes in each op's metadata
    hlo = low.compile().as_text()
    assert {"bg.relayout", "bg.kernel", "bg.quantize"} <= set(
        re.findall(r'op_name="[^"]*/(bg\.[a-z]+)/', hlo))


@pytest.mark.parametrize("backend,kernel", [
    ("fused_streamed", "bg_fused_streamed"), ("fused", "bg_fused")])
def test_pallas_kernels_carry_their_names(backend, kernel):
    from repro.kernels.bg_fused import bg_fused_impl

    jaxpr = jax.make_jaxpr(lambda x: bg_fused_impl(
        x, CFG, interpret=True, stream_input=backend == "fused_streamed"))(
            jnp.zeros((B, H, W), jnp.float32))
    names = [e.params["name"] for e in jaxpr.jaxpr.eqns
             if e.primitive.name == "pallas_call"]
    assert names == [kernel]


def test_engine_device_programs_are_scoped():
    rows = [np.zeros((H, W), np.uint8)] * 3
    assert "bg.ingest" in _scopes(
        stack_rows.lower(rows, jnp.float32).as_text(debug_info=True))
    x = jnp.zeros((3, H, W))
    assert "bg.split" in _scopes(split_rows.lower(x, 3).as_text(debug_info=True))
    out = stack_rows(rows, jnp.float32)
    assert out.dtype == jnp.float32 and out.shape == (3, H, W)
    parts = split_rows(out, 2)
    assert len(parts) == 2 and parts[1].shape == (H, W)


def test_guards_are_scoped_and_unchanged():
    x = np.ones((3, 4, 5), np.float32)
    x[1, 2, 3] = np.nan
    np.testing.assert_array_equal(np.asarray(finite_rows(x)), [True, False, True])
    c = np.ones((2, 3, 2), np.float32)
    c[0, 0, 0] = 2e12
    np.testing.assert_array_equal(np.asarray(carry_ok_rows(c)), [False, True])
    np.testing.assert_array_equal(np.asarray(carry_ok_rows(c, limit=3e12)), [True, True])
    from repro.reliability.guards import _guard_programs

    finite, carry_ok = _guard_programs()
    assert "bg.guard" in _scopes(finite.lower(x).as_text(debug_info=True))
    assert "bg.guard" in _scopes(carry_ok.lower(c, limit=1e12).as_text(debug_info=True))
