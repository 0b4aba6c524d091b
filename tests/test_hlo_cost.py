"""Structural HLO cost model: trip-count multiplication, dot FLOPs,
slice/DUS refinement, collective classification — validated against
hand-computable programs compiled on the host backend."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.launch.hlo_cost import analyze_hlo


def _hlo(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


def test_scan_trip_count_multiplies_flops():
    N, D, T = 64, 64, 7

    def f(c, xs):
        def body(c, x):
            return jnp.tanh(c @ x), ()

        c, _ = jax.lax.scan(body, c, xs)
        return c

    txt = _hlo(
        f,
        jax.ShapeDtypeStruct((N, D), jnp.float32),
        jax.ShapeDtypeStruct((T, D, D), jnp.float32),
    )
    hc = analyze_hlo(txt)
    expect = T * 2 * N * D * D
    assert hc.flops == pytest.approx(expect, rel=0.01), (hc.flops, expect)


def test_nested_scan_multiplies():
    D, T1, T2 = 32, 3, 5

    def f(c):
        def outer(c, _):
            def inner(c, _):
                return c @ c, ()

            c, _ = jax.lax.scan(inner, c, None, length=T2)
            return c, ()

        c, _ = jax.lax.scan(outer, c, None, length=T1)
        return c

    hc = analyze_hlo(_hlo(f, jax.ShapeDtypeStruct((D, D), jnp.float32)))
    expect = T1 * T2 * 2 * D**3
    assert hc.flops == pytest.approx(expect, rel=0.01)


def test_dynamic_slice_in_loop_counts_slice_not_operand():
    """A scan that slices one row per step must charge ~row bytes per step,
    not the whole array."""
    S, D = 1024, 256

    def f(xs):
        def body(acc, i):
            row = jax.lax.dynamic_slice(xs, (i, 0), (1, D))
            return acc + jnp.sum(row), ()

        acc, _ = jax.lax.scan(body, 0.0, jnp.arange(S))
        return acc

    hc = analyze_hlo(_hlo(f, jax.ShapeDtypeStruct((S, D), jnp.float32)))
    full_per_step = S * (S * D * 4)  # what naive counting would charge
    assert hc.hbm_bytes < full_per_step / 20, (hc.hbm_bytes, full_per_step)


def test_dot_flops_with_batch_dims():
    B, M, K, N = 4, 32, 48, 16

    def f(a, b):
        return jnp.einsum("bmk,bkn->bmn", a, b)

    hc = analyze_hlo(
        _hlo(
            f,
            jax.ShapeDtypeStruct((B, M, K), jnp.float32),
            jax.ShapeDtypeStruct((B, K, N), jnp.float32),
        )
    )
    assert hc.flops == pytest.approx(2 * B * M * K * N, rel=0.01)


def test_collectives_counted_with_ring_model():
    import subprocess, sys, os, textwrap

    code = textwrap.dedent(
        """
        import os
        os.environ['XLA_FLAGS'] = '--xla_force_host_platform_device_count=8'
        import sys
        sys.path.insert(0, %r)
        import jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.launch.hlo_cost import analyze_hlo

        mesh = jax.make_mesh((8,), ("x",), axis_types=(jax.sharding.AxisType.Auto,))
        sh = NamedSharding(mesh, P("x", None))
        rep = NamedSharding(mesh, P())
        def f(a):
            return jnp.sum(a * 2.0)
        with jax.set_mesh(mesh):
            txt = jax.jit(f, in_shardings=(sh,), out_shardings=rep).lower(
                jax.ShapeDtypeStruct((64, 32), jnp.float32)).compile().as_text()
        hc = analyze_hlo(txt)
        kinds = set(hc.collectives)
        assert kinds & {"all-reduce", "all-reduce->rs"}, kinds
        print("OK", hc.collectives)
        """
        % (os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "src"),)
    )
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True, timeout=300
    )
    assert out.returncode == 0, out.stderr[-2000:]


def test_roofline_report_renders():
    from repro.launch.roofline import render_dryrun_table, render_roofline_table

    cells = [
        {
            "arch": "a",
            "shape": "train_4k",
            "mesh": "16x16",
            "status": "ok",
            "compile_s": 1.0,
            "memory": {"argument_size_in_bytes": 1, "temp_size_in_bytes": 2,
                       "output_size_in_bytes": 3},
            "useful_flops_ratio": 0.7,
            "roofline": {
                "compute_s": 1.0,
                "memory_s": 2.0,
                "collective_s": 0.5,
                "dominant": "memory",
                "collective_breakdown": {"all-gather": {"count": 3, "bytes": 9.0}},
            },
        },
        {"arch": "b", "shape": "long_500k", "mesh": "16x16",
         "status": "skipped", "reason": "encoder-only"},
    ]
    t1 = render_dryrun_table(cells)
    t2 = render_roofline_table(cells)
    assert "SKIP" in t1 and "all-gather×3" in t1
    assert "**memory**" in t2 and "50.0%" in t2


def test_peaks_keyed_by_device_kind():
    """The v5e peaks answer to v5e's device_kind; a host with no TPU models
    the target chip; an unknown TPU kind raises instead of borrowing v5e's
    numbers."""
    from repro.launch.hlo_analysis import PEAKS, TARGET_KIND, chip_peaks

    v5e = chip_peaks("TPU v5 lite")
    assert v5e["peak_flops"] == 197e12 and v5e["hbm_bw"] == 819e9
    assert TARGET_KIND in PEAKS
    assert chip_peaks() == PEAKS[TARGET_KIND]  # this host has no TPU
    with pytest.raises(KeyError, match="TPU v9"):
        chip_peaks("TPU v9")
