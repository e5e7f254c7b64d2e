"""Compressed gradient all-reduce: unbiasedness via error feedback."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.launch.mesh import make_host_mesh
from repro.parallel.collectives import (
    compressed_psum,
    dequantize_int8,
    flatten_grads,
    make_compressed_grad_allreduce,
    quantize_int8,
    unflatten_grads,
)


def test_quantize_roundtrip_error_bounded(rng):
    x = jnp.asarray(rng.standard_normal(1024), jnp.float32)
    q, scale = quantize_int8(x)
    err = np.abs(np.asarray(dequantize_int8(q, scale)) - np.asarray(x))
    assert err.max() <= float(scale) * 0.5 + 1e-7  # half-ULP rounding


def test_flatten_unflatten_roundtrip(rng):
    tree = {"a": jnp.asarray(rng.standard_normal((3, 4)), jnp.float32),
            "b": {"c": jnp.asarray(rng.standard_normal(7), jnp.float32)}}
    flat, meta = flatten_grads(tree)
    back = unflatten_grads(flat, meta)
    for x, y in zip(jax.tree.leaves(tree), jax.tree.leaves(back)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_error_feedback_unbiased_over_steps(rng):
    """Repeatedly reducing the SAME gradients with error feedback must
    converge: the cumulative mean of compressed reductions approaches the
    exact mean (the EF carry re-injects quantization residuals)."""
    mesh = make_host_mesh()
    n = mesh.shape["data"]
    ef = make_compressed_grad_allreduce(mesh, "data")
    g = jnp.asarray(rng.standard_normal((n, 512)), jnp.float32)
    exact = np.asarray(jnp.mean(g, axis=0))
    carry = jnp.zeros_like(g)
    acc = np.zeros_like(exact)
    steps = 20
    for _ in range(steps):
        mean, carry = ef(g, carry)
        acc += np.asarray(mean)
    avg = acc / steps
    # single-shot error can be ~1e-2; EF-averaged error is ~n x smaller
    one_shot, _ = ef(g, jnp.zeros_like(g))
    assert np.abs(avg - exact).max() <= np.abs(np.asarray(one_shot) - exact).max() + 1e-6
    assert np.abs(avg - exact).max() < 5e-3


def test_compressed_psum_close_to_exact(rng):
    mesh = make_host_mesh()
    n = mesh.shape["data"]
    if n < 2:
        # single-device mesh: compressed psum must be a near-identity
        from functools import partial
        from jax.sharding import PartitionSpec as P

        @partial(jax.shard_map, mesh=mesh, in_specs=P("data"), out_specs=P())
        def f(x):
            return compressed_psum(x[0], "data")

        x = jnp.asarray(rng.standard_normal((1, 256)), jnp.float32)
        out = np.asarray(f(x))
        half_step = float(np.abs(x).max()) / 127.0 / 2.0
        assert np.abs(out - np.asarray(x[0])).max() <= half_step + 1e-6
