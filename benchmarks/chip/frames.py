"""Noisy 8-bit frames made from a seed, on the device, in one jitted call.

The scene follows the composition of the repository's synthetic test image
(smooth shading, six hard-edged ellipses, fine texture, lumpy shading); the
noise is the paper's: additive Gaussian, sigma 30, rounded and clipped to
8 bits. A stream is a window panning diagonally over its own scene, so
consecutive frames show the same content under camera motion.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

N_OBJECTS = 6


def seed_key(seed: int, *salt: int) -> jax.Array:
    """A PRNG key from any non-negative integer seed (wider than 32 bits
    too) and optional salts, by way of numpy's SeedSequence."""
    words = np.random.SeedSequence([int(seed), *map(int, salt)]).generate_state(2)
    return jax.random.wrap_key_data(np.asarray(words, np.uint32))


def _scene(key, h: int, w: int) -> jnp.ndarray:
    yy, xx = jnp.mgrid[0:h, 0:w].astype(jnp.float32)
    u, v = xx / w, yy / h
    img = 150.0 + 60.0 * (u - 0.5) + 35.0 * jnp.sin(2.3 * jnp.pi * v)
    lo = jnp.asarray([0.12, 0.12, 0.06, 0.06, 0.0, 20.0])
    hi = jnp.asarray([0.88, 0.88, 0.22, 0.22, jnp.pi, 235.0])
    draws = lo + (hi - lo) * jax.random.uniform(key, (N_OBJECTS, 6))
    for k in range(N_OBJECTS):
        cx, cy, ax, ay, theta, level = (draws[k, j] for j in range(6))
        cx, ax = cx * w, ax * w
        cy, ay = cy * h, ay * h
        dx = (xx - cx) * jnp.cos(theta) + (yy - cy) * jnp.sin(theta)
        dy = -(xx - cx) * jnp.sin(theta) + (yy - cy) * jnp.cos(theta)
        img = jnp.where((dx / ax) ** 2 + (dy / ay) ** 2 <= 1.0, level, img)
    img = img + 6.0 * jnp.sin(2 * jnp.pi * (xx / 7.3 + yy / 11.1))
    img = img + 12.0 * jnp.sin(2 * jnp.pi * u * 1.7) * jnp.cos(2 * jnp.pi * v * 1.3)
    return jnp.clip(img, 0.0, 255.0)


@partial(jax.jit, static_argnums=(1, 2, 3, 4, 5))
def _streams(key, n_streams: int, n_frames: int, h: int, w: int, pan: float):
    span = int(math.ceil(abs(pan) * (n_frames - 1)))
    offsets = jnp.asarray([int(round(abs(pan) * t)) for t in range(n_frames)])
    skeys = jax.random.split(key, (n_streams, 2))

    def one_stream(k):
        scene = _scene(jax.random.wrap_key_data(k[0]), h + span, w + span)
        clean = jax.vmap(
            lambda o: jax.lax.dynamic_slice(scene, (o, o), (h, w)))(offsets)
        noise = 30.0 * jax.random.normal(
            jax.random.wrap_key_data(k[1]), (n_frames, h, w))
        return jnp.clip(jnp.floor(clean + noise + 0.5), 0, 255).astype(jnp.uint8)

    return jax.lax.map(one_stream, jax.random.key_data(skeys))


def make_streams(seed: int, n_streams: int, n_frames: int, h: int, w: int,
                 pan: float) -> np.ndarray:
    """``(n_streams, n_frames, h, w)`` uint8 on the host: each stream a
    window panning ``pan`` pixels per frame over its own scene, with
    independent noise in every frame."""
    return np.asarray(_streams(seed_key(seed, 1), n_streams, n_frames, h, w, float(pan)))


def ping_pong(t: int, n: int) -> int:
    """Index into a pool of ``n`` frames for sequence position ``t``, played
    forward and back (0, 1, ..., n-1, n-2, ..., 1, 0, 1, ...) so that the
    motion stays continuous."""
    if n == 1:
        return 0
    period = 2 * (n - 1)
    k = t % period
    return k if k < n else period - k
