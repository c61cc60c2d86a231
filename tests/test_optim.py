"""Optimizer substrate tests: gradient compression properties
(unbiasedness + bounded error) and the cross-pod compressed psum."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest
from _hypothesis_compat import given, settings, st

from repro.optim import compress


@settings(max_examples=20, deadline=None)
@given(st.integers(0, 2**31 - 1), st.integers(1, 4))
def test_quantize_roundtrip_error(seed, scale_pow):
    rng = np.random.default_rng(seed)
    x = (rng.standard_normal((3, 130)) * 10 ** scale_pow).astype(np.float32)
    q, s = compress.quantize(jnp.asarray(x), jax.random.PRNGKey(seed))
    y = np.asarray(compress.dequantize(q, s, x.shape, jnp.float32))
    # error per element bounded by one quantization step (per-block scale)
    step = np.asarray(s)[:, None] * np.ones((1, compress.BLOCK))
    step = step.reshape(-1)[: x.size].reshape(x.shape)
    assert np.all(np.abs(y - x) <= step + 1e-6)


def test_quantize_unbiased():
    x = jnp.asarray(np.linspace(-3, 3, 512, dtype=np.float32))
    outs = []
    for k in range(200):
        q, s = compress.quantize(x, jax.random.PRNGKey(k))
        outs.append(np.asarray(compress.dequantize(q, s, x.shape,
                                                   jnp.float32)))
    mean = np.mean(outs, axis=0)
    scale = float(np.max(np.abs(x))) / 127.0
    # stochastic rounding: mean converges to x (tolerance ~ step/sqrt(N))
    assert np.max(np.abs(mean - x)) < 0.35 * scale


def test_compression_ratio_beats_bf16():
    r = compress.compression_ratio((4096, 4096))
    assert r < 0.6           # int8+scales ≈ 0.51 of bf16 wire bytes


def test_compressed_psum_single_axis():
    """shard_map over the host device(s): compressed sum ≈ exact sum."""
    from jax.sharding import PartitionSpec as P
    devs = jax.devices()
    mesh = jax.make_mesh((len(devs),), ("pod",))
    x = np.random.default_rng(0).standard_normal(
        (len(devs), 512)).astype(np.float32)

    def f(xs):
        y = compress.compressed_psum(xs[0], "pod", jax.random.PRNGKey(0),
                                     group_size=len(devs))
        return y[None]

    out = jax.jit(jax.shard_map(f, mesh=mesh, in_specs=(P("pod"),),
                                out_specs=P("pod")))(jnp.asarray(x))
    exact = x.sum(axis=0)
    got = np.asarray(out)[0]
    err = np.abs(got - exact)
    step = np.abs(x).max() / 127.0 * len(devs)
    assert np.all(err <= step * 1.5 + 1e-5)
