"""GPipe pipeline: output must equal the sequential stage composition."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.launch.mesh import make_mesh
from repro.parallel.pipeline import gpipe_forward, pipeline_bubble_fraction


@pytest.mark.skipif(jax.device_count() < 2,
                    reason="pipeline test needs >=2 devices "
                           "(run under XLA_FLAGS=--xla_force_host_platform"
                           "_device_count=8 in CI)")
def test_gpipe_matches_sequential():
    P_ = min(4, jax.device_count())
    mesh = make_mesh((P_,), ("stage",))
    M, mb, d = 6, 2, 8
    key = jax.random.PRNGKey(0)
    stage_w = jax.random.normal(key, (P_, d, d)) * 0.3
    x = jax.random.normal(jax.random.fold_in(key, 1), (M, mb, d))

    def apply_fn(w, x):
        return jnp.tanh(x @ w)

    piped = gpipe_forward(apply_fn, mesh)
    y = piped({"w": stage_w}[next(iter({"w"}))] if False else stage_w, x)

    ref = x
    for p in range(P_):
        ref = jnp.tanh(ref @ stage_w[p])
    np.testing.assert_allclose(np.asarray(y), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


def test_bubble_fraction():
    assert pipeline_bubble_fraction(4, 12) == pytest.approx(3 / 15)
    assert pipeline_bubble_fraction(1, 8) == 0.0
