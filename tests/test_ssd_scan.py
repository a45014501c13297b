"""The chunked SSD form of the Mamba-2 / Hymba time recurrence against a
plain sequential loop of ``_ssm_step``, and the served prefill's program
holding no loop over the prompt's positions."""
from functools import partial

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config
from repro.models import build_model, ssm
from repro.models.ssm import _ssm_step, _time_scan

RTOL = 1e-5
B, H, P, N = 2, 4, 8, 16


def _inputs(S, log_dt, seed=0):
    """Inputs of an S-step recurrence from a nonzero state; per-step
    ``dt * A`` spans ``log_dt`` (base-10 exponents of its magnitude)."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    state = jax.random.normal(ks[0], (B, H, P, N), jnp.float32)
    x = jax.random.normal(ks[1], (S, B, H, P), jnp.float32)
    lo, hi = log_dt
    dt = 10.0 ** jax.random.uniform(ks[2], (S, B, H), jnp.float32, lo, hi)
    Bm = jax.random.normal(ks[3], (S, B, N), jnp.float32)
    Cm = jax.random.normal(ks[4], (S, B, N), jnp.float32)
    A = -jnp.ones((H,), jnp.float32)
    return state, (x, dt, Bm, Cm), A


def _sequential(state, xs, A):
    return jax.lax.scan(partial(_ssm_step, A=A), state, xs)


def _rel(got, want):
    return float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))


@pytest.mark.parametrize("decay", {"weak": (-2.0, -1.0),
                                   "strong": (-1.0, 1.3)}.items(),
                         ids=["weak", "strong"])
@pytest.mark.parametrize("S", [1, 2, 24, 64, 100, 512])
def test_chunked_matches_sequential(S, decay):
    """dt * A from -0.01 to -0.1 (weak) or -0.1 to -20 (strong)."""
    state, xs, A = _inputs(S, decay[1])
    want_state, want_ys = _sequential(state, xs, A)
    got_state, got_ys = jax.jit(_time_scan)(state, xs, A)
    assert got_ys.shape == want_ys.shape
    assert _rel(got_ys, want_ys) < RTOL
    assert _rel(got_state, want_state) < RTOL


def test_chunked_grad_is_finite():
    """Reverse mode through the masked decay matrices gives no NaN."""
    S = ssm.CHUNK + 37
    state, xs, A = _inputs(S, (-2.0, 1.3), seed=1)

    def loss(state, xs, A):
        st, ys = _time_scan(state, xs, A)
        return jnp.sum(ys ** 2) + jnp.sum(st ** 2)

    grads = jax.grad(loss, argnums=(0, 1, 2))(state, xs, A)
    for g in jax.tree.leaves(grads):
        assert bool(jnp.all(jnp.isfinite(g)))
    assert float(jnp.max(jnp.abs(grads[2]))) > 0


def _scan_lengths(jaxpr):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "scan":
            yield eqn.params["length"]
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _scan_lengths(sub)


def test_granite_prefill_has_no_loop_over_positions():
    """The prefill runs no scan as long as the prompt: its Mamba-2
    recurrence is not a loop of one step per position."""
    cfg = get_config("granite-4.0-h-micro").reduced()
    m = build_model(cfg)
    S = 2 * ssm.CHUNK + 3
    batch = {"tokens": jax.ShapeDtypeStruct((2, S), jnp.int32)}
    jaxpr = jax.make_jaxpr(
        lambda p, b: m.prefill_fn(p, b, cache_len=S + 8))(
            m.abstract_params(), batch)
    lengths = set(_scan_lengths(jaxpr.jaxpr))
    assert lengths, "the layer scans of pattern_stack are missing"
    assert S not in lengths
