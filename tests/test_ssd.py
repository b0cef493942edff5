"""The Mamba-2 recurrence three ways (``ops/ssd.py``): the chunked form
and the one-token step against the token-by-token recurrence, which is
the definition; the step's kernel (interpreted here) against its jnp
path.

Tolerances.  In float32 and float64 the three differ by the order of
their sums: 1e-4 on outputs of order 50 in float32 (measured 6e-5),
1e-9 in float64.  In bfloat16 the chunked form rounds the operands of
its matrix products, as the model's other products do: outputs of order
40 agree to a bfloat16 ulp there (0.25), the float32 state to 2e-2."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mpi4torch_tpu.ops import ssd

HEADS, P, GROUPS, N = 8, 16, 2, 32


def _inputs(b, s, dtype, seed=0, heads=HEADS, p=P, groups=GROUPS, n=N):
    k = jax.random.split(jax.random.PRNGKey(seed), 6)
    f32 = jnp.float32
    x = jax.random.normal(k[0], (b, s, heads, p), f32).astype(dtype)
    dt = jax.nn.softplus(jax.random.normal(k[1], (b, s, heads), f32) - 2.0)
    A = -jax.random.uniform(k[2], (heads,), f32, 1.0, 16.0)
    B = jax.random.normal(k[3], (b, s, groups, n), f32).astype(dtype)
    C = jax.random.normal(k[4], (b, s, groups, n), f32).astype(dtype)
    D = jnp.ones((heads,), f32)
    h0 = jax.random.normal(k[5], (b, heads, p, n), f32)
    return x, dt, A, B, C, D, h0


def _gap(a, b):
    return float(np.max(np.abs(np.asarray(a, np.float64)
                               - np.asarray(b, np.float64))))


@pytest.mark.parametrize("dtype,tol_y,tol_h", [
    (jnp.float64, 1e-9, 1e-9), (jnp.float32, 1e-4, 1e-5),
    (jnp.bfloat16, 0.26, 2e-2)])
@pytest.mark.parametrize("s", [5, 64, 100, 128])
@pytest.mark.parametrize("start", ["zero", "given"])
def test_chunked_equals_the_recurrence(dtype, tol_y, tol_h, s, start):
    """Lengths that are and are not multiples of the chunk (32), shorter
    than one chunk too; from nothing and from a state handed in."""
    *args, h0 = _inputs(2, s, dtype)
    h0 = None if start == "zero" else h0
    y, h = ssd.ssd_recurrent(*args, h0)
    y_c, h_c = ssd.ssd_chunked(*args, h0, chunk=32)
    assert y_c.dtype == y.dtype == dtype and y_c.shape == y.shape
    assert h_c.dtype == h.dtype and h.dtype == jnp.promote_types(
        dtype, jnp.float32)
    assert _gap(y, y_c) < tol_y
    assert _gap(h, h_c) < tol_h


def test_a_padded_tail_leaves_the_last_real_tokens_state():
    """100 tokens in chunks of 32: the 28 padding tokens write nothing
    and do not decay, so the state is the state after token 99; and
    two passes, the second from the first's state, are one."""
    *args, h0 = _inputs(1, 100, jnp.float32)
    x, dt, A, B, C, D = args
    _, whole = ssd.ssd_chunked(*args, h0, chunk=32)
    cut = lambda t, a, b: t[:, a:b]
    y1, h1 = ssd.ssd_chunked(cut(x, 0, 37), cut(dt, 0, 37), A,
                             cut(B, 0, 37), cut(C, 0, 37), D, h0, chunk=32)
    y2, h2 = ssd.ssd_chunked(cut(x, 37, 100), cut(dt, 37, 100), A,
                             cut(B, 37, 100), cut(C, 37, 100), D, h1,
                             chunk=32)
    y, _ = ssd.ssd_chunked(*args, h0, chunk=32)
    assert _gap(whole, h2) < 1e-5
    assert _gap(y, jnp.concatenate([y1, y2], axis=1)) < 1e-4


@pytest.mark.parametrize("dtype,tol", [(jnp.float64, 1e-10),
                                       (jnp.float32, 1e-5)])
def test_the_step_repeated_is_the_recurrence(dtype, tol):
    x, dt, A, B, C, D, h0 = _inputs(3, 9, dtype)
    y, h = ssd.ssd_recurrent(x, dt, A, B, C, D, h0)
    state = h0.astype(jnp.promote_types(dtype, jnp.float32))
    for t in range(x.shape[1]):
        y_t, state = ssd.ssd_step(state, x[:, t], dt[:, t], A, B[:, t],
                                  C[:, t], D)
        assert _gap(y[:, t], y_t) < tol
    assert _gap(h, state) < tol


def test_a_prefill_then_steps_is_one_pass():
    """The chunked form over T tokens hands its state to n one-token
    steps: the outputs and the last state are one recurrence's over
    T + n."""
    T, n = 40, 6
    x, dt, A, B, C, D, _ = _inputs(2, T + n, jnp.float32, seed=3)
    y, h = ssd.ssd_recurrent(x, dt, A, B, C, D)
    y_p, state = ssd.ssd_chunked(x[:, :T], dt[:, :T], A, B[:, :T],
                                 C[:, :T], D, chunk=16)
    assert _gap(y[:, :T], y_p) < 1e-4
    for t in range(T, T + n):
        y_t, state = ssd.ssd_step(state, x[:, t], dt[:, t], A, B[:, t],
                                  C[:, t], D)
        assert _gap(y[:, t], y_t) < 1e-4
    assert _gap(h, state) < 1e-5


@pytest.mark.parametrize("b", [1, 4, 6])
def test_the_steps_kernel_equals_its_jnp_path(b):
    """Interpreted: states of whole (8, 128) tiles, 16 heads in 2
    groups, bfloat16 inputs as the model hands them; a batch that the
    kernel's block of sequences does and does not divide."""
    x, dt, A, B, C, D, h0 = _inputs(b, 1, jnp.bfloat16, heads=16, p=8,
                                    n=128)
    args = (h0, x[:, 0], dt[:, 0], A, B[:, 0], C[:, 0], D)
    y, h = ssd.ssd_step(*args, impl="jnp")
    y_k, h_k = jax.jit(lambda *a: ssd.ssd_step(*a, impl="pallas"))(*args)
    assert y_k.dtype == jnp.bfloat16 and h_k.dtype == jnp.float32
    assert _gap(y, y_k) < 1e-2 and _gap(h, h_k) < 1e-5


def test_the_kernel_refuses_states_it_cannot_tile():
    x, dt, A, B, C, D, h0 = _inputs(2, 1, jnp.float32)      # n = 32
    with pytest.raises(ValueError, match="whole"):
        ssd.ssd_step(h0, x[:, 0], dt[:, 0], A, B[:, 0], C[:, 0], D,
                     impl="pallas")
    assert not ssd.uses_kernel(h0, GROUPS)
    with pytest.raises(ValueError, match="unknown impl"):
        ssd.ssd_step(h0, x[:, 0], dt[:, 0], A, B[:, 0], C[:, 0], D,
                     impl="mosaic")


def test_a_bfloat16_state_is_advanced_in_float32():
    """A caller that keeps the state in a narrower type gets float32
    arithmetic and a float32 state back: the rounding is the caller's."""
    x, dt, A, B, C, D, h0 = _inputs(2, 1, jnp.float32)
    y, h = ssd.ssd_step(h0.astype(jnp.bfloat16), x[:, 0], dt[:, 0], A,
                        B[:, 0], C[:, 0], D)
    want_y, want_h = ssd.ssd_step(
        h0.astype(jnp.bfloat16).astype(jnp.float32), x[:, 0], dt[:, 0], A,
        B[:, 0], C[:, 0], D)
    assert h.dtype == jnp.float32
    assert _gap(h, want_h) == 0.0 and _gap(y, want_y) == 0.0
