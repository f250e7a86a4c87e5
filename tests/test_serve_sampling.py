"""Sampling fixes + vectorized per-request sampling (serve/sampling.py)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hypothesis_compat import given, settings, st
from repro.serve.sampling import greedy, sample, sample_vec


def _logits(key, b, v):
    return jax.random.normal(key, (b, v), jnp.float32) * 3.0


# ---------------------------------------------------------------------------
# scalar `sample` fixes
# ---------------------------------------------------------------------------

def test_top_k_larger_than_vocab_is_clamped(key):
    logits = _logits(key, 3, 16)
    big = sample(jax.random.PRNGKey(1), logits, temperature=1.0, top_k=999)
    exact = sample(jax.random.PRNGKey(1), logits, temperature=1.0, top_k=16)
    np.testing.assert_array_equal(np.asarray(big), np.asarray(exact))


def test_top_p_one_keeps_full_distribution(key):
    logits = _logits(key, 4, 32)
    with_p1 = sample(jax.random.PRNGKey(2), logits, temperature=0.7,
                     top_p=1.0)
    without = sample(jax.random.PRNGKey(2), logits, temperature=0.7,
                     top_p=0.0)
    np.testing.assert_array_equal(np.asarray(with_p1), np.asarray(without))


def test_top_p_above_one_is_safe(key):
    logits = _logits(key, 2, 8)
    t = sample(jax.random.PRNGKey(3), logits, temperature=1.0, top_p=1.5)
    assert np.all((np.asarray(t) >= 0) & (np.asarray(t) < 8))


# ---------------------------------------------------------------------------
# sample_vec: per-row params, one signature
# ---------------------------------------------------------------------------

def _keys(b, seed=0):
    return jnp.stack([jnp.asarray(jax.random.PRNGKey(seed + i), jnp.uint32)
                      for i in range(b)])


def test_sample_vec_greedy_rows_are_argmax(key):
    logits = _logits(key, 4, 64)
    toks = sample_vec(_keys(4), logits,
                      temperature=jnp.zeros(4), top_k=jnp.zeros(4, jnp.int32),
                      top_p=jnp.zeros(4))
    np.testing.assert_array_equal(np.asarray(toks), np.asarray(greedy(logits)))


def test_sample_vec_mixed_rows(key):
    """Greedy + top-k + nucleus rows coexist in one call."""
    logits = jnp.arange(50, dtype=jnp.float32)[None].repeat(3, 0)
    toks = sample_vec(_keys(3), logits,
                      temperature=jnp.asarray([0.0, 1.0, 1.0]),
                      top_k=jnp.asarray([0, 5, 0], jnp.int32),
                      top_p=jnp.asarray([0.0, 0.0, 0.2]))
    t = np.asarray(toks)
    assert t[0] == 49                            # greedy row
    assert t[1] >= 45                            # top-5 support
    assert t[2] >= 47                            # tight nucleus stays at head


def test_sample_vec_row_isolation(key):
    """A row's draw depends only on its own key/params — not on what else
    is in the batch (the engine's per-request isolation contract)."""
    logits = _logits(key, 2, 32)
    a = sample_vec(_keys(2), logits,
                   temperature=jnp.asarray([0.8, 0.8]),
                   top_k=jnp.asarray([10, 10], jnp.int32),
                   top_p=jnp.asarray([0.9, 0.9]))
    b = sample_vec(_keys(2), logits,
                   temperature=jnp.asarray([0.8, 0.0]),   # partner changed
                   top_k=jnp.asarray([10, 0], jnp.int32),
                   top_p=jnp.asarray([0.9, 0.0]))
    assert int(a[0]) == int(b[0])


def test_sample_vec_top_k_clamps_to_vocab(key):
    logits = _logits(key, 2, 16)
    big = sample_vec(_keys(2), logits, temperature=jnp.ones(2),
                     top_k=jnp.asarray([500, 500], jnp.int32),
                     top_p=jnp.zeros(2))
    exact = sample_vec(_keys(2), logits, temperature=jnp.ones(2),
                       top_k=jnp.asarray([16, 16], jnp.int32),
                       top_p=jnp.zeros(2))
    np.testing.assert_array_equal(np.asarray(big), np.asarray(exact))


@settings(max_examples=20, deadline=None)
@given(seed=st.integers(0, 2**16), top_k=st.integers(0, 64),
       top_p=st.floats(0.0, 1.5), temperature=st.floats(0.0, 2.0))
def test_sampled_token_always_in_masked_support(seed, top_k, top_p,
                                                temperature):
    """Property: the drawn token survives the top-k mask — never an
    out-of-support index, for any (top_k, top_p, temperature) combo."""
    V = 32
    k = jax.random.PRNGKey(seed)
    logits = jax.random.normal(k, (1, V), jnp.float32) * 2.0
    tok = int(sample(jax.random.fold_in(k, 1), logits,
                     temperature=temperature, top_k=top_k, top_p=top_p)[0])
    assert 0 <= tok < V
    if temperature > 0.0 and top_k > 0:
        k_eff = min(top_k, V)
        kth = np.sort(np.asarray(logits[0]))[-k_eff]
        assert np.asarray(logits)[0, tok] >= kth


def test_knobs_that_underflow_float32_keep_the_top_token(key):
    """A positive temperature that is 0 in float32 (1e-160), or subnormal
    there (3.4e-39), with top_k 1: the greedy token, not a draw from
    inf/nan logits; likewise a top_p that is 0 (5e-324) or subnormal
    (3.4e-39) in float32 keeps the top-1 token."""
    logits = _logits(key, 2, 32)
    best = np.argmax(np.asarray(logits), axis=-1)
    for temperature in (1e-160, 3.4e-39):
        tok = sample(jax.random.fold_in(key, 1), logits,
                     temperature=temperature, top_k=1)
        np.testing.assert_array_equal(np.asarray(tok), best)
    for top_p in (5e-324, 3.4e-39):
        tok = sample(jax.random.fold_in(key, 1), logits, temperature=1.0,
                     top_k=1, top_p=top_p)
        np.testing.assert_array_equal(np.asarray(tok), best)
    tok = sample_vec(_keys(2), logits,
                     temperature=np.asarray([1e-160, 1e-160]),
                     top_k=jnp.asarray([1, 1], jnp.int32),
                     top_p=jnp.zeros(2))
    np.testing.assert_array_equal(np.asarray(tok), best)
