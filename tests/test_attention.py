"""``ops/attention.blocked_attention`` (the Pallas kernels, interpreted on the
CPU) against dense masked softmax attention (``tests/helpers/dense_attention``):
forward and the gradients of q, k, v for both mask kinds, the block schedule's
count against a hand count."""

import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from shifu_tpu.ops import attention

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "helpers"))
from dense_attention import allowed, dense_attention  # noqa: E402

BLOCK, W = 16, 32                   # a window of two blocks


def _qkv(seq, kv, r, hd=16, n=2, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    return (jax.random.normal(ks[0], (n, seq, kv, r, hd), jnp.float32),
            jax.random.normal(ks[1], (n, seq, kv, hd), jnp.float32),
            jax.random.normal(ks[2], (n, seq, kv, hd), jnp.float32),
            jax.random.normal(ks[3], (n, seq, kv, r, hd), jnp.float32))


@pytest.mark.parametrize("r", [1, 8])
@pytest.mark.parametrize("seq", [BLOCK, W, 4 * W + BLOCK])
@pytest.mark.parametrize("window", [None, W])
def test_forward_and_gradients_match_dense_masked_attention(window, seq, r):
    q, k, v, c = _qkv(seq, 2 if r == 1 else 1, r)
    both = lambda fn: jax.value_and_grad(lambda q, k, v: jnp.sum(fn(q, k, v) * c), argnums=(0, 1, 2))
    got, got_grads = jax.jit(both(
        lambda q, k, v: attention.blocked_attention(q, k, v, window, BLOCK)))(q, k, v)
    want, want_grads = both(lambda q, k, v: dense_attention(q, k, v, window))(q, k, v)
    np.testing.assert_allclose(got, want, rtol=1e-5)
    np.testing.assert_allclose(attention.blocked_attention(q, k, v, window, BLOCK),
                               dense_attention(q, k, v, window), atol=2e-6)
    for a, b in zip(got_grads, want_grads):
        assert a.dtype == jnp.float32
        np.testing.assert_allclose(a, b, atol=2e-5)


def test_the_first_query_sees_itself_alone_and_a_window_cuts_the_far_keys():
    q, k, v, _ = _qkv(4 * W, 1, 2)
    full = attention.blocked_attention(q, k, v, None, BLOCK)
    win = attention.blocked_attention(q, k, v, W, BLOCK)
    np.testing.assert_allclose(full[:, 0], jnp.broadcast_to(v[:, 0, :, None], full[:, 0].shape),
                               atol=1e-6)
    np.testing.assert_allclose(win[:, :W], full[:, :W], atol=2e-6)      # inside the first window
    assert float(jnp.abs(win[:, W:] - full[:, W:]).max()) > 0.05        # past it they differ
    # moving a key further back than the window moves no windowed output
    k2 = k.at[:, 0].add(3.0)
    again = attention.blocked_attention(q, k2, v, W, BLOCK)
    np.testing.assert_allclose(again[:, W:], win[:, W:], atol=2e-6)
    assert float(jnp.abs(attention.blocked_attention(q, k2, v, None, BLOCK) - full)[:, W:].max()) > 1e-3


def test_a_window_as_long_as_the_sequence_is_a_full_layer():
    q, k, v, _ = _qkv(2 * W, 2, 2)
    full = attention.blocked_attention(q, k, v, None, BLOCK)
    for window in (2 * W, 5 * W, 2 * W + 3):
        assert attention.blocked_attention(q, k, v, window, BLOCK).tobytes() == full.tobytes()
        assert attention.visited_key_blocks(2 * W, BLOCK, window) == \
            attention.visited_key_blocks(2 * W, BLOCK, None)


@pytest.mark.parametrize("seq,block,window,by_hand", [
    (16, 16, None, 1), (64, 16, None, 1 + 2 + 3 + 4), (64, 16, 16, 1 + 2 + 2 + 2),
    (64, 16, 32, 1 + 2 + 3 + 3), (8192, 512, None, 136), (8192, 512, 2048, 1 + 2 + 3 + 4 + 12 * 5)])
def test_visited_key_blocks_against_a_hand_count(seq, block, window, by_hand):
    assert attention.visited_key_blocks(seq, block, window) == by_hand
    # the schedule covers the mask: every allowed pair lies in a visited block
    if seq <= 64:
        ok = allowed(seq, window).reshape(seq // block, block, seq // block, block).any((1, 3))
        assert int(ok.sum()) == by_hand


def test_shapes_the_blocks_do_not_divide_are_refused():
    q, k, v, _ = _qkv(24, 1, 1)
    with pytest.raises(ValueError, match="not whole blocks of 16"):
        attention.blocked_attention(q, k, v, None, BLOCK)
    q, k, v, _ = _qkv(64, 1, 1)
    with pytest.raises(ValueError, match="window of 24 keys is not whole blocks"):
        attention.blocked_attention(q, k, v, 24, BLOCK)
