"""``ops/attention.blocked_attention`` (the Pallas kernels, interpreted on the
CPU) against dense masked softmax attention (``tests/helpers/dense_attention``):
forward and the gradients of q, k, v under the causal masks and under masks
handed over as data (``sdar_moe``'s two, over halves padded to toy blocks), the
block schedule's count against a hand count."""

import os
import sys
from functools import partial

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from shifu_tpu.models.tower_sdar import block_mask, eval_mask
from shifu_tpu.ops import attention

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "helpers"))
from dense_attention import allowed, dense_attention  # noqa: E402

BLOCK, W = 16, 32                   # a window of two blocks
TOY = 8                             # the general masks' block


def _halves(mask, s, block=TOY):
    """``mask`` over halves of ``s`` positions -> over halves padded to whole
    blocks: a pad key lies in nobody's row, a pad query sees nothing."""
    half = -(-s // block) * block
    real = (np.arange(len(mask)) // s) * half + np.arange(len(mask)) % s
    out = np.zeros((len(mask) // s * half,) * 2, bool)
    out[np.ix_(real, real)] = mask
    return out


# sdar_moe's masks at block length 4: [x_t ; x_0] under the block-diffusion mask, one half under eval's
GENERAL = {f"{name}-{s}": _halves(fn(s, 4), s) for name, fn in (("diffusion", block_mask), ("eval", eval_mask))
           for s in (12, 20)}


def _qkv(seq, kv, r, hd=16, n=2, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    return (jax.random.normal(ks[0], (n, seq, kv, r, hd), jnp.float32),
            jax.random.normal(ks[1], (n, seq, kv, hd), jnp.float32),
            jax.random.normal(ks[2], (n, seq, kv, hd), jnp.float32),
            jax.random.normal(ks[3], (n, seq, kv, r, hd), jnp.float32))


@pytest.mark.parametrize("r", [1, 8])
@pytest.mark.parametrize("window,seq", [(w, seq) for w in (None, W) for seq in (BLOCK, W, 4 * W + BLOCK)] +
                         [(name, len(dense)) for name, dense in GENERAL.items()])
def test_forward_and_gradients_match_dense_masked_attention(window, seq, r):
    """``window``: None or a number of keys (causal), or the name of a mask
    handed over as data."""
    q, k, v, c = _qkv(seq, 2 if r == 1 else 1, r)
    if window in GENERAL:
        attend = partial(attention.blocked_attention, block=TOY, mask=attention.mask_of(GENERAL[window]))
        dense = partial(dense_attention, mask=GENERAL[window])
    else:
        attend = partial(attention.blocked_attention, window=window, block=BLOCK)
        dense = partial(dense_attention, window=window)
    both = lambda fn: jax.value_and_grad(lambda q, k, v: jnp.sum(fn(q, k, v) * c), argnums=(0, 1, 2))
    got, got_grads = jax.jit(both(attend))(q, k, v)
    want, want_grads = both(dense)(q, k, v)
    np.testing.assert_allclose(got, want, rtol=1e-5)
    np.testing.assert_allclose(attend(q, k, v), dense(q, k, v), atol=2e-6)
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
    (64, 16, 32, 1 + 2 + 3 + 3), (8192, 512, None, 136), (8192, 512, 2048, 1 + 2 + 3 + 4 + 12 * 5),
    # [x_t ; x_0], halves of 16 (12 real) in blocks of 8: a noised block its own and the clean one
    # before it, 2 + 2; the clean blocks themselves and those before them, 1 + 2
    (32, 8, "diffusion-12", 2 + 2 + 1 + 2),
    (48, 8, "diffusion-20", 2 + 3 + 3 + 1 + 2 + 3), (16, 8, "eval-12", 1 + 2),
    # the cell sdar-train: halves of 512 (436 real); a noised half its own block and the clean one, the clean one itself
    (1024, 512, _halves(block_mask(436, 4), 436, 512), 2 + 1),
    (1024, 256, _halves(block_mask(436, 4), 436, 256), 2 + 3 + 1 + 2),
    (512, 512, _halves(eval_mask(436, 4), 436, 512), 1)])
def test_visited_key_blocks_against_a_hand_count(seq, block, window, by_hand):
    """``window`` as in the test above, or a dense mask itself."""
    dense = GENERAL[window] if isinstance(window, str) else window if isinstance(window, np.ndarray) else None
    if dense is None:
        assert attention.visited_key_blocks(seq, block, window) == by_hand
    else:
        assert attention.visited_key_blocks(seq, block, mask=attention.mask_of(dense)) == by_hand
        assert (attention.mask_of(dense).dense() == dense).all()
    # the schedule covers the mask: every allowed pair lies in a visited block
    if seq <= 1024:
        ok = (allowed(seq, window) if dense is None else dense).reshape(
            seq // block, block, seq // block, block).any((1, 3))
        assert int(ok.sum()) == by_hand


def test_pad_keys_have_no_weight_and_pad_queries_reach_nothing():
    dense = GENERAL["diffusion-12"]                         # halves of 16, the last 4 of each a pad
    mask, pad = attention.mask_of(dense), ~dense.any(0)
    assert pad.sum() == 8 and (~dense.any(1) == pad).all()
    q, k, v, c = _qkv(len(dense), 1, 8)
    attend = partial(attention.blocked_attention, block=TOY, mask=mask)
    out = attend(q, k, v)
    assert (np.asarray(out)[:, pad] == 0).all()             # a query that sees no key
    # whatever stands at a pad key moves no output ...
    loud = lambda a: a.at[:, pad].set(1e3)
    np.testing.assert_array_equal(attend(q, loud(k), loud(v)), out)
    # ... and a cotangent at the pad queries alone reaches no q, k or v
    grads = jax.grad(lambda q, k, v: jnp.sum(attend(q, k, v) * c * pad[None, :, None, None, None]),
                     argnums=(0, 1, 2))(q, k, v)
    assert all(not np.asarray(g).any() for g in grads)
    real = jax.grad(lambda q, k, v: jnp.sum(attend(q, k, v) * c), argnums=(1, 2))(q, k, v)
    assert all(np.asarray(g)[:, ~pad].any() and not np.asarray(g)[:, pad].any() for g in real)


def test_a_mask_the_description_cannot_hold_is_refused():
    comb = np.eye(8, dtype=bool) | np.eye(8, k=3, dtype=bool) | np.eye(8, k=-3, dtype=bool)
    with pytest.raises(ValueError, match="query 3 sees 3 separate runs of keys"):
        attention.mask_of(comb)
    two = np.eye(8, dtype=bool) | np.eye(8, k=-3, dtype=bool)          # two runs a query: held
    assert (attention.mask_of(two).dense() == two).all()
    with pytest.raises(ValueError, match="in order and inside the sequence"):
        attention.Mask(np.array([[0, 2], [3, 1], [3, 2], [3, 2]]))     # query 1: hi0 < lo0
    q, k, v, _ = _qkv(16, 1, 1)
    with pytest.raises(ValueError, match="a mask says it all"):
        attention.blocked_attention(q, k, v, window=8, block=8, mask=attention.causal_mask(16))
    with pytest.raises(ValueError, match="a mask says it all"):
        attention.blocked_attention(q, k, v, block=8, mask=attention.causal_mask(32))


def test_shapes_the_blocks_do_not_divide_are_refused():
    q, k, v, _ = _qkv(24, 1, 1)
    with pytest.raises(ValueError, match="not whole blocks of 16"):
        attention.blocked_attention(q, k, v, None, BLOCK)
    q, k, v, _ = _qkv(64, 1, 1)
    with pytest.raises(ValueError, match="window of 24 keys is not whole blocks"):
        attention.blocked_attention(q, k, v, 24, BLOCK)


def test_a_head_narrower_than_whole_lanes_is_padded_and_cut_off(monkeypatch):
    """The chip's path for a head of fewer than 128 channels, interpreted:
    zero channels on the way in, cut off the output on the way out, and the
    same attention and gradients as the dense reference."""
    monkeypatch.setattr(attention, "_lane_width", lambda hd, interpret: -(-hd // attention.LANES) *
                        attention.LANES)
    q, k, v, c = _qkv(2 * BLOCK, 2, 4, hd=64)
    attend = partial(attention.blocked_attention, block=BLOCK)
    both = lambda fn: jax.value_and_grad(lambda q, k, v: jnp.sum(fn(q, k, v) * c), argnums=(0, 1, 2))
    got, got_grads = jax.jit(both(attend))(q, k, v)
    want, want_grads = both(dense_attention)(q, k, v)
    assert attend(q, k, v).shape == q.shape
    np.testing.assert_allclose(got, want, rtol=1e-5)
    for a, b in zip(got_grads, want_grads):
        assert a.shape == b.shape and a.dtype == jnp.float32
        np.testing.assert_allclose(a, b, atol=2e-5)


@pytest.mark.parametrize("lanes", [False, True], ids=["any-width", "whole-lanes"])
@pytest.mark.parametrize("seq", [BLOCK, 4 * BLOCK])
@pytest.mark.parametrize("hd,dv", [(192, 128), (64, 64)])
def test_a_value_width_of_its_own_matches_dense_attention(hd, dv, seq, lanes, monkeypatch):
    """v of ``dv`` channels beside q / k of ``hd`` (latent attention's 128
    against 192; equal widths stack k and v as one operand): forward and the
    gradients of q, k and v against the dense reference, at one block and at
    several.  ``whole-lanes`` runs the chip's layout, interpreted: q / k
    padded to 256 lanes, v left at its own 128 — and q / k handed over
    already padded with ``qk_dim`` give the same."""
    if lanes:
        monkeypatch.setattr(attention, "_lane_width", lambda c, interpret: -(-c // attention.LANES) *
                            attention.LANES)
    ks = jax.random.split(jax.random.PRNGKey(3), 4)
    q = jax.random.normal(ks[0], (2, seq, 2, 1, hd), jnp.float32)
    k = jax.random.normal(ks[1], (2, seq, 2, hd), jnp.float32)
    v = jax.random.normal(ks[2], (2, seq, 2, dv), jnp.float32)
    c = jax.random.normal(ks[3], (2, seq, 2, 1, dv), jnp.float32)
    attend = partial(attention.blocked_attention, block=BLOCK)
    both = lambda fn: jax.value_and_grad(lambda q, k, v: jnp.sum(fn(q, k, v) * c), argnums=(0, 1, 2))
    got, got_grads = jax.jit(both(attend))(q, k, v)
    want, want_grads = both(dense_attention)(q, k, v)
    out = attend(q, k, v)
    assert out.shape == (2, seq, 2, 1, dv)
    np.testing.assert_allclose(got, want, rtol=1e-5)
    np.testing.assert_allclose(out, dense_attention(q, k, v), atol=2e-5)
    for a, b in zip(got_grads, want_grads):
        assert a.shape == b.shape and a.dtype == jnp.float32
        np.testing.assert_allclose(a, b, atol=5e-5)
    if lanes and hd % attention.LANES:
        width = attention._lane_width(hd, True)
        pad = lambda x: jnp.pad(x, ((0, 0),) * (x.ndim - 1) + ((0, width - hd),))
        np.testing.assert_allclose(attend(pad(q), pad(k), v, qk_dim=hd), out, atol=1e-6)
