"""The dense masked softmax attention that blocked attention is held to, in
one place: every ``[S, S]`` score computed, the ruled-out ones masked, a
plain softmax, float64 where x64 is on."""

import numpy as np

import jax
import jax.numpy as jnp


def allowed(seq: int, window=None) -> np.ndarray:
    """[S, S] bool: query i sees key j when j <= i and, with ``window``, i - j < window."""
    back = np.arange(seq)[:, None] - np.arange(seq)[None, :]
    return (back >= 0) if window is None else (back >= 0) & (back < window)


def dense_attention(q, k, v, window=None, mask=None):
    """q [n, S, KV, R, hd], k / v [n, S, KV, hd] -> [n, S, KV, R, hd]; under
    ``mask`` [S, S] bool when given (True = the query, a row, sees the key),
    a query that sees no key gets 0."""
    seq, hd = q.shape[1], q.shape[-1]
    ok = allowed(seq, window) if mask is None else np.asarray(mask, bool)
    live = ok.any(1)[:, None]
    scores = jnp.einsum("nqgrd,nkgd->ngrqk", q, k, precision="highest") / np.sqrt(hd)
    probs = jax.nn.softmax(jnp.where(jnp.asarray(ok | ~live), scores, -jnp.inf), axis=-1) * live
    return jnp.einsum("ngrqk,nkgd->nqgrd", probs, v, precision="highest")
