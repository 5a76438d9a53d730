"""Plain reference for the MLP: forward, log loss and an ADAM loop.

float32 under ``jax.default_matmul_precision("highest")`` (NumPy float64 for
the forward's error bound); no code shared with ``shifu_tpu/``.  Weights are
a list of ``(w [in, out], b [out])``; hidden layers are relu, the head is a
sigmoid, the loss is the mean of ``-(y log p + (1-y) log(1-p))`` with p cut
at 1e-7 — what ``NumHiddenNodes``/``ActivationFunc``/``Loss: log`` mean in
the reference system.
"""

from __future__ import annotations

from typing import Dict, List, Sequence, Tuple

import numpy as np

# one rounding to bfloat16 (8 significant bits): the error is uniform within
# +-2^-8 of the binade's lower edge, so relative to the value its variance,
# averaged over a binade, is (2^-8)^2 / 3 x 0.54
BF16_REL_VAR = (2.0 ** -8) ** 2 / 3.0 * (0.75 / (2.0 * np.log(2.0)))


def forward64(weights: Sequence[Tuple[np.ndarray, np.ndarray]], x: np.ndarray
              ) -> Tuple[np.ndarray, np.ndarray]:
    """(p, sigma): the float64 forward and, per row, the standard deviation
    of the difference to expect from a forward whose every dot takes
    **bfloat16 operands and accumulates in float32** (the TPU's default for
    f32 inputs).

    The working: first-order error propagation.  Every operand of every dot
    — the inputs, each layer's weights, each hidden activation — is rounded
    once, by a relative error of variance ``BF16_REL_VAR``, independently.
    A rounded quantity q moves the logit by ``dlogit/dq x q x error``, so

        var(logit) = BF16_REL_VAR x sum over rounded q of (q x dlogit/dq)^2

    with the derivatives from one backward pass per row (relu gates from
    the float64 forward).  For a dot of depth 432, 512 or 256 the depth
    enters through these sums.  The derivatives matter: errors that one
    rounded input sends into all 512 hidden units are *coherent* in a
    trained net (an earlier model that treated the units as independent
    was right on random weights and read 9 sigma on a trained, overfit net
    on the chip).  float32 accumulation (2^-24 a term) is three orders
    below and left out; the sigmoid scales the logit's deviation by p(1-p).
    """
    acts = [np.asarray(x, np.float64)]
    ws = [np.asarray(w, np.float64) for w, _ in weights]
    for i, (w, (_, b)) in enumerate(zip(ws, weights)):
        z = acts[-1] @ w + np.asarray(b, np.float64)
        if i == len(ws) - 1:
            break
        acts.append(np.maximum(z, 0.0))
    p = 1.0 / (1.0 + np.exp(-z[:, 0]))
    # backward: g = dlogit/dz of layer i (per row), G = dlogit/d(activation entering layer i)
    var = np.zeros(len(p))
    g = np.ones((len(p), 1))
    for i in range(len(ws) - 1, -1, -1):
        a, w = acts[i], ws[i]
        var += ((a * a) @ (w * w) * (g * g)).sum(1)        # this layer's weights, rounded
        G = g @ w.T
        var += ((a * G) ** 2).sum(1)                       # the activations entering it, rounded
        if i > 0:
            g = G * (acts[i] > 0)
    return p, np.sqrt(BF16_REL_VAR * var) * p * (1.0 - p)


def forward_bf16_everywhere(weights, x: np.ndarray) -> np.ndarray:
    """Operands, every hidden activation and the logit rounded to bfloat16
    (emulated by cutting float32 mantissas): the tests use it to show what
    the tolerance can and cannot tell apart."""
    def bf16(v):
        u = np.asarray(v, np.float32).view(np.uint32)
        u = (u + 0x7FFF + ((u >> 16) & 1)) & 0xFFFF0000
        return u.astype(np.uint32).view(np.float32).astype(np.float64)
    a = bf16(x)
    for i, (w, b) in enumerate(weights):
        z = bf16(a @ bf16(w) + bf16(b))
        a = np.maximum(z, 0.0)
    return 1.0 / (1.0 + np.exp(-z[:, 0]))


def log_loss(p: np.ndarray, y: np.ndarray) -> float:
    p = np.clip(np.asarray(p, np.float64), 1e-7, 1.0 - 1e-7)
    return float(-(y * np.log(p) + (1 - y) * np.log(1 - p)).mean())


def train_adam(x: np.ndarray, y: np.ndarray, hidden: List[int], epochs: int, batch: int,
               rate: float, valid_rate: float, runs: int, seed: int) -> Dict[str, np.ndarray]:
    """``runs`` independent trainings (own xavier init, own split, own
    shuffles) of relu-MLP + sigmoid head under ADAM(0.9, 0.999, 1e-8).  An
    epoch is one pass over *all* rows in minibatches of ``batch``; a step's
    loss is the mean log loss of the batch's training rows (the validation
    rows ride along with weight 0).  Returns the validation log loss after
    every epoch, per run.  One jitted program, float32, highest matmul
    precision; the data are arguments, so the program stays small enough
    for the compile cache."""
    import jax
    import jax.numpy as jnp

    n, d = x.shape
    n_valid = int(round(n * valid_rate))
    bs = min(batch, n)
    steps = -(-n // bs)                    # the tail batch is filled by wrapping round
    dims = [d] + list(hidden) + [1]

    def init(key):
        ws = []
        for fi, fo in zip(dims[:-1], dims[1:]):
            key, sub = jax.random.split(key)
            lim = (6.0 / (fi + fo)) ** 0.5
            ws.append((jax.random.uniform(sub, (fi, fo), jnp.float32, -lim, lim),
                       jnp.zeros((fo,), jnp.float32)))
        return ws

    def fwd(ws, xb):
        a = xb
        for w, b in ws[:-1]:
            a = jnp.maximum(a @ w + b, 0.0)
        return jax.nn.sigmoid(a @ ws[-1][0] + ws[-1][1])[:, 0]

    def loss(ws, xb, yb, wb):
        p = jnp.clip(fwd(ws, xb), 1e-7, 1.0 - 1e-7)
        per_row = -(yb * jnp.log(p) + (1 - yb) * jnp.log(1 - p))
        return (per_row * wb).sum() / jnp.maximum(wb.sum(), 1e-9)

    def one_run(key, xd, yd):
        k_init, k_split, k_shuf = jax.random.split(key, 3)
        is_valid = jnp.zeros(n, jnp.float32).at[jax.random.permutation(k_split, n)[:n_valid]].set(1.0)
        ws = init(k_init)
        zeros = jax.tree_util.tree_map(jnp.zeros_like, ws)

        def step(carry, idx):
            ws, m, v, t = carry
            g = jax.grad(loss)(ws, xd[idx], yd[idx], 1.0 - is_valid[idx])
            t = t + 1
            m = jax.tree_util.tree_map(lambda a, b: 0.9 * a + 0.1 * b, m, g)
            v = jax.tree_util.tree_map(lambda a, b: 0.999 * a + 0.001 * b * b, v, g)
            ws = jax.tree_util.tree_map(
                lambda p, a, b: p - rate * (a / (1 - 0.9 ** t)) /
                (jnp.sqrt(b / (1 - 0.999 ** t)) + 1e-8), ws, m, v)
            return (ws, m, v, t), None

        def epoch(carry, key):
            order = jnp.resize(jax.random.permutation(key, n), (steps, bs))
            carry, _ = jax.lax.scan(step, carry, order)
            return carry, loss(carry[0], xd, yd, is_valid)

        carry = (ws, zeros, zeros, jnp.zeros((), jnp.float32))
        _, vals = jax.lax.scan(epoch, carry, jax.random.split(k_shuf, epochs))
        return vals

    with jax.default_matmul_precision("highest"):
        keys = jax.random.split(jax.random.key(seed & 0x7FFFFFFF), runs)
        vals = np.asarray(jax.jit(jax.vmap(one_run, in_axes=(0, None, None)))(
            keys, jnp.asarray(x, jnp.float32), jnp.asarray(y, jnp.float32)))
    return {"first": vals[:, 0], "last": vals[:, -1], "curve": vals}
