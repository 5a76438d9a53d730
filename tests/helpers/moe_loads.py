"""Routed loads that hit each edge of ``ops/moe.py``'s chunk walk: 16 tokens
(so a chunk is 16 buffer rows), ``k`` choices a token, experts ``0 .. 3`` held
of ``n_experts``; and a router matrix that routes exactly so."""

import numpy as np

TOKENS, HELD = 16, 4
LOADS = ("none", "one_chunk", "one_more", "spanning", "all")


def routed(load: str, k: int) -> int:
    """Pairs the load routes to the held experts."""
    return {"none": 0,                          # zero trips: y = 0, every gradient 0
            "one_chunk": TOKENS,                # exactly T pairs
            "one_more": TOKENS + 1,             # a second chunk for one row
            "spanning": 22,                     # expert 1's group (rows 10 .. 21) spans two chunks
            "all": TOKENS * min(k, HELD)}[load]   # every choice that can be held is: all chunks


def picks(load: str, k: int, n_experts: int) -> np.ndarray:
    """[16, k] distinct experts a token, in the order the router ranks them."""
    t = np.arange(TOKENS)
    p = np.stack([HELD + (t + j) % (n_experts - HELD) for j in range(k)], 1)   # absent experts
    if load in ("one_chunk", "one_more"):
        p[:, 0] = t % HELD
        if load == "one_more":
            p[0, 1] = 3
    elif load == "spanning":
        p[:, 0] = np.where(t < 10, 0, 1)
        p[:6, 1] = 1
    elif load == "all":
        for j in range(min(k, HELD)):
            p[:, j] = (t + j) % HELD
    assert int((p < HELD).sum()) == routed(load, k), load
    assert all(len(set(row)) == k for row in p.tolist())
    return p


def router_to(x, p: np.ndarray, n_experts: int) -> np.ndarray:
    """A router matrix under which token t's top choices are ``p[t]``, in
    that order: the logits are set outright (x has full row rank: 16 <= D)."""
    logits = np.full((p.shape[0], n_experts), -4.0)
    np.put_along_axis(logits, p, 3.0 - 0.5 * np.arange(p.shape[1]), axis=1)
    return (np.linalg.pinv(np.asarray(x, np.float64)) @ logits).astype(np.float32)
