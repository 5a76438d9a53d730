"""Sharded WDL categorical plane — mesh-partitioned embedding/wide tables.

The replicated WDL trainer keeps every embedding and wide table whole on
every device, which caps the model at one chip's memory and makes each
step pay a full-table gradient allreduce plus a full-table optimizer
sweep per device.  This module partitions each ``embed``/``wide_cat``
table (and its optimizer moments) ROW-wise over the mesh ``data`` axis
and rewrites the lookup and the update around that layout:

- **sparse row gather**: the minibatch's int bin indices all-gather over
  the axis (4 bytes/row/column — the only replicated traffic), each
  device resolves the gathered ids against its own row shard (masked
  local take), and ONE tiled ``psum_scatter`` returns every device the
  embedding rows of its own data block.  Each (row, column) pair has
  exactly one nonzero contributor, so the scatter reconstructs the
  replicated gather bit for bit (``x + 0 == x``);
- **sharded weight update**: autodiff transposes the psum_scatter to an
  all_gather of the local cotangents, so each shard's gradient lands
  complete on its owner with NO cross-device table traffic, and the
  optimizer steps only the local rows — the full-table allreduce and
  the ``(D-1)/D`` redundant Adam work of the replicated path are gone
  (this is the throughput lever, per "Automatic Cross-Replica Sharding
  of Weight Update in Data-Parallel Training");
- **dense leaves stay replicated**: their per-device partial grads psum
  AFTER ``jax.grad`` — never inside it, because with replication
  tracking off (``check_vma=False``) a ``psum`` inside the
  differentiated region transposes to another psum and inflates every
  cotangent by the axis size.  The loss normalizer is parameter-free,
  so it is computed outside the grad for the same reason (exact);
- **row padding**: each table pads with zero rows to a ``data``-axis
  multiple.  Lookups clip to the TRUE cardinality, so padded rows are
  never gathered, their grads stay zero, and every update rule leaves
  them zero; host snapshots unpad so saved models keep exact shapes.

Serving (``shifu.wdl.serveCopy``) closes the loop without a full-table
allgather anywhere: multi-device backends score through the same masked
lookup + psum inside the AOT executable (bitwise-equal scores, zero
recompiles — the batch is replicated, only table rows move); single
device picks the replicated copy or an opt-in lossy hot-rows copy built
at swap time (first K rows exact + one mean-of-tail fallback row, which
the classic forward's clip resolves with no code change).
"""

from __future__ import annotations

import logging
from functools import partial
from typing import Any, Dict, List, Optional

import numpy as np

import jax
import jax.numpy as jnp

from .. import obs
from ..config import environment
from ..models import wdl as wdl_model
from .optimizers import mixed_apply

log = logging.getLogger(__name__)

_AXIS = "data"


# replication tracking off: see the module docstring's dense-leaves note
# for what that obliges
_shard_map = partial(jax.shard_map, check_vma=False)


# ------------------------------------------------------------------ knobs
def shard_mode() -> str:
    """``shifu.wdl.shardTables``: on | off | auto (size-gated)."""
    raw = str(environment.get_property("shifu.wdl.shardTables", "auto")
              or "auto").lower()
    if raw in ("on", "true", "1"):
        return "on"
    if raw in ("off", "false", "0"):
        return "off"
    if raw != "auto":
        log.warning("unknown shifu.wdl.shardTables %r; using auto", raw)
    return "auto"


def shard_min_bytes() -> int:
    """``shifu.wdl.shardMinBytes``: auto-shard threshold on the
    replicated per-device footprint of tables + moments."""
    return environment.get_int("shifu.wdl.shardMinBytes", 64 << 20)


def serve_copy_mode() -> str:
    """``shifu.wdl.serveCopy``: auto | full | sharded | hot."""
    raw = str(environment.get_property("shifu.wdl.serveCopy", "auto")
              or "auto").lower()
    if raw in ("auto", "full", "sharded", "hot"):
        return raw
    log.warning("unknown shifu.wdl.serveCopy %r; using auto", raw)
    return "auto"


def serve_hot_rows() -> int:
    """``shifu.wdl.serveHotRows``: exact head rows of the lossy
    single-device serving copy."""
    return environment.get_int("shifu.wdl.serveHotRows", 1 << 16)


def table_param_bytes(spec, bags: int = 1, precision: str = "f32") -> int:
    """Replicated per-device bytes of all categorical tables + their two
    Adam moments, stacked over ``bags`` — what the auto gate weighs
    (mixed also carries an f32 master+moments; this stays a f32-ladder
    estimate on purpose: a conservative lower bound)."""
    per = 4 if precision == "f32" else 2
    elems = 0
    for c in spec.cat_cardinalities:
        if spec.deep_enable:
            elems += int(c) * spec.embed_dim
        if spec.wide_enable:
            elems += int(c)
    return 3 * elems * per * bags


def shard_enabled(spec, mesh, bags: int = 1, precision: str = "f32",
                  override: Optional[bool] = None) -> bool:
    """Whether this run shards the WDL categorical plane: an explicit
    trainer arg wins, else ``shifu.wdl.shardTables`` (auto = multi-device
    mesh AND tables past ``shifu.wdl.shardMinBytes``)."""
    if not spec.cat_cardinalities:
        return False
    if override is not None:
        return bool(override)
    mode = shard_mode()
    if mode == "off":
        return False
    if mode == "on":
        return True
    if int(mesh.shape[_AXIS]) <= 1:
        return False
    return table_param_bytes(spec, bags, precision) >= shard_min_bytes()


# ------------------------------------------------------------------ plane
class WDLShardPlane:
    """Row-sharding layout of one spec over one mesh: per-table shard
    sizes, padded cardinalities, PartitionSpec/NamedSharding trees for the
    stacked params and any optimizer state, pad/unpad helpers."""

    def __init__(self, mesh, spec, bags: int):
        self.mesh = mesh
        self.spec = spec
        self.bags = bags
        self.d = int(mesh.shape[_AXIS])
        self.cards = [int(c) for c in spec.cat_cardinalities]
        self.vs = [-(-c // self.d) for c in self.cards]   # rows per shard
        self.vp = [v * self.d for v in self.vs]           # padded rows

    # -- shape plumbing
    def pad_params(self, tree: Dict) -> Dict:
        """Zero-pad one member's table leaves [V, ...] to [Vp, ...] BEFORE
        optimizer init, so moments are born shard-aligned too."""
        def pad(a, vp):
            extra = vp - a.shape[0]
            if not extra:
                return a
            return jnp.pad(a, [(0, extra)] + [(0, 0)] * (a.ndim - 1))
        out = dict(tree)
        if "embed" in out:
            out["embed"] = [pad(t, vp)
                            for t, vp in zip(out["embed"], self.vp)]
        if "wide_cat" in out:
            out["wide_cat"] = [pad(t, vp)
                               for t, vp in zip(out["wide_cat"], self.vp)]
        return out

    def unpad_params(self, tree: Dict) -> Dict:
        """Slice one member's host tree back to the true cardinalities —
        saved ``.wdl`` models keep the replicated path's exact shapes
        (a padded table would change ``clip(idx, 0, V-1)`` semantics for
        out-of-range ids)."""
        out = dict(tree)
        if "embed" in out:
            out["embed"] = [t[:c] for t, c in zip(out["embed"], self.cards)]
        if "wide_cat" in out:
            out["wide_cat"] = [t[:c]
                               for t, c in zip(out["wide_cat"], self.cards)]
        return out

    def param_specs(self) -> Dict:
        """PartitionSpec tree over the STACKED [B, ...] param tree: table
        rows split on ``data``, everything else only on ``ensemble``."""
        from jax.sharding import PartitionSpec as P
        spec = self.spec
        out: Dict[str, Any] = {"bias": P("ensemble")}
        if spec.deep_enable:
            out["embed"] = [P("ensemble", _AXIS, None) for _ in self.cards]
            out["deep"] = [{"w": P("ensemble"), "b": P("ensemble")}
                           for _ in range(len(spec.hidden_nodes) + 1)]
        if spec.wide_enable:
            out["wide_cat"] = [P("ensemble", _AXIS) for _ in self.cards]
            out["wide_num"] = P("ensemble")
        return out

    def state_specs(self, opt_state, stacked) -> Any:
        """Spec tree for any optimizer state by STRUCTURE matching: every
        params-shaped subtree (adam m/v, momentum v, the mixed master)
        inherits the param specs, scalar-stacked leaves (adam's step
        counter) stay ensemble-only — no per-optimizer plumbing."""
        from jax.sharding import PartitionSpec as P
        pspecs = self.param_specs()
        ptree = jax.tree_util.tree_structure(stacked)

        def is_params(node):
            return jax.tree_util.tree_structure(node) == ptree

        return jax.tree_util.tree_map(
            lambda node: pspecs if is_params(node) else P("ensemble"),
            opt_state, is_leaf=is_params)

    def _shardings(self, spec_tree):
        from jax.sharding import NamedSharding, PartitionSpec
        return jax.tree_util.tree_map(
            lambda s: NamedSharding(self.mesh, s), spec_tree,
            is_leaf=lambda x: isinstance(x, PartitionSpec))

    def param_shardings(self):
        return self._shardings(self.param_specs())

    def state_shardings(self, opt_state, stacked):
        return self._shardings(self.state_specs(opt_state, stacked))

    def put(self, stacked, opt_state):
        """Place padded stacked params + optimizer state shard-aligned."""
        return (jax.device_put(stacked, self.param_shardings()),
                jax.device_put(opt_state,
                               self.state_shardings(opt_state, stacked)))

    def table_bytes_per_device(self, precision: str = "f32") -> int:
        return table_param_bytes(self.spec, self.bags, precision) // self.d


# ---------------------------------------------------------- local compute
def _gather_rows(tabs: List, gcat, cards: List[int], vs: List[int], me):
    """[N, C, ...] masked local lookups of the all-gathered global bin
    indices: rows owned by this shard keep their values, foreign rows are
    zero — exactly one nonzero contributor per (row, column) across the
    axis, so a psum/psum_scatter reconstructs the replicated gather
    bitwise.  Clips use the TRUE cardinality: padded rows never load."""
    outs = []
    for i, t in enumerate(tabs):
        gi = jnp.clip(gcat[:, i], 0, cards[i] - 1)
        rel = gi - me * vs[i]
        ok = (rel >= 0) & (rel < vs[i])
        rows = t[jnp.clip(rel, 0, vs[i] - 1)]
        mask = ok[:, None] if rows.ndim == 2 else ok
        outs.append(jnp.where(mask, rows, jnp.zeros_like(rows)))
    return jnp.stack(outs, axis=1)


def _local_forward_logits(lp, spec, cards, vs, x_num, gcat):
    """forward_logits against row-sharded tables, from INSIDE shard_map:
    ``x_num`` is this device's row block, ``gcat`` the all-gathered
    [N, C] indices.  Touched rows move through one tiled psum_scatter per
    side; the dense half is the replicated gather lowering's own code
    (``forward_logits_gathered``), so the arithmetic matches bit for
    bit."""
    me = jax.lax.axis_index(_AXIS)
    emb = None
    wide_rows = None
    if spec.deep_enable:
        emb = jax.lax.psum_scatter(
            _gather_rows(lp["embed"], gcat, cards, vs, me), _AXIS,
            scatter_dimension=0, tiled=True)
    if spec.wide_enable:
        wide_rows = jax.lax.psum_scatter(
            _gather_rows(lp["wide_cat"], gcat, cards, vs, me), _AXIS,
            scatter_dimension=0, tiled=True)
    return wdl_model.forward_logits_gathered(lp, spec, x_num, emb,
                                             wide_rows)


def _psum_dense(grads: Dict, axis: str = _AXIS) -> Dict:
    """Sum the REPLICATED leaves' per-device partial grads.  Table shards
    skip this: the psum_scatter transpose already delivered every row's
    complete gradient to its owner."""
    out = dict(grads)
    for k, v in grads.items():
        if k in ("embed", "wide_cat"):
            continue
        out[k] = jax.tree_util.tree_map(
            lambda g: jax.lax.psum(g, axis), v)
    return out


def _member_data_loss(lp, spec, cards, vs, x_num, gcat, yb, mw, inv_den):
    """This device's share of one member's weighted BCE (NO psum — see
    the module docstring; the caller psums the value for reporting and
    the dense grads after ``jax.grad``).  ``inv_den`` is the global
    ``1/max(sum w, 1e-9)``, parameter-free, computed outside the grad."""
    logit = _local_forward_logits(lp, spec, cards, vs, x_num, gcat)
    p = jax.nn.sigmoid(logit)
    per = wdl_model.per_row_bce(p, yb[:, None])
    return (per * mw).sum() * inv_den


def _member_loss_sum(lp, spec, cards, vs, x_num, gcat, yb, mw):
    """Streamed-path local weighted-SUM loss (normalization happens in
    apply_update, as in the replicated ``_loss_sum``)."""
    logit = _local_forward_logits(lp, spec, cards, vs, x_num, gcat)
    p = jax.nn.sigmoid(logit)
    return (wdl_model.per_row_bce(p, yb[:, None]) * mw).sum()


def _member_eval_sums(lp, spec, cards, vs, x_num, gcat, yb, mw, vw):
    """[4] global (train num, train wsum, valid num, valid wsum) — one
    forward for both masks, one psum on the stacked sums."""
    logit = _local_forward_logits(lp, spec, cards, vs, x_num, gcat)
    p = jax.nn.sigmoid(logit)
    per = wdl_model.per_row_bce(p, yb[:, None])
    s = jnp.stack([(per * mw).sum(), mw.sum(),
                   (per * vw).sum(), vw.sum()])
    return jax.lax.psum(s, _AXIS)


def _make_member_update(spec, cards, vs, opt, precision: str, l2: float):
    def member_update(lp, lo, x_num, gcat, yb, mw, inv_den):
        loss, grads = jax.value_and_grad(_member_data_loss)(
            lp, spec, cards, vs, x_num, gcat, yb, mw, inv_den)
        grads = _psum_dense(grads)
        if l2:
            # the in-RAM weighted_loss's L2 term, applied analytically
            # AFTER the dense psum (in-loss L2 would be psummed D times);
            # the factor-2 reassociation is exact, so this stays bitwise
            grads = jax.tree_util.tree_map(
                jnp.add, grads, wdl_model.l2_grads(lp, l2))
        if precision == "mixed":
            lp, lo = mixed_apply(opt, grads, lo)
            return lp, lo, loss
        delta, lo = opt.update(grads, lo, lp)
        lp = jax.tree_util.tree_map(
            lambda p, d: p + d.astype(p.dtype), lp, delta)
        return lp, lo, loss
    return member_update


# ----------------------------------------------------- trainer executables
def build_inram_fns(plane: WDLShardPlane, stacked, opt_state, opt,
                    precision: str, l2: float) -> Dict[str, Any]:
    """The in-RAM trainer's sharded executables: ``step`` (full batch),
    ``epoch_steps`` (lax.scan over pre-batched [n_batches, bs_local]
    blocks by permuted batch id) and ``eval_errors``.  Same call shapes
    as the replicated ones apart from eval taking the data planes as
    explicit args (shard_map cannot close over sharded arrays)."""
    from jax.sharding import PartitionSpec as P
    mesh, spec = plane.mesh, plane.spec
    cards, vs = plane.cards, plane.vs
    member_update = _make_member_update(spec, cards, vs, opt, precision, l2)
    pspecs = plane.param_specs()
    ospecs = plane.state_specs(opt_state, stacked)

    def step_local(st, os_, xn, xc, yb, tw):
        gcat = jax.lax.all_gather(xc, _AXIS, axis=0, tiled=True)
        den = jax.lax.psum(tw.sum(axis=1), _AXIS)
        inv = 1.0 / jnp.maximum(den, 1e-9)
        st, os_, losses = jax.vmap(
            member_update, in_axes=(0, 0, None, None, None, 0, 0))(
            st, os_, xn, gcat, yb, tw, inv)
        # the DATA loss only — same semantics as the replicated
        # member_update, which applies L2 analytically after the grad
        losses = jax.lax.psum(losses, _AXIS)
        return st, os_, losses

    step = obs.costed_jit("wdl.shard_step", _shard_map(
        step_local, mesh=mesh,
        in_specs=(pspecs, ospecs, P(_AXIS, None), P(_AXIS, None),
                  P(_AXIS), P("ensemble", _AXIS)),
        out_specs=(pspecs, ospecs, P("ensemble"))))

    def epoch_local(st, os_, xn3, xc3, y3, tw3, border):
        def body(carry, bi):
            st, os_ = carry
            xnb, xcb, yb, twb = xn3[bi], xc3[bi], y3[bi], tw3[:, bi]
            gcat = jax.lax.all_gather(xcb, _AXIS, axis=0, tiled=True)
            den = jax.lax.psum(twb.sum(axis=1), _AXIS)
            inv = 1.0 / jnp.maximum(den, 1e-9)
            st, os_, _ = jax.vmap(
                member_update, in_axes=(0, 0, None, None, None, 0, 0))(
                st, os_, xnb, gcat, yb, twb, inv)
            return (st, os_), None
        (st, os_), _ = jax.lax.scan(body, (st, os_), border)
        return st, os_

    epoch_steps = obs.costed_jit("wdl.shard_epoch_steps", _shard_map(
        epoch_local, mesh=mesh,
        in_specs=(pspecs, ospecs, P(None, _AXIS, None),
                  P(None, _AXIS, None), P(None, _AXIS),
                  P("ensemble", None, _AXIS), P(None)),
        out_specs=(pspecs, ospecs)))

    def eval_local(st, tw, vw, xn, xc, yv):
        gcat = jax.lax.all_gather(xc, _AXIS, axis=0, tiled=True)

        def one(lp, mw):
            logit = _local_forward_logits(lp, spec, cards, vs, xn, gcat)
            p = jax.nn.sigmoid(logit)
            per = wdl_model.per_row_bce(p, yv[:, None])
            num = jax.lax.psum((per * mw).sum(), _AXIS)
            den = jax.lax.psum(mw.sum(), _AXIS)
            return num / jnp.maximum(den, 1e-9)
        return jax.vmap(one)(st, tw), jax.vmap(one)(st, vw)

    eval_errors = obs.costed_jit("wdl.shard_eval", _shard_map(
        eval_local, mesh=mesh,
        in_specs=(pspecs, P("ensemble", _AXIS), P("ensemble", _AXIS),
                  P(_AXIS, None), P(_AXIS, None), P(_AXIS)),
        out_specs=(P("ensemble"), P("ensemble"))))

    return {"step": step, "epoch_steps": epoch_steps,
            "eval_errors": eval_errors}


def build_streamed_fns(plane: WDLShardPlane, stacked, opt_state, opt,
                       precision: str, l2: float) -> Dict[str, Any]:
    """The streamed trainer's sharded executables: per-window grad+stat
    accumulation, eval-only window sweep, and the end-of-epoch sharded
    apply (normalize, L2, optimizer step — all on local rows only)."""
    from jax.sharding import PartitionSpec as P
    mesh, spec = plane.mesh, plane.spec
    cards, vs = plane.cards, plane.vs
    pspecs = plane.param_specs()
    ospecs = plane.state_specs(opt_state, stacked)

    def gew_local(st, gacc, sacc, xn, xc, yb, tw, vw):
        gcat = jax.lax.all_gather(xc, _AXIS, axis=0, tiled=True)

        def one(lp, mw, vwm):
            grads = jax.grad(_member_loss_sum)(
                lp, spec, cards, vs, xn, gcat, yb, mw)
            grads = _psum_dense(grads)
            return grads, _member_eval_sums(lp, spec, cards, vs, xn, gcat,
                                            yb, mw, vwm)
        grads, stats = jax.vmap(one)(st, tw, vw)
        gacc = jax.tree_util.tree_map(jnp.add, gacc, grads)
        return gacc, sacc + stats

    grad_eval_window = obs.costed_jit(
        "wdl.shard_grad_eval_window", _shard_map(
            gew_local, mesh=mesh,
            in_specs=(pspecs, pspecs, P("ensemble", None), P(_AXIS, None),
                      P(_AXIS, None), P(_AXIS), P("ensemble", _AXIS),
                      P("ensemble", _AXIS)),
            out_specs=(pspecs, P("ensemble", None))))

    def ew_local(st, sacc, xn, xc, yb, tw, vw):
        gcat = jax.lax.all_gather(xc, _AXIS, axis=0, tiled=True)
        stats = jax.vmap(lambda lp, mw, vwm: _member_eval_sums(
            lp, spec, cards, vs, xn, gcat, yb, mw, vwm))(st, tw, vw)
        return sacc + stats

    eval_window = obs.costed_jit("wdl.shard_eval_window", _shard_map(
        ew_local, mesh=mesh,
        in_specs=(pspecs, P("ensemble", None), P(_AXIS, None),
                  P(_AXIS, None), P(_AXIS), P("ensemble", _AXIS),
                  P("ensemble", _AXIS)),
        out_specs=P("ensemble", None)))

    def au_local(st, os_, gacc, wsum):
        def one(lp, lo, g, ws):
            inv = 1.0 / jnp.maximum(ws, 1e-9)
            g = jax.tree_util.tree_map(lambda a: a * inv, g)
            if l2:
                g = jax.tree_util.tree_map(
                    jnp.add, g, wdl_model.l2_grads(lp, l2))
            if precision == "mixed":
                return mixed_apply(opt, g, lo)
            delta, lo = opt.update(g, lo, lp)
            lp = jax.tree_util.tree_map(
                lambda p, d: p + d.astype(p.dtype), lp, delta)
            return lp, lo
        return jax.vmap(one)(st, os_, gacc, wsum)

    apply_update = obs.costed_jit("wdl.shard_apply_update", _shard_map(
        au_local, mesh=mesh,
        in_specs=(pspecs, ospecs, pspecs, P("ensemble")),
        out_specs=(pspecs, ospecs)))

    return {"grad_eval_window": grad_eval_window,
            "eval_window": eval_window, "apply_update": apply_update}


# -------------------------------------------------------------- telemetry
def _register_cost_models() -> None:
    """Analytic roofline entries for the shard_map executables XLA's cost
    analysis cannot attribute (same contract as ``pallas.tree_traverse``):
    per-call totals across members and devices."""
    def sparse_gather(rows=0, cols=0, embed=0, members=1, devices=1,
                      bytes_per=4):
        touched = float(rows) * cols * (embed + 1) * members
        # index all_gather (4B ints) + table reads + psum-scatter traffic
        return {"flops": 2.0 * touched,
                "bytes_accessed": float(rows) * cols * 4 * devices
                + 2.0 * touched * bytes_per}

    def shard_update(table_elems=0, members=1, steps=1, bytes_per=4):
        # adam-shaped bound: ~10 flops/elem, p+m+v read and written once
        elems = float(table_elems) * members * steps
        return {"flops": 10.0 * elems,
                "bytes_accessed": 6.0 * elems * bytes_per}

    obs.register_cost_model("wdl.sparse_gather", sparse_gather)
    obs.register_cost_model("wdl.shard_update", shard_update)


_register_cost_models()


def record_shard_gauges(plane: WDLShardPlane, precision: str,
                        hash_buckets: int = 0, hashed_cols: int = 0) -> None:
    """One-shot setup gauges for the sharded run (no-op when telemetry
    is off — gauge handles are no-op singletons then)."""
    if not obs.enabled():
        return
    obs.gauge("wdl.shard_devices").set(float(plane.d))
    obs.gauge("wdl.shard_table_bytes").set(
        float(plane.table_bytes_per_device(precision)))
    obs.gauge("wdl.hash_buckets").set(float(hash_buckets))
    obs.gauge("wdl.hashed_cols").set(float(hashed_cols))


def record_epoch_launches(plane: WDLShardPlane, rows: int, steps: int,
                          precision: str = "f32") -> None:
    """Attribute one epoch's sparse gathers + sharded updates to the
    analytic cost models (keys are constant per run: one registry entry,
    ``steps`` launches folded into the shape signature)."""
    spec = plane.spec
    bytes_per = 4 if precision == "f32" else 2
    obs.record_model_launch(
        "wdl.sparse_gather", rows=int(rows),
        cols=len(plane.cards),
        embed=spec.embed_dim if spec.deep_enable else 0,
        members=plane.bags, devices=plane.d, bytes_per=bytes_per)
    elems = sum(vp * (spec.embed_dim if spec.deep_enable else 0) + vp
                for vp in plane.vp)
    obs.record_model_launch(
        "wdl.shard_update", table_elems=int(elems), members=plane.bags,
        steps=int(steps), bytes_per=bytes_per)


# ---------------------------------------------------------------- serving
def resolve_serve_mode(spec, params) -> str:
    """Effective serving-copy mode for one loaded WDL model: the knob
    wins; ``auto`` picks the sharded gather on multi-device backends with
    tables past the shard threshold, else the replicated copy."""
    mode = serve_copy_mode()
    if not spec.cat_cardinalities:
        return "full"
    if mode != "auto":
        return mode
    if jax.device_count() > 1 and \
            table_param_bytes(spec) >= shard_min_bytes():
        return "sharded"
    return "full"


def _hot_params(spec, params, k: int):
    """Lossy single-device serving copy: first ``k`` rows exact + ONE
    mean-of-tail fallback row per table.  The classic forward's
    ``clip(idx, 0, V-1)`` then maps every cold id to the fallback row —
    no forward change needed."""
    def squash(t):
        if t.shape[0] <= k + 1:
            return t
        return jnp.concatenate([t[:k], t[k:].mean(axis=0, keepdims=True)])
    out = dict(params)
    if spec.deep_enable:
        out["embed"] = [squash(t) for t in params["embed"]]
    if spec.wide_enable:
        out["wide_cat"] = [squash(t) for t in params["wide_cat"]]
    return out


def build_serve_forward(spec, params):
    """Serving-copy forward for one WDL model, built at scorer-construction
    (= hot-swap) time.  Returns ``(mode, fn)`` where ``fn(x_num, x_cat)
    -> [N, 1] probabilities`` is traceable inside the scorer's AOT jit,
    or ``(mode, None)`` to keep the classic replicated forward.

    ``sharded`` scores against row-sharded table copies with the SAME
    masked-lookup + psum the trainer uses — the batch stays replicated,
    only touched rows move, scores are bitwise the replicated forward's
    (single nonzero psum contribution per row/column), and the lookup
    traces into the padded-bucket executables so the zero-recompile
    contract holds."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    mode = resolve_serve_mode(spec, params)
    if mode == "full":
        return mode, None
    if mode == "hot":
        hot = _hot_params(spec, params, max(1, serve_hot_rows()))

        def fwd_hot(x_num, x_cat):
            return wdl_model.forward(hot, spec, x_num, x_cat)
        return mode, fwd_hot

    devs = np.asarray(jax.devices())
    mesh = Mesh(devs, (_AXIS,))
    d = len(devs)
    cards = [int(c) for c in spec.cat_cardinalities]
    vs = [-(-c // d) for c in cards]

    def pad_put(t, vp, spec2):
        extra = vp - t.shape[0]
        if extra:
            t = jnp.pad(jnp.asarray(t),
                        [(0, extra)] + [(0, 0)] * (t.ndim - 1))
        return jax.device_put(t, NamedSharding(mesh, spec2))

    dense = {k: v for k, v in params.items()
             if k not in ("embed", "wide_cat")}
    embed_s = wide_s = None
    if spec.deep_enable:
        embed_s = [pad_put(t, v * d, P(_AXIS, None))
                   for t, v in zip(params["embed"], vs)]
    if spec.wide_enable:
        wide_s = [pad_put(t, v * d, P(_AXIS))
                  for t, v in zip(params["wide_cat"], vs)]

    def lookup_local(tabs, xc):
        me = jax.lax.axis_index(_AXIS)
        return jax.lax.psum(_gather_rows(tabs, xc, cards, vs, me), _AXIS)

    n_tab = len(cards)
    emb_fn = _shard_map(lookup_local, mesh=mesh,
                        in_specs=([P(_AXIS, None)] * n_tab, P(None, None)),
                        out_specs=P(None, None, None))
    wide_fn = _shard_map(lookup_local, mesh=mesh,
                         in_specs=([P(_AXIS)] * n_tab, P(None, None)),
                         out_specs=P(None, None))

    def fwd_sharded(x_num, x_cat):
        emb = emb_fn(embed_s, x_cat) if embed_s is not None else None
        wr = wide_fn(wide_s, x_cat) if wide_s is not None else None
        logit = wdl_model.forward_logits_gathered(dense, spec, x_num,
                                                  emb, wr)
        return jax.nn.sigmoid(logit)

    if obs.enabled():
        obs.gauge("wdl.serve_shard_devices").set(float(d))
    return mode, fwd_sharded
