"""Speculative read — prefetching layer parameters from the expansion tier.

The paper's SR unit pre-shares upcoming load addresses with the endpoint
(`MemSpecRd`) so the EP's internal DRAM already holds the page when the real
read arrives. The TPU analogue (DESIGN.md §4.2): issue the all-gather of
layer *i+depth* while layer *i* computes so ICI transfers hide behind the
MXU. Two execution modes:

* ``mode="train"`` — the body is rematerialized for the backward pass, so
  gathered weights must NOT live in the scan carry (they would be saved as
  residuals and defeat the pool tier). Overlap is instead exposed via scan
  ``unroll=depth+1``: the unrolled body lets XLA's latency-hiding scheduler
  start iteration i+1's gather during iteration i's compute.

* ``mode="infer"`` — no backward, so we run the *literal* SR mechanism: the
  carry holds ``depth`` gathered layer buffers (the EP-DRAM prefetch slots);
  iteration i computes with slot 0 and issues the gather for layer i+depth.

``granularity`` mirrors MemSpecRd aggregation (256B..1KB): leaves are split
into g chunks gathered separately, trading per-collective overhead for finer
overlap opportunities.

Body contract: ``body(x, layer_params, extra_slice) -> (y, out_slice)`` where
``extra_slice``/``out_slice`` come from/stack into a leading layer axis
(e.g. the layer's index in, the new tokens' K/V out). Use ``None`` when
unused.
"""
from __future__ import annotations

from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.parallel import sharding as shlib


def _tree_index(stacked: Any, i) -> Any:
    return jax.tree_util.tree_map(
        lambda a: jax.lax.dynamic_index_in_dim(a, i, axis=0, keepdims=False),
        stacked)


def strip_stack_axis(specs: Any) -> Any:
    """Per-layer specs from stacked specs (drop the leading layer axis)."""
    from jax.sharding import PartitionSpec as P
    return jax.tree_util.tree_map(
        lambda s: P(*tuple(s)[1:]), specs,
        is_leaf=lambda x: isinstance(x, P))


def materialize(layer: Any, layer_specs: Any, granularity: int = 1) -> Any:
    """Gather one layer's params to their expanded (FSDP-axis-free) form.

    This is the speculative-read *load*: a sharding constraint whose
    placement in the schedule (ahead of the consumer) is what hides the
    pool-tier latency.
    """
    gathered = shlib.gathered_specs(layer_specs)
    if granularity <= 1:
        return shlib.constrain(layer, gathered)

    from jax.sharding import PartitionSpec as P

    def gather_leaf(x, spec):
        if not hasattr(x, "shape") or x.ndim == 0 or \
                x.shape[0] % granularity:
            return jax.lax.with_sharding_constraint(x, spec) \
                if hasattr(x, "shape") else x
        sub = P(None, *tuple(spec))
        chunked = x.reshape((granularity, x.shape[0] // granularity)
                            + x.shape[1:])
        out = jax.lax.with_sharding_constraint(chunked, sub)
        return out.reshape(x.shape)

    flat_l, treedef = jax.tree_util.tree_flatten(layer)
    flat_s = treedef.flatten_up_to(gathered)
    return treedef.unflatten([gather_leaf(x, s)
                              for x, s in zip(flat_l, flat_s)])


def stream_layers(body: Callable, x0: Any, stacked_params: Any,
                  stacked_specs: Any, *, n_layers: int,
                  prefetch_depth: int = 1, granularity: int = 1,
                  mode: str = "train", remat: bool = True,
                  stacked_extras: Any = None,
                  unroll: int = 0, remat_policy: str = "none"
                  ) -> Tuple[Any, Any]:
    """Run layers under the SR pipeline; returns (final_carry, stacked_outs).

    unroll > 0 overrides the scan unroll factor (unroll == n_layers fully
    unrolls — used by the roofline cost extraction so HLO op counts are
    exact; XLA cost analysis visits a while body once).
    """
    layer_specs = strip_stack_axis(stacked_specs)

    if mode == "infer" and prefetch_depth > 0:
        return _stream_infer(body, x0, stacked_params, layer_specs,
                             n_layers=n_layers, depth=prefetch_depth,
                             granularity=granularity,
                             stacked_extras=stacked_extras,
                             unroll=unroll)

    # training path: materialize inside the (remat'd) body; cross-iteration
    # overlap comes from unrolling (saved residuals stay pool-sharded).
    def scan_body(x, xs):
        layer_raw, extra = xs
        layer = materialize(layer_raw, layer_specs, granularity)
        y, out = body(x, layer, extra)
        return y, out

    if remat:
        policy = (jax.checkpoint_policies.dots_with_no_batch_dims_saveable
                  if remat_policy == "dots"
                  else jax.checkpoint_policies.nothing_saveable)
        scan_body = jax.checkpoint(scan_body, policy=policy)
    if unroll <= 0:
        unroll = max(1, prefetch_depth + 1) if mode == "train" else 1
    x, outs = jax.lax.scan(scan_body, x0, (stacked_params, stacked_extras),
                           unroll=min(unroll, n_layers))
    return x, outs


def _stream_infer(body, x0, stacked_params, layer_specs, *, n_layers,
                  depth, granularity, stacked_extras, unroll: int = 0):
    """Literal SR: carry holds `depth` prefetched (gathered) layer buffers."""
    depth = min(depth, n_layers)
    bufs = tuple(
        materialize(_tree_index(stacked_params, i), layer_specs, granularity)
        for i in range(depth))

    def scan_body(carry, xs):
        i, extra = xs
        x, bufs = carry
        cur = bufs[0]
        y, out = body(x, cur, extra)
        # issue the speculative read for layer i+depth (wraps at the end;
        # tail gathers are idle SR slots past the end of the trace)
        nxt_idx = jax.lax.rem(i + depth, jnp.int32(n_layers))
        nxt = materialize(_tree_index(stacked_params, nxt_idx), layer_specs,
                          granularity)
        return (y, bufs[1:] + (nxt,)), out

    (x, _), outs = jax.lax.scan(
        scan_body, (x0, bufs),
        (jnp.arange(n_layers, dtype=jnp.int32), stacked_extras),
        unroll=min(unroll, n_layers) if unroll > 0 else 1)
    return x, outs
