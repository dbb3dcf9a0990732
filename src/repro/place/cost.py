"""NoC-aware placement cost model, vmapped over candidate placements.

A candidate placement is a ``[N]`` node -> PE assignment. Its cost has two
terms, both *integer-valued* so every score (and every annealer accept
decision built on score deltas) is bit-deterministic across machines and XLA
versions:

  * **traffic** — hop-weighted NoC load: each dataflow edge pays the
    dimension-ordered hop count of the unidirectional Hoplite torus between
    its endpoint PEs (``(dx mod nx) + (dy mod ny)`` — the torus is one-way,
    so going "back" one column costs ``nx - 1`` hops, exactly like the
    simulator), weighted ``1 + crit_scale * crit / crit_max`` so edges on the
    critical chain count more (they are latency-, not just bandwidth-bound).
  * **slot pressure** — criticality-weighted load balance: each PE's load is
    the sum of its nodes' integer weights (same criticality ramp), and the
    term is the sum of squared loads. Quadratic pressure penalizes piling
    work — especially critical work — onto few PEs, which both serializes
    fire opportunities (1 fire/PE/cycle) and deepens local memories.

``total = traffic + pressure_weight * pressure``. The model is a pure jnp
function of the placement vector, so thousands of candidates score as one
``jax.vmap`` batch on-device (:meth:`CostModel.batch_cost`).
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from ..core.criticality import criticality as _criticality
from ..core.graph import DataflowGraph


def edge_endpoints(g: DataflowGraph) -> tuple[np.ndarray, np.ndarray]:
    """CSR fanout lists -> flat ([E] src, [E] dst) int32 endpoint arrays."""
    src = np.repeat(np.arange(g.num_nodes, dtype=np.int32), g.fanout_count())
    return src, g.fanout_dst.astype(np.int32)


def integer_weights(crit: np.ndarray, crit_scale: int) -> np.ndarray:
    """[N] int32 weights ``1 + crit_scale * crit / crit_max`` (floored)."""
    c = np.asarray(crit, dtype=np.int64)
    c = c - c.min() if c.size else c  # neg_slack labels are <= 0
    top = max(1, int(c.max(initial=0)))
    return (1 + (crit_scale * c) // top).astype(np.int32)


def edge_tables(
    g: DataflowGraph, *, metric: str = "height", crit_scale: int = 3,
) -> tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
    """Flat integer scoring tables ``(src, dst, w_edge, w_node)`` for ``g``.

    ``w_node`` is the criticality ramp :func:`integer_weights`; each edge
    carries its *source* node's weight (the token that travels is the source's
    result). This is the shared table builder behind :func:`build_cost_model`,
    the surrogate feature extractor (:mod:`repro.surrogate.features`) and the
    multilevel coarsener (:mod:`repro.place.coarsen`) — one definition of
    "edge weight" keeps their notions of criticality aligned.
    """
    crit = _criticality(g, metric)
    src, dst = edge_endpoints(g)
    w_node = integer_weights(crit, crit_scale)
    return src, dst, w_node[src].astype(np.int32), w_node


def torus_hops(src_pe, dst_pe, nx: int, ny: int):
    """Dimension-ordered hop count on the unidirectional nx x ny torus.

    PE ids follow the overlay convention ``pe = x * ny + y``.
    """
    sx, sy = src_pe // ny, src_pe % ny
    dx_, dy_ = dst_pe // ny, dst_pe % ny
    return jnp.mod(dx_ - sx, nx) + jnp.mod(dy_ - sy, ny)


@dataclasses.dataclass(frozen=True)
class CostModel:
    """Static per-graph scoring tables + the jnp cost functions."""

    nx: int
    ny: int
    src: jnp.ndarray          # [E] int32 edge source node
    dst: jnp.ndarray          # [E] int32 edge destination node
    w_edge: jnp.ndarray       # [E] int32 criticality edge weight
    w_node: jnp.ndarray       # [N] int32 criticality node weight
    pressure_weight: int

    @property
    def num_pes(self) -> int:
        return self.nx * self.ny

    def traffic(self, node_pe) -> jnp.ndarray:
        with jax.enable_x64(True):  # int64 accumulations must not wrap (see cost())
            node_pe = jnp.asarray(node_pe, jnp.int32)
            hops = torus_hops(node_pe[self.src], node_pe[self.dst],
                              self.nx, self.ny)
            return jnp.sum(self.w_edge.astype(jnp.int64)
                           * hops.astype(jnp.int64))

    def loads(self, node_pe) -> jnp.ndarray:
        """[P] int64 criticality-weighted node load per PE."""
        with jax.enable_x64(True):
            return jnp.zeros(self.num_pes, jnp.int64).at[
                jnp.asarray(node_pe, jnp.int32)].add(
                    self.w_node.astype(jnp.int64))

    def pressure(self, node_pe) -> jnp.ndarray:
        with jax.enable_x64(True):
            loads = self.loads(node_pe)
            return jnp.sum(loads * loads)

    def cost(self, node_pe) -> jnp.ndarray:
        """Scalar int64 cost of one [N] placement (jit-able).

        Runs under scoped x64 so the squared-load accumulation cannot wrap
        on large graphs (callers need no global ``jax_enable_x64``)."""
        with jax.enable_x64(True):
            node_pe = jnp.asarray(node_pe, jnp.int32)
            return (self.traffic(node_pe)
                    + self.pressure_weight * self.pressure(node_pe))

    def batch_cost(self, placements) -> jnp.ndarray:
        """[B] int64 costs of a stacked [B, N] candidate batch (one vmap)."""
        with jax.enable_x64(True):
            return jax.vmap(self.cost)(jnp.asarray(placements, jnp.int32))


def build_cost_model(
    g: DataflowGraph,
    nx: int,
    ny: int,
    *,
    metric: str = "height",
    crit_scale: int = 3,
    pressure_weight: int = 1,
) -> CostModel:
    """Precompute the scoring tables for ``g`` on an ``nx x ny`` grid."""
    src, dst, w_edge, w_node = edge_tables(
        g, metric=metric, crit_scale=crit_scale)
    return CostModel(
        nx=nx, ny=ny,
        src=jnp.asarray(src), dst=jnp.asarray(dst),
        w_edge=jnp.asarray(w_edge),   # edge carries its source's weight
        w_node=jnp.asarray(w_node),
        pressure_weight=int(pressure_weight),
    )
