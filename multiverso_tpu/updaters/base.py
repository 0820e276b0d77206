"""Updater base + registry + the default (plain add) updater.

Reference: ``include/multiverso/updater/updater.h`` — base ``Update``/
``Access`` virtuals and the ``GetUpdater`` factory switch (SURVEY.md §2.16).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, Optional, Tuple, Type

import jax
import jax.numpy as jnp

from .. import metrics

__all__ = ["AddOption", "GetOption", "Updater", "register_updater",
           "get_updater", "updater_names", "aggregate_rows",
           "scatter_apply"]


@dataclass(frozen=True)
class AddOption:
    """Per-Add hyper-parameters (reference ``AddOption``; SURVEY.md §2.10).

    The reference packs these into the message header; here they are static
    jit arguments (python floats hash into the compilation cache).
    """

    learning_rate: float = 0.1
    momentum: float = 0.9
    rho: float = 0.9          # smoothing coefficient (smooth_gradient)
    eps: float = 1e-8         # adagrad denominator floor
    worker_id: int = -1       # carried for parity; unused by math


@dataclass(frozen=True)
class GetOption:
    """Per-Get options (reference ``GetOption``); reserved for parity."""

    worker_id: int = -1


State = Tuple[jax.Array, ...]


class Updater:
    """Pure-functional updater. Subclasses override the three hooks.

    All hooks are shape-polymorphic and jittable; tables call them inside
    their compiled push path (dense) or scatter path (rows).
    """

    name = "default"
    num_slots = 0  # state arrays, each shaped like the table
    # True iff apply is linear in the delta, i.e. scatter-adding duplicate
    # rows equals applying their pre-aggregated sum.  Non-linear updaters
    # (stateful or normalized) require duplicate rows to be segment-summed
    # first — eager tables do it host-side; fused steps via aggregate_rows.
    linear = True

    # -- state --------------------------------------------------------------
    def init_state(self, shape, dtype) -> State:
        return tuple(jnp.zeros(shape, dtype) for _ in range(self.num_slots))

    # -- dense path ---------------------------------------------------------
    def apply_dense(self, w: jax.Array, state: State, delta: jax.Array,
                    opt: AddOption) -> Tuple[jax.Array, State]:
        return w + delta, state

    # -- sparse (row) path --------------------------------------------------
    def row_increment(self, delta: jax.Array, opt: AddOption) -> jax.Array:
        """What a ``linear`` updater adds to a row for its ``delta``.

        A linear updater with no state is this function and nothing else:
        ``apply_rows`` scatter-adds it, and a fused step may add it to the
        rows by another route (``scatter_apply``).  Non-linear updaters
        override ``apply_rows`` and never see this called.
        """
        return delta

    def apply_rows(self, w: jax.Array, state: State, rows: jax.Array,
                   delta: jax.Array, opt: AddOption,
                   mask: Optional[jax.Array] = None
                   ) -> Tuple[jax.Array, State]:
        """Scatter-apply to ``w[rows]``.

        ``rows``: int32 [k]; ``delta``: [k, cols]; ``mask``: bool [k] marks
        valid entries (padding rows carry mask=False and must not touch
        state). Default: plain scatter-add, duplicate rows accumulate.
        """
        rows = effective_rows(rows, mask, w.shape[0])
        return w.at[rows].add(self.row_increment(masked(delta, mask), opt),
                              mode="drop"), state


_REGISTRY: Dict[str, Type[Updater]] = {}


def register_updater(cls: Type[Updater]) -> Type[Updater]:
    _REGISTRY[cls.name] = cls
    return cls


register_updater(Updater)  # "default"
_REGISTRY["add"] = Updater  # alias


def get_updater(name: str) -> Updater:
    """Factory — reference ``Updater<T>::GetUpdater`` switch."""
    try:
        return _REGISTRY[name]()
    except KeyError:
        raise ValueError(
            f"unknown updater_type '{name}'; known: {sorted(_REGISTRY)}")


def updater_names():
    return sorted(_REGISTRY)


def aggregate_rows(rows: jax.Array, delta: jax.Array
                   ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Jittable static-shape segment-sum of duplicate row ids.

    Sorts the batch, sums each duplicate group into its first slot, and
    returns ``(uniq_rows [k], agg_delta [k, ...], mask [k])`` where surplus
    slots carry ``mask=False`` (feed all three to ``Updater.apply_rows`` —
    ``effective_rows`` turns masked slots into dropped scatters).  This is
    the in-jit equivalent of the host-side ``np.unique`` + segment-sum the
    eager tables do, required before any non-``linear`` updater.
    """
    order = jnp.argsort(rows)
    r = rows[order]
    d = delta[order]
    is_new = jnp.concatenate(
        [jnp.ones((1,), bool), r[1:] != r[:-1]])
    seg = jnp.cumsum(is_new) - 1
    agg = jnp.zeros_like(d).at[seg].add(d)
    uniq = jnp.zeros_like(r).at[seg].set(r)
    mask = jnp.zeros(r.shape, bool).at[seg].set(True)
    return uniq, agg, mask


def _row_kernel() -> Optional[bool]:
    """Whether this backend has the row-update kernel: ``None`` for no,
    else the kernel's ``interpret``.  A TPU compiles it; the tests run it
    in interpret mode by patching this."""
    return False if jax.default_backend() == "tpu" else None


def _one_device() -> bool:
    """Tables live on the context's mesh; a Mosaic call under plain ``jit``
    does not lower on a mesh of several devices."""
    from ..core import context

    try:
        return context.get_context().mesh.size == 1
    except RuntimeError:                        # no init(): jit's default
        return True


def scatter_apply(upd: "Updater", data, state, rows, delta, opt: AddOption):
    """In-jit row scatter through an updater.

    THE one spelling of "apply a row batch through an updater inside a
    fused step" (its callers: ``apps/word2vec.py``,
    ``apps/skipgram_mixture.py``); the scope it opens names all of it in
    their compiled programs, and ``tables.scatter_traced{path=}`` counts,
    at trace time, which body a compiled step holds:

    - ``kernel``: a linear updater's batch is applied **in row order**.
      The ids are sorted once (stable, so equal ids keep their batch order)
      and ``ops/row_update.py`` adds the permuted increments group of rows
      by group of rows, in place.  Taken where the code can see it is safe:
      on a TPU, the table on one device, float32 rows a whole number of
      lanes wide (a ``MatrixTable``'s ``stored_cols``), an updater that is
      its ``row_increment`` alone.
    - ``xla``: every other linear update, as ``Updater.apply_rows`` spells
      it: ``.at[rows].add``, batch order, duplicates the scatter's business.
    - ``aggregated``: a non-linear updater gets its duplicates
      segment-summed first (``aggregate_rows``), matching the eager path's
      host-side ``np.unique`` aggregation.
    """
    with jax.named_scope("tables.scatter_apply"):
        if not upd.linear:
            metrics.counter("tables.scatter_traced",
                            {"path": "aggregated"}).inc()
            uniq, agg, mask = aggregate_rows(rows, delta)
            return upd.apply_rows(data, state, uniq, agg, opt, mask=mask)
        from ..ops import row_update

        interpret = _row_kernel()
        if (interpret is None
                or type(upd).apply_rows is not Updater.apply_rows
                or not row_update.usable(data, rows, delta)
                or not _one_device()):
            metrics.counter("tables.scatter_traced", {"path": "xla"}).inc()
            return upd.apply_rows(data, state, rows, delta, opt)
        metrics.counter("tables.scatter_traced", {"path": "kernel"}).inc()
        # jnp's own reading of an id: negative ids count from the end, and
        # what is still out of range is dropped (it sorts last).
        num_rows = data.shape[0]
        rows = jnp.where(rows < 0, rows + num_rows, rows)
        rows = jnp.where((rows < 0) | (rows >= num_rows), num_rows, rows)
        order = jnp.argsort(rows, stable=True)
        return row_update.row_update(
            data, rows[order], upd.row_increment(delta, opt)[order],
            interpret=interpret), state


def masked(delta: jax.Array, mask: Optional[jax.Array]) -> jax.Array:
    """Zero out padding rows so they cannot perturb weights or state."""
    if mask is None:
        return delta
    return jnp.where(mask[:, None], delta, 0)


def effective_rows(rows: jax.Array, mask: Optional[jax.Array],
                   num_rows: int) -> jax.Array:
    """Redirect padding entries to an out-of-bounds index.

    With ``mode="drop"`` scatters, an out-of-bounds row is silently skipped,
    so padding can never clobber real rows — regardless of whether the caller
    padded with in-bounds indices. Callers must pre-aggregate duplicate rows
    (tables do, via segment-sum) before stateful ``.set`` updaters.
    """
    if mask is None:
        return rows
    return jnp.where(mask, rows, num_rows)
