"""Progressive filtering cascades, TPU-native (paper §III, Fig. 2 & 4b).

The face-authentication pipeline is a cascade: motion detection passes a
fraction of frames to Viola-Jones, which passes a fraction of windows to
the NN.  The VJ classifier is *itself* a cascade of stages.  The paper's
observation is that this structure "spend[s] more computation on windows
where there is likely to be a face, rather than executing a uniform
computation at every window."

On a GPU/ASIC this is data-dependent control flow.  On TPU, data-dependent
shapes are hostile to XLA, so we adapt the idea (DESIGN.md §2) with two
TPU-idiomatic mechanisms:

1. **Masked cascade** (:func:`masked_cascade`): every stage computes on the
   full batch but multiplies by a live-mask; `jax.lax.cond`-free, fully
   static.  This saves *no* FLOPs but gives exact cascade semantics —
   it is the oracle, and what you use when stages are cheap.

2. **Compacting cascade** (:func:`compacting_cascade`): after each stage,
   survivors are *compacted* to the front (stable argsort on the mask) and
   the next stage runs on a statically-bounded prefix — a *capacity* in
   the MoE sense.  Work drops geometrically with stage selectivity while
   shapes stay static: this is the paper's "86% fewer classifier
   invocations" knob expressed for a systolic machine.  Overflowing
   survivors beyond capacity are dropped and counted (like MoE token
   dropping); capacities are chosen from measured stage selectivities the
   same way the paper chose window step/scale from workload statistics.

Both mechanisms are shape-polymorphic and jit/pjit-compatible; the
compacting variant is what `examples/cascade_serving.py` uses to put a
cheap scorer in front of a large LM — "Viola-Jones in front of the NN" for
an inference cluster.
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Callable, Sequence

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class Stage:
    """One cascade stage.

    fn:        (carry_items) -> scores, shape (batch,) float.  Items with
               score >= threshold survive.  fn must be jit-traceable.
    threshold: survival threshold.
    name:      for reporting.
    """

    fn: Callable
    threshold: float
    name: str = "stage"


@dataclasses.dataclass
class CascadeResult:
    mask: jax.Array            # (batch,) bool — survived every stage
    scores: jax.Array          # (n_stages, batch) raw scores (masked stages = -inf)
    n_survivors: jax.Array     # (n_stages,) int32 survivor counts per stage
    dropped: jax.Array         # (n_stages,) int32 capacity-overflow drops


def masked_cascade(stages: Sequence[Stage], items: jax.Array) -> CascadeResult:
    """Exact cascade semantics via masking; computes every stage on all items."""
    batch = items.shape[0]
    mask = jnp.ones((batch,), dtype=bool)
    all_scores = []
    counts = []
    for st in stages:
        scores = st.fn(items)
        scores = jnp.where(mask, scores, -jnp.inf)
        mask = mask & (scores >= st.threshold)
        all_scores.append(scores)
        counts.append(jnp.sum(mask).astype(jnp.int32))
    return CascadeResult(
        mask=mask,
        scores=jnp.stack(all_scores),
        n_survivors=jnp.stack(counts),
        dropped=jnp.zeros((len(stages),), jnp.int32),
    )


def _compact(items: jax.Array, mask: jax.Array, capacity: int):
    """Stable-move survivors to the front; return (compacted, perm, kept_mask).

    Static shapes: output batch == capacity.  Survivors beyond capacity are
    dropped (counted by the caller).  Non-survivors fill the tail of the
    capacity window and are masked off.
    """
    batch = items.shape[0]
    # key: survivors first (0), then dead (1); stable by original index.
    order = jnp.argsort(jnp.where(mask, 0, 1), stable=True)
    perm = order[:capacity]
    compacted = jnp.take(items, perm, axis=0)
    kept_mask = jnp.take(mask, perm, axis=0)
    return compacted, perm, kept_mask


def compacting_cascade(
    stages: Sequence[Stage],
    items: jax.Array,
    capacities: Sequence[int],
) -> CascadeResult:
    """Cascade with survivor compaction to statically-bounded batches.

    ``capacities[i]`` bounds the number of items stage ``i`` processes.
    ``capacities[0]`` must equal ``items.shape[0]``.  Returns masks/scores
    in the *original* index space.
    """
    if len(capacities) != len(stages):
        raise ValueError("need one capacity per stage")
    batch = items.shape[0]
    if capacities[0] != batch:
        raise ValueError("capacities[0] must equal the input batch")

    # original-index bookkeeping
    idx = jnp.arange(batch)
    cur_items, cur_idx = items, idx
    cur_mask = jnp.ones((batch,), bool)

    full_mask = jnp.ones((batch,), bool)
    all_scores = []
    counts = []
    drops = []

    for i, st in enumerate(stages):
        cap = capacities[i]
        if cur_items.shape[0] != cap:
            # count drops before shrinking
            n_live = jnp.sum(cur_mask)
            dropped_here = jnp.maximum(n_live - cap, 0).astype(jnp.int32)
            cur_items, perm, cur_mask = _compact(cur_items, cur_mask, cap)
            cur_idx = jnp.take(cur_idx, perm, axis=0)
        else:
            dropped_here = jnp.int32(0)

        # the stage's name scopes its device ops (HLO op_name metadata,
        # read off a profiler trace; free at run time)
        with jax.named_scope(st.name):
            scores = st.fn(cur_items)
        scores = jnp.where(cur_mask, scores, -jnp.inf)
        cur_mask = cur_mask & (scores >= st.threshold)

        # scatter scores / mask back to original index space; items dropped by
        # capacity are no longer carried, hence read back as dead.
        full_scores = jnp.full((batch,), -jnp.inf, scores.dtype).at[cur_idx].set(scores)
        full_mask = jnp.zeros((batch,), bool).at[cur_idx].set(cur_mask)

        all_scores.append(full_scores)
        counts.append(jnp.sum(cur_mask).astype(jnp.int32))
        drops.append(dropped_here)

    return CascadeResult(
        mask=full_mask,
        scores=jnp.stack(all_scores),
        n_survivors=jnp.stack(counts),
        dropped=jnp.stack(drops),
    )


def capacities_from_counts(batch: int, survivor_counts: Sequence[int],
                           margin: float = 1.5, quantum: int = 128) -> list:
    """Derive compacting capacities from *measured* per-stage survivor counts.

    ``survivor_counts[i]`` is the (max over calibration items) number of
    survivors after stage ``i``; stage ``i + 1``'s capacity bounds exactly
    that population.  ``margin`` multiplies the measurement and ``quantum``
    rounds up (lane-width friendly), so natural workload variation does not
    overflow into drops — the same measure-then-set-the-knob procedure the
    paper uses for window scale/step.  Stage 0 always gets the full batch.
    """
    caps = [int(batch)]
    for c in list(survivor_counts)[:-1]:
        cap = (int(math.ceil(float(c) * margin)) // quantum + 1) * quantum
        caps.append(int(min(batch, max(quantum, cap))))
    return caps


def compaction_work(stage_costs: Sequence[float], batch: int,
                    capacities: Sequence[int] | None = None) -> tuple:
    """(masked_total, compacted_total) unit-work for one cascade pass.

    The masked oracle evaluates every stage on the full batch; compaction
    clips stage ``i`` to ``capacities[i]``.  The ratio is the *actual* FLOP
    saving static-shape compaction realizes (vs the data-dependent ideal
    that ``cascade_flops`` counts).
    """
    masked = float(batch) * float(sum(stage_costs))
    if capacities is None:
        return masked, masked
    compacted = float(sum(float(c) * float(f)
                          for c, f in zip(capacities, stage_costs)))
    return masked, compacted


def cascade_flops(
    stage_flops: Sequence[float],
    selectivities: Sequence[float],
    capacities: Sequence[float] | None = None,
) -> float:
    """Expected per-item FLOPs of a cascade (analysis-side companion).

    With no capacities this is the paper's energy argument: stage i costs
    ``stage_flops[i] * prod(selectivities[:i])``.  With capacities, work is
    additionally clipped — the static-shape price of the TPU adaptation.
    """
    total = 0.0
    frac = 1.0
    for i, f in enumerate(stage_flops):
        eff = frac
        if capacities is not None:
            eff = min(eff, capacities[i])
        total += f * eff
        frac *= selectivities[i]
    return total
