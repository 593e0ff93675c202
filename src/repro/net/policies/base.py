"""Sender-policy protocol (DESIGN.md §11).

A load-balancing scheme is a *policy*: a set of pure, flow-batched
functions over a per-flow state pytree, registered in
``repro.net.policies.registry``.  The engine never names a scheme — its
tick dispatches ``choose_path`` / ``on_feedback`` through a single
``lax.switch`` over the registry-ordered branches, so adding a scheme is
a registry addition, not an engine edit.

Protocol (all device-side functions are jit-traceable; ``state`` is the
policy *family's* substate inside the stacked policy dict, or ``None``
for stateless families):

    init_state(weights, static_path) -> state            (host, once)
    choose_path(state, cfg, tables, ctx) -> (path, explored, state)
    on_feedback(state, cfg, tables, ctx) -> state

``choose_path`` runs every executed tick for every flow and must only
mutate state for ``ctx.active`` flows (and tick-pure bookkeeping like
FLICR's move/reset, which is identity when no feedback accrued);
``on_feedback`` must be the identity when ``ctx.fb_type == FB_NONE``.
Both invariants are what keep the event-horizon jump bit-exact
(DESIGN.md §4) — a policy that mutates state on an event-free tick
desynchronizes the compressed driver from the dense reference and fails
``tests/test_engine_equiv.py``.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp


class PolicyTables(NamedTuple):
    """Static per-spec device arrays every policy may consult."""

    path_ports: jax.Array      # [F, P, H] global port id per hop (-1 pad)
    path_len: jax.Array        # [F, P] hops incl. delivery port
    path_lat: jax.Array        # [F, P] f32 path latency (Scout's sort key)
    valiant_w: jax.Array       # [F, P] per-hop-uniform Valiant weights
    min_path: jax.Array        # [F] index of the minimal/static route


class SendCtx(NamedTuple):
    """Per-tick dynamic inputs to ``choose_path``."""

    rng: jax.Array             # positional per-tick path key (fold_in(base, t))
    t: jax.Array               # [] i32 current tick
    active: jax.Array          # [F] bool — flows that emit a packet this tick
    occ: jax.Array             # [n_ports] i32 analytic queue occupancy
    weights: jax.Array         # [F, P] lane sampling weights for this scheme
    static_path: jax.Array     # [F] lane ECMP/minimal static choice


class FeedbackCtx(NamedTuple):
    """Per-tick feedback inputs to ``on_feedback``: the representative
    event per flow (priority TO > NACK > ECN > clean ACK, DESIGN.md §9)
    plus the exact per-class counts of this tick."""

    t: jax.Array               # [] i32
    ev: jax.Array              # [F] path index the feedback refers to
    fb_type: jax.Array         # [F] FB_* code (FB_NONE = no event this tick)
    ecn_rate: jax.Array        # [F] f32 running ECN rate over sampled packets
    n_mark: jax.Array          # [F] i32 ECN-marked ACKs this tick
    n_nack: jax.Array          # [F] i32 NACKs (trims) this tick
    n_to: jax.Array            # [F] i32 RTO timeouts this tick


@dataclasses.dataclass(frozen=True)
class FlowLevelRule:
    """Flow-level re-selection abstraction of a scheme (DESIGN.md §12).

    The flow-level engine (``repro.fabric.flowsim``) sees no packets:
    each policy instead declares how its per-packet control loop
    collapses to one path-(re)selection decision per progressive-filling
    epoch.  ``kind`` picks the host-side re-selection lane:

    * ``static``  — pick once at flow start, never move (MINIMAL, ECMP,
      and — a documented fidelity limit — per-flow VALIANT);
    * ``respray`` — oblivious redraw every epoch (OPS u/w: the
      time-average of per-packet spraying);
    * ``ugal``    — when the current path crosses a hot link, compare
      against one random candidate by *first-hop* load (the UGAL-L
      information set);
    * ``evict``   — when the current path crosses a hot link, sample
      ``n_cands`` candidates and move to the least-loaded only on a
      ``>= (1 - hysteresis)`` max-load improvement (Spritz hot-link
      eviction; the good-path cache's reuse-until-negative-feedback
      stability);
    * ``recycle`` — keep the current path while it stays clean, redraw
      fresh uniform entropy the moment it crosses a hot link (REPS
      entropy recycling: hot == the ECN mark that stops a recycle).

    ``init`` chooses the flow-start path (``minimal`` | ``uniform`` |
    ``weighted`` Eq.-1 at the engine's ``w_scale``); ``cands`` the
    candidate distribution (``uniform`` | ``eq1`` latency weights at
    scale 1 | ``eq1_scaled`` at the engine's ``w_scale``).
    ``latency_pref`` breaks candidate-load ties toward lower-latency
    paths (Scout's latency-sorted buffer).  Failed paths are masked out
    of every lane's candidate set; a flow whose current path crosses a
    down port is force-reselected on adaptive lanes (never on
    ``static``).
    """

    kind: str
    init: str = "uniform"
    cands: str = "uniform"
    n_cands: int = 4
    hysteresis: float = 0.8
    latency_pref: bool = False

    def __post_init__(self):
        if self.kind not in ("static", "respray", "ugal", "evict", "recycle"):
            raise ValueError(f"unknown flow-level kind {self.kind!r}")
        if self.init not in ("minimal", "uniform", "weighted"):
            raise ValueError(f"unknown flow-level init {self.init!r}")
        if self.cands not in ("uniform", "eq1", "eq1_scaled"):
            raise ValueError(f"unknown flow-level cands {self.cands!r}")


@dataclasses.dataclass(frozen=True)
class PolicyDef:
    """One registered scheme (see ``registry.register``).

    ``family`` keys the scheme's substate inside the stacked policy dict
    carried by the engine; schemes sharing state (Scout and both Sprays)
    share a family.  ``uniform_weights`` / ``pin_minimal`` are the
    host-side lane rules ``build_spec`` and ``lane_arrays`` read instead
    of the old integer if-ladders; ``failover`` marks schemes able to
    adapt around failures (the ``bench_failures`` scheme set);
    ``flow_level`` is the scheme's :class:`FlowLevelRule` — required,
    so every registered scheme runs at flow level (DESIGN.md §12).
    """

    name: str
    code: int
    family: str | None
    make_cfg: Callable[[Any], Any]
    choose_path: Callable[..., tuple]
    on_feedback: Callable[..., Any] | None = None
    init_state: Callable[[jnp.ndarray, jnp.ndarray], Any] | None = None
    uniform_weights: bool = False
    pin_minimal: bool = False
    failover: bool = False
    flow_level: FlowLevelRule | None = None
    doc: str = ""


def prefix_sum(w: jnp.ndarray) -> jnp.ndarray:
    """Inclusive prefix sum over the last axis as a sequential chain of
    f32 adds (``c_j = c_{j-1} + w_j``).  ``jnp.cumsum`` lowers to a
    different association per backend, so the sampler fixes the order
    here; the Pallas ``spritz_select`` kernel adds in the same order,
    which keeps the kernel and jnp paths bit-identical on every
    backend (DESIGN.md §14)."""
    cols = [w[..., 0]]
    for j in range(1, w.shape[-1]):
        cols.append(cols[-1] + w[..., j])
    return jnp.stack(cols, axis=-1)


def weighted_sample_rows(rng: jax.Array, w: jnp.ndarray) -> jnp.ndarray:
    """Per-row weighted index sample from ONE shared uniform draw.

    Every policy's sampler must route its randomness through this exact
    draw (``uniform(rng, (F, 1))``): the batched driver evaluates all
    registry branches under ``vmap`` and selects by lane scheme id, so a
    lane is bit-identical to the specialized solo run only because each
    branch consumes the tick key identically (DESIGN.md §5).
    Rows with all-zero weights fall back to index 0.
    """
    csum = prefix_sum(w)
    u = jax.random.uniform(rng, (w.shape[0], 1)) * jnp.maximum(
        csum[:, -1:], 1e-30)
    return jnp.minimum(jnp.sum((csum < u).astype(jnp.int32), -1),
                       w.shape[-1] - 1)


def all_explored(ref: jnp.ndarray) -> jnp.ndarray:
    """Default ``explored`` flags: every packet counts as sampled."""
    return jnp.ones(ref.shape[0], bool)
