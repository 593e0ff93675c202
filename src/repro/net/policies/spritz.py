"""Spritz sender-based load balancing core (paper §IV, Algorithms 1-3).

All state is batched over flows as fixed-shape JAX arrays so the whole
control loop jit-compiles inside the simulator's device driver:

  w            [F, P]  sampling weights (Eq. 1 init; 0 = temporarily blocked)
  w_orig       [F, P]  pristine weights (timer restore target)
  ecn_counts   [F, P]  per-path ECN counters (Scout)
  buffer       [F, B]  cached good-path EV ids, -1 = empty slot (B = 8)
  packet_count [F]     packets since last forced exploration
  blocked_until[F, P]  tick at which a timeout-blocked path is re-enabled

Variants: SCOUT keeps the buffer front until negative feedback evicts it;
SPRAY pops the front on every use (circular good-path consumption).
OPS(u)/OPS(w) reuse the same send path with ``always_sample=True``.

This module also registers the three Spritz schemes (Scout, Spray-u,
Spray-w) with the sender-policy layer (DESIGN.md §11); the shared
``SpritzState`` is the ``"spritz"`` family substate in the engine's
stacked policy dict.  ``repro.core.spritz`` re-exports everything here
for backwards compatibility.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.net.policies import base as PB

SCOUT = 0
SPRAY = 1

BUF_SLOTS = 8  # paper: "fixed size buffer_paths with 8 positions"


class SpritzConfig(NamedTuple):
    explore_threshold: int = 44     # packets (0.5 * BDP, Table II)
    ecn_threshold: int = 8          # marked ACKs per path  (~0.1 * BDP)
    ecn_rate_bias: float = 0.9      # ecn_rate above which we bias minimal
    min_bias_factor: float = 8.0    # w[0] override under uniform congestion
    block_ticks: int = 1 << 18      # timeout-block duration (global timer;
    #   §IV-C: tuned to failure durations — long relative to experiment time
    #   so a dead path is probed at most a handful of times)
    insert_cooldown: int = 2048     # Scout: an ECN/NACK-evicted EV may not
    #   re-enter buffer_paths for this many ticks.  DEVIATION (DESIGN §9):
    #   Alg. 2 has no cooldown, which under *partial* marking (mark rate
    #   < 1) lets a low-latency congested path re-insert at the buffer
    #   front on every occasional clean ACK — the latency-sorted buffer
    #   then pins it again and the flow oscillates.  One-RTT-scale
    #   hysteresis restores the paper's "reuse good paths until negative
    #   feedback" intent; at mark rates ~1 (the paper's regime) it is a
    #   no-op because those paths never produce clean ACKs.
    variant: int = SCOUT
    always_sample: bool = False     # True => OPS behaviour (no buffer/state)
    # §IV ❸-1 "Update weight: increase or decrease w_i" — the framework's
    # weight-update action (Scout uses it to steer exploration away from
    # marked/trimmed paths; factors are ours, the paper gives none).
    weight_update: bool = True
    w_down: float = 0.5
    w_up: float = 1.25
    w_floor: float = 0.05
    use_kernels: bool = False       # route Algorithm 1's selection core
    #   through kernels.spritz_select (DESIGN.md §14); bit-identical to
    #   the jnp path — both consume ONE uniform(rng, (F, 1)) draw and run
    #   the same prefix-sum (policies.base.prefix_sum)/compare math per row


class SpritzState(NamedTuple):
    w: jnp.ndarray              # [F, P] float32
    w_orig: jnp.ndarray         # [F, P] float32
    ecn_counts: jnp.ndarray     # [F, P] int32
    buffer: jnp.ndarray         # [F, B] int32 (EV ids, -1 empty)
    packet_count: jnp.ndarray   # [F] int32
    blocked_until: jnp.ndarray  # [F, P] int32
    no_insert_until: jnp.ndarray  # [F, P] i32 (Scout eviction cooldown)


def init_state(weights: jnp.ndarray) -> SpritzState:
    """weights: [F, P] Eq.-1 weights (0 beyond each flow's n_paths)."""
    F, P = weights.shape
    return SpritzState(
        w=weights.astype(jnp.float32),
        w_orig=weights.astype(jnp.float32),
        ecn_counts=jnp.zeros((F, P), jnp.int32),
        buffer=jnp.full((F, BUF_SLOTS), -1, jnp.int32),
        packet_count=jnp.zeros((F,), jnp.int32),
        blocked_until=jnp.zeros((F, P), jnp.int32),
        no_insert_until=jnp.zeros((F, P), jnp.int32),
    )


_weighted_sample = PB.weighted_sample_rows  # one shared draw (DESIGN.md §5)


def effective_weights(state: SpritzState, t: jnp.ndarray) -> jnp.ndarray:
    """Apply the timeout-block timer: blocked paths contribute 0; expired
    blocks are (lazily) restored to their original Eq.-1 weight."""
    blocked = t < state.blocked_until
    return jnp.where(blocked, 0.0, jnp.where(state.w == 0.0, state.w_orig, state.w))


# --------------------------------------------------------------------- send
def send_logic(state: SpritzState, cfg: SpritzConfig, rng: jax.Array,
               t: jnp.ndarray, active: jnp.ndarray
               ) -> tuple[SpritzState, jnp.ndarray, jnp.ndarray]:
    """Algorithm 1 for every flow at once.

    active: [F] bool — flows that emit a packet this tick.  State only
    mutates for active flows.  Returns (new_state, ev_index[F],
    explored[F]) — `explored` marks packets whose path came from weighted
    sampling rather than the good-path buffer (used for the network-wide
    ECN-rate estimate behind the minimal-bias rule).
    """
    w_eff = effective_weights(state, t)

    if cfg.always_sample:  # OPS(u)/OPS(w): stateless spraying
        sampled = _weighted_sample(rng, w_eff)
        return state, sampled, jnp.ones_like(sampled, dtype=bool)

    explore = state.packet_count >= cfg.explore_threshold
    buf_front = state.buffer[:, 0]
    buf_nonempty = buf_front >= 0
    # §IV-C timer: a buffered EV whose timeout-block is still running must
    # not be reused — e.g. a path that died *after* it was cached.  The
    # sender falls back to weighted sampling (which also zeroes blocked
    # paths); Spray additionally consumes the dead front so its circular
    # walk skips over still-blocked EVs instead of wedging on one.
    front_blocked = buf_nonempty & (
        jnp.take_along_axis(state.blocked_until,
                            jnp.maximum(buf_front, 0)[:, None],
                            axis=1)[:, 0] > t)

    if cfg.use_kernels:
        # the kernel fuses sampling + explore-counter + front selection;
        # a blocked front is passed as -1 (empty), which reproduces the
        # use_buffer = ~explore & nonempty & ~blocked rule exactly
        from repro.kernels import ops as KOPS
        front_eff = jnp.where(front_blocked, -1, buf_front)
        u = jax.random.uniform(rng, (w_eff.shape[0], 1))[:, 0]
        ev, _, use_buffer = KOPS.spritz_select(
            w_eff, u, front_eff, state.packet_count,
            explore_threshold=cfg.explore_threshold)
    else:
        sampled = _weighted_sample(rng, w_eff)
        use_buffer = (~explore) & buf_nonempty & ~front_blocked
        ev = jnp.where(use_buffer, buf_front, sampled)

    # Spray consumes the front slot whenever the walk consults the buffer —
    # either using a live front or discarding a blocked one.  Explore ticks
    # never consult it, so they leave the buffer untouched (Algorithm 1).
    popped = jnp.concatenate(
        [state.buffer[:, 1:], jnp.full((state.buffer.shape[0], 1), -1, jnp.int32)],
        axis=1,
    )
    pop = (~explore) & buf_nonempty & (cfg.variant == SPRAY) & active
    new_buffer = jnp.where(pop[:, None], popped, state.buffer)

    new_count = jnp.where(explore, 0, state.packet_count + 1)
    new_count = jnp.where(active, new_count, state.packet_count)

    return (state._replace(buffer=new_buffer, packet_count=new_count),
            ev, ~use_buffer)


# ----------------------------------------------------------------- feedback
ACK_OK, ACK_ECN, NACK, TIMEOUT, NO_FB = 0, 1, 2, 3, 4


def _buffer_remove(buffer: jnp.ndarray, ev: jnp.ndarray,
                   mask: jnp.ndarray) -> jnp.ndarray:
    """Remove (all occurrences of) ev from each masked row, compacting left."""
    B = buffer.shape[1]
    hit = (buffer == ev[:, None]) & mask[:, None]
    kept = jnp.where(hit, -1, buffer)
    # stable-compact: order by (is_empty, slot index)
    key = jnp.where(kept < 0, B + jnp.arange(B), jnp.arange(B))
    order = jnp.argsort(key, axis=1)
    return jnp.take_along_axis(kept, order, axis=1)


def _buffer_insert_sorted(buffer: jnp.ndarray, ev: jnp.ndarray,
                          lat: jnp.ndarray, path_lat: jnp.ndarray,
                          mask: jnp.ndarray) -> jnp.ndarray:
    """Scout: insert ev by ascending latency into rows where mask holds,
    only if not already present and a free slot exists."""
    B = buffer.shape[1]
    present = jnp.any(buffer == ev[:, None], axis=1)
    size = jnp.sum((buffer >= 0).astype(jnp.int32), axis=1)
    do = mask & (~present) & (size < B) & (ev >= 0)

    BIG = jnp.float32(3.4e38)
    buf_lat = jnp.where(
        buffer >= 0,
        jnp.take_along_axis(path_lat, jnp.maximum(buffer, 0), axis=1),
        BIG,
    )
    # position = number of existing entries with latency <= candidate
    pos = jnp.sum((buf_lat <= lat[:, None]).astype(jnp.int32), axis=1)
    idx = jnp.arange(B)[None, :]
    shifted = jnp.concatenate([buffer[:, :1], buffer[:, :-1]], axis=1)
    inserted = jnp.where(
        idx < pos[:, None], buffer,
        jnp.where(idx == pos[:, None], ev[:, None], shifted),
    )
    return jnp.where(do[:, None], inserted, buffer)


def _buffer_push_back(buffer: jnp.ndarray, ev: jnp.ndarray,
                      mask: jnp.ndarray) -> jnp.ndarray:
    """Spray: append ev (duplicates allowed) if a slot is free."""
    B = buffer.shape[1]
    size = jnp.sum((buffer >= 0).astype(jnp.int32), axis=1)
    do = mask & (size < B) & (ev >= 0)
    idx = jnp.arange(B)[None, :]
    appended = jnp.where(idx == size[:, None], ev[:, None], buffer)
    return jnp.where(do[:, None], appended, buffer)


def feedback_logic(state: SpritzState, cfg: SpritzConfig,
                   ev: jnp.ndarray, fb_type: jnp.ndarray,
                   ecn_rate: jnp.ndarray, path_lat: jnp.ndarray,
                   t: jnp.ndarray) -> SpritzState:
    """Algorithms 2 (Scout) / 3 (Spray), batched over flows.

    ev       [F] path index the feedback refers to (same EV echoed by receiver)
    fb_type  [F] one of ACK_OK/ACK_ECN/NACK/TIMEOUT/NO_FB
    ecn_rate [F] sender's running ECN-mark rate (from the CC layer)
    path_lat [F, P] per-path latency (ns) for sorted insertion
    """
    if cfg.always_sample:  # OPS: no feedback loop
        return state

    F = ev.shape[0]
    evc = jnp.clip(ev, 0, state.w.shape[1] - 1)
    lat = jnp.take_along_axis(path_lat, evc[:, None], axis=1)[:, 0]
    onehot = jax.nn.one_hot(evc, state.w.shape[1], dtype=jnp.int32)

    is_ok = fb_type == ACK_OK
    is_ecn = fb_type == ACK_ECN
    is_nack = fb_type == NACK
    is_to = fb_type == TIMEOUT

    buffer = state.buffer
    ecn_counts = state.ecn_counts
    w = state.w
    blocked_until = state.blocked_until

    no_insert_until = state.no_insert_until
    if cfg.variant == SCOUT:
        # framework weight update: negative feedback halves the sampling
        # weight, positive feedback recovers it toward the Eq-1 value.
        if cfg.weight_update:
            sel = onehot.astype(bool)
            bad = (is_ecn | is_nack)[:, None] & sel
            good = is_ok[:, None] & sel
            w = jnp.where(bad & (w > 0),
                          jnp.maximum(w * cfg.w_down, cfg.w_floor), w)
            w = jnp.where(good & (w > 0),
                          jnp.minimum(w * cfg.w_up, state.w_orig), w)
        # ACK (no ECN): cache good path, sorted by latency, deduplicated —
        # unless the path is inside its eviction cooldown (see SpritzConfig).
        in_cooldown = jnp.take_along_axis(no_insert_until, evc[:, None],
                                          axis=1)[:, 0] > t
        buffer = _buffer_insert_sorted(buffer, evc, lat, path_lat,
                                       is_ok & ~in_cooldown)
        # ACK (ECN): count marks; above threshold -> evict from cache.
        ecn_counts = ecn_counts + onehot * is_ecn[:, None]
        over = (jnp.take_along_axis(ecn_counts, evc[:, None], axis=1)[:, 0]
                > cfg.ecn_threshold) & is_ecn
        evict = over | is_nack | is_to
        ecn_counts = jnp.where(evict[:, None] & onehot.astype(bool),
                               0, ecn_counts)
        buffer = _buffer_remove(buffer, evc, evict)
        no_insert_until = jnp.where(
            evict[:, None] & onehot.astype(bool),
            t + cfg.insert_cooldown, no_insert_until)
    else:  # SPRAY: only positive feedback refills; ECN/NACK ignored.
        buffer = _buffer_push_back(buffer, evc, is_ok)

    # Timeout: temporarily block the path (both variants).
    blocked_until = jnp.where(
        (is_to[:, None] & onehot.astype(bool)),
        t + cfg.block_ticks, blocked_until)
    w = jnp.where(is_to[:, None] & onehot.astype(bool), 0.0, w)

    # Uniformly high congestion: bias toward the minimal path (index 0).
    bias = (ecn_rate > cfg.ecn_rate_bias) & (fb_type != NO_FB)
    w = w.at[:, 0].set(jnp.where(bias, cfg.min_bias_factor, w[:, 0]))

    return state._replace(w=w, ecn_counts=ecn_counts, buffer=buffer,
                          blocked_until=blocked_until,
                          no_insert_until=no_insert_until)


# ------------------------------------------------- policy layer adapters --
FAMILY = "spritz"


def _make_cfg(variant):
    def make_cfg(spec) -> SpritzConfig:
        return SpritzConfig(
            variant=variant,
            explore_threshold=spec.explore_threshold,
            ecn_threshold=spec.ecn_threshold,
            min_bias_factor=spec.min_bias_factor,
            block_ticks=spec.block_ticks,
            always_sample=False,
            use_kernels=bool(getattr(spec, "use_kernels", False)),
        )
    return make_cfg


def _init_state(weights: jnp.ndarray, static_path: jnp.ndarray) -> SpritzState:
    del static_path
    return init_state(weights)


def _choose_path(state: SpritzState, cfg: SpritzConfig,
                 tables: PB.PolicyTables, ctx: PB.SendCtx):
    state, ev, explored = send_logic(state, cfg, ctx.rng, ctx.t, ctx.active)
    return ev, explored, state


def _on_feedback(state: SpritzState, cfg: SpritzConfig,
                 tables: PB.PolicyTables, ctx: PB.FeedbackCtx) -> SpritzState:
    return feedback_logic(state, cfg, ctx.ev, ctx.fb_type, ctx.ecn_rate,
                          tables.path_lat, ctx.t)


def _policy(name: str, code: int, variant: int, *, uniform: bool,
            flow_level: PB.FlowLevelRule, doc: str) -> PB.PolicyDef:
    return PB.PolicyDef(
        name=name, code=code, family=FAMILY,
        make_cfg=_make_cfg(variant),
        choose_path=_choose_path, on_feedback=_on_feedback,
        init_state=_init_state,
        uniform_weights=uniform, failover=True, flow_level=flow_level,
        doc=doc)


def make_policies(codes) -> tuple[PB.PolicyDef, ...]:
    """codes: (SCOUT, SPRAY_U, SPRAY_W) integer scheme ids."""
    scout, spray_u, spray_w = codes
    # Flow level (DESIGN.md §12): all three collapse to hot-link eviction
    # with hysteresis — sample a few candidates, move only on a clear
    # max-load win (the good-path cache's reuse-until-negative-feedback
    # stability).  Scout additionally prefers low-latency candidates on
    # load ties (its buffer is latency-sorted).
    return (
        _policy("spritz_scout", scout, SCOUT, uniform=False,
                flow_level=PB.FlowLevelRule("evict", init="weighted",
                                            cands="eq1_scaled",
                                            latency_pref=True),
                doc="Spritz-Scout: latency-sorted good-path cache (Alg. 2)"),
        _policy("spritz_spray_u", spray_u, SPRAY, uniform=True,
                flow_level=PB.FlowLevelRule("evict", cands="eq1"),
                doc="Spritz-Spray, uniform weights (Alg. 3)"),
        _policy("spritz_spray_w", spray_w, SPRAY, uniform=False,
                flow_level=PB.FlowLevelRule("evict", init="weighted",
                                            cands="eq1_scaled"),
                doc="Spritz-Spray, Eq.-1 weights (Alg. 3)"),
    )
