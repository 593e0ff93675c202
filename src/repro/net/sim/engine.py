"""Packet-level network simulator with event-horizon time compression.

1 tick = 83.2 ns = serialization of one 4160 B packet @ 400 Gb/s.

TPU-native re-think of htsim's event queues (DESIGN.md §3): the in-flight
packet table is a fixed-shape structure-of-arrays; per-port FIFO order is
preserved *analytically* with a service-slot counter per port:

    depart(pkt) = max(tail[port], t) + rank_within_tick + 1
    tail[port] += #accepted            occupancy(port) = max(tail - t, 0)

so there are no queue data structures at all — enqueue, RED/ECN marking,
trimming, service, propagation, CC and the sender policy loop are dense
array ops over the packet table.  A packet's per-hop constants (port,
next port, service-to-next-event delay) are resolved once, when it joins
a queue, and carried in the table (the hop cache, DESIGN.md §14).

Time advances by *event horizon* rather than tick-by-tick (DESIGN.md §4):
every state change is anchored to an event tick (pending packet events,
RTO deadlines, flow starts, injection eligibility, deferred CC round
closure), so the driver jumps ``t`` straight to the next such tick.
Per-tick PRNG keys are derived positionally (``fold_in(base, t)``), which
makes the jump bit-exact against the dense reference stepper: executing
the skipped ticks would have been the identity.

Load-balancing schemes are *not* wired into the tick (DESIGN.md §11):
path choice and feedback handling dispatch through one ``lax.switch``
over the branches of ``repro.net.policies.registry`` — the engine carries
a stacked per-family policy state dict and never names a scheme.

The run loop is a device-side ``lax.while_loop`` with a donated carry (no
per-chunk host sync, exact early stop), and ``run_batch`` vmaps the whole
driver over (scheme, seed) lanes so a sweep compiles once (DESIGN.md §5).
"""
from __future__ import annotations

import dataclasses
import hashlib
import warnings
from functools import partial
from typing import NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.kernels import ops as KOPS
from repro.kernels.red_ecn import mark_scale
from repro.net.policies import base as PB
from repro.net.policies import registry as REG
from repro.net.sim.types import (FB_ACK_ECN, FB_ACK_OK, FB_NACK, FB_NONE,
                                 FB_TIMEOUT, P_ACKWAIT, P_FREE, P_LOST,
                                 P_NACKWAIT, P_PROP, P_QUEUED, SimResult,
                                 SimSpec, enqueue_bound)

INF_TICK = jnp.int32(1 << 30)
_NEVER_SVC = -(1 << 30)   # last_svc sentinel: first service always legal

# Device scopes (``jax.named_scope``) of the tick phases and the horizon:
# the innermost one lands in every compiled instruction's ``op_name``, so a
# profiler trace maps device time to phases whatever XLA names its fusions.
# The policy scopes sit inside A_feedback and D_injection.  The benchmark
# (``bench/program_trace.py``) reads these names.
SCOPES = ("spritz.A0_failures", "spritz.A_feedback", "spritz.policy_feedback",
          "spritz.B_service", "spritz.C_propagation", "spritz.D_injection",
          "spritz.policy_send", "spritz.E_enqueue", "spritz.horizon")
# Host spans (``jax.profiler.TraceAnnotation``) of ``run_batch``, on the
# profiler's clock with the device planes: the whole call (``lanes``,
# ``pad_lanes``, ``shards``), lane assembly, the runner lookup and call
# (``runner_built`` = 1 where the call traced and compiled the runner),
# and the result copies (``copied_bytes`` of the per-lane arrays returned,
# ``transfers``: device arrays fetched, whatever the lane count, and
# ``steps``: the longest lane's horizon steps, which every lane's loop
# ran for).
SPANS = ("spritz.run_batch", "spritz.assemble_lanes", "spritz.dispatch",
         "spritz.results")


def _event_ivls(spec: SimSpec) -> np.ndarray:
    """Per-event service intervals (ticks/packet, 0 = down).  Pre-rate
    callers build specs with an empty ``fail_event_ivl`` — derive the
    binary encoding (up -> 1, down -> 0) from ``fail_event_up``."""
    if len(spec.fail_event_ivl) == len(spec.fail_event_tick):
        return np.asarray(spec.fail_event_ivl, np.int32)
    return np.where(spec.fail_event_up, 1, 0).astype(np.int32)


def _ceildiv(a, b):
    return (a + b - 1) // b

# one-hot intermediates ([M, n_ports] rank histogram, [N, n_flows] flow-sum
# GEMM operand) are used while they stay under this many cells; beyond it
# (paper-scale fabrics) the rank falls back to an argsort over the
# M-compacted enqueue set and the per-flow sums to segment scatter-adds.
_ONEHOT_CELLS = 1 << 22


class Carry(NamedTuple):
    rng: jax.Array             # base PRNG key (constant; per-tick via fold_in)
    q_tail: jax.Array          # [n_ports] i32
    # failure timeline (DESIGN.md §10): live link state + next-event cursor
    port_up: jax.Array         # [n_ports] bool
    port_ivl: jax.Array        # [n_ports] i32 — live service interval
    #   (ticks/packet; a down port keeps its pre-outage interval)
    last_svc: jax.Array        # [n_ports] i32 — last service tick (rate audit)
    fail_idx: jax.Array        # [] i32 — first unapplied timeline event
    viol: jax.Array            # [] i32 — services across a down port (== 0)
    rviol: jax.Array           # [] i32 — services above scheduled rate (== 0)
    # packet table
    pstate: jax.Array          # [N] i32
    pflow: jax.Array           # [N] i32
    ppath: jax.Array           # [N] i32
    phop: jax.Array            # [N] i32
    pevent: jax.Array          # [N] i32
    pecn: jax.Array            # [N] bool
    pexp: jax.Array            # [N] bool (exploration/sampled packet)
    psent: jax.Array           # [N] i32
    ppsn: jax.Array            # [N] i32
    # hop cache (DESIGN.md §14): the packet's current hop resolved once,
    # when it enters a queue — written in D (hop 0, ``pport`` only) and in
    # E (every enqueue).  For every P_QUEUED or P_PROP packet,
    #   pport  == path_ports[pflow, ppath, phop]
    #   pnport == path_ports[pflow, ppath, phop + 1], or n_ports when
    #             phop == path_len[pflow, ppath] - 1 (the delivery hop)
    #   pdelay == ret_ticks[pflow, ppath] at the delivery hop, else
    #             port_lat[pport]
    pport: jax.Array           # [N] i32 port of the current hop
    pnport: jax.Array          # [N] i32 port of the next hop (n_ports: none)
    pdelay: jax.Array          # [N] i32 ticks from service to next event
    # flow state
    next_seq: jax.Array        # [F] i32
    acked: jax.Array
    retx_pend: jax.Array
    inflight: jax.Array
    inj_cnt: jax.Array
    exp_psn: jax.Array
    cwnd: jax.Array            # [F] f32
    alpha: jax.Array
    exp_alpha: jax.Array       # [F] f32 ECN rate over exploration packets
    round_acks: jax.Array
    round_marks: jax.Array
    round_nacks: jax.Array
    round_size: jax.Array
    # stacked sender-policy state: {family: substate} (DESIGN.md §11)
    policy: dict
    # stats
    fct: jax.Array
    delivered: jax.Array
    trims: jax.Array
    timeouts: jax.Array
    ooo: jax.Array
    retx: jax.Array


class Lane(NamedTuple):
    """Per-lane dynamic parameters for the batched driver (DESIGN.md §5)."""

    scheme: jax.Array          # [] i32
    weights: jax.Array         # [F, P] f32 sampling weights for this scheme
    static_path: jax.Array     # [F] i32


def _tick_keys(rng: jax.Array, t: jax.Array):
    """Positional per-tick keys: skipping a tick leaves the stream intact."""
    return jax.random.split(jax.random.fold_in(rng, t), 2)


def _tree_select(pred, a, b):
    return jax.tree.map(lambda x, y: jnp.where(pred, x, y), a, b)


def _padded(a: jax.Array, fill) -> jax.Array:
    return jnp.concatenate([a, jnp.full((1,), fill, a.dtype)])


def build_tick(spec: SimSpec, *, batched: bool = False):
    """Returns the jit-able transition ``tick(carry, t, lane) -> carry``.

    With ``batched=False`` the scheme is specialized at trace time from
    ``spec.scheme`` (only that registry branch is traced) and ``lane`` may
    be ``None``; with ``batched=True`` the scheme id, sampling weights and
    static path come from ``lane`` and the policy dispatch is a
    ``lax.switch`` over every registry branch, so one compiled program
    serves every (scheme, seed) lane of ``run_batch``.
    """
    F = spec.n_flows
    N = spec.n_pkt
    NP_ = spec.n_ports

    # static device arrays
    path_ports = jnp.asarray(spec.path_ports, jnp.int32)      # [F,P,H]
    path_len = jnp.asarray(spec.path_len, jnp.int32)          # [F,P]
    path_lat = jnp.asarray(spec.path_lat_ns, jnp.float32)     # [F,P]
    spec_weights = jnp.asarray(spec.weights, jnp.float32)
    valiant_w = jnp.asarray(spec.valiant_w, jnp.float32)
    spec_static = jnp.asarray(spec.static_path, jnp.int32)
    min_path = jnp.asarray(spec.min_path, jnp.int32)
    ret_ticks = jnp.asarray(spec.ret_ticks, jnp.int32)        # [F,P]
    rem_ticks = jnp.asarray(spec.rem_ticks, jnp.int32)        # [F,P,H]
    port_lat = jnp.asarray(spec.port_lat, jnp.int32)          # [ports]
    src_ep = jnp.asarray(spec.src_ep, jnp.int32)
    size_pkts = jnp.asarray(spec.size_pkts, jnp.int32)
    start_tick = jnp.asarray(spec.start_tick, jnp.int32)
    dep = jnp.asarray(spec.dep, jnp.int32)
    bg_mask = jnp.asarray(spec.bg_mask, bool)
    has_dep = bool((spec.dep >= 0).any())
    has_bg = bool(spec.bg_mask.any())

    # failure timeline (DESIGN.md §10); E == 0 (static network) removes the
    # whole event phase from the traced program
    E_EV = len(spec.fail_event_tick)
    fev_tick = jnp.asarray(spec.fail_event_tick, jnp.int32)   # [E]
    fev_port = jnp.asarray(spec.fail_event_port, jnp.int32)   # [E]
    fev_up = jnp.asarray(spec.fail_event_up, bool)            # [E]
    fev_ivl_np = _event_ivls(spec)
    fev_ivl = jnp.asarray(fev_ivl_np, jnp.int32)              # [E]
    # rate machinery is traced only for plans that actually carry degraded
    # intervals — binary up/down plans compile to the identical program
    # (the new carry fields ride along as untouched constants), which is
    # what keeps pre-rate plans bit-identical including steps_executed.
    HAS_RATE = bool((fev_ivl_np > 1).any())

    n_eps = int(spec.src_ep.max()) + 1 if len(spec.src_ep) else 1
    # Per-tick enqueue bound (types.enqueue_bound): all FIFO/RED/trim math
    # runs over [M] compacted arrays, never [N] or [M, n_ports].
    M = enqueue_bound(N, NP_, n_eps)
    use_kernels = bool(getattr(spec, "use_kernels", False))
    use_onehot_rank = M * NP_ <= _ONEHOT_CELLS
    use_gemm_sums = N * F <= _ONEHOT_CELLS

    # sender-policy layer (DESIGN.md §11): registry-ordered branches over
    # a stacked per-family state dict.  The engine holds no scheme logic.
    tables = PB.PolicyTables(path_ports=path_ports, path_len=path_len,
                             path_lat=path_lat, valiant_w=valiant_w,
                             min_path=min_path)
    cfgs = REG.make_cfgs(spec)
    send_brs = REG.send_branches(cfgs, tables)
    fb_brs = REG.feedback_branches(cfgs, tables)
    n_pol = len(send_brs)
    scheme_code = int(spec.scheme)
    if not batched and not 0 <= scheme_code < n_pol:
        raise ValueError(f"unknown scheme {scheme_code}")

    # ------------------------------------------------------- tick phases --
    def apply_failure_events(c: Carry, t):
        """A0 (DESIGN.md §10): apply every timeline event with tick <= t
        past the cursor (the horizon stops at each event tick, so in the
        compressed driver that set is exactly this tick's events; the
        dense stepper sees the same sets tick by tick).  Last event per
        port wins — a scatter-max over event index."""
        if not E_EV:
            return (c.port_up, c.port_ivl, c.last_svc, c.fail_idx,
                    c.q_tail, c.pstate, c.pevent, c.trims)
        eidx = jnp.arange(E_EV, dtype=jnp.int32)
        due = (eidx >= c.fail_idx) & (fev_tick <= t)
        last = jnp.full(NP_ + 1, -1, jnp.int32).at[
            jnp.where(due, fev_port, NP_)].max(
            jnp.where(due, eidx, -1))[:NP_]
        new_up = jnp.where(last >= 0, fev_up[jnp.maximum(last, 0)],
                           c.port_up)
        went_down = c.port_up & ~new_up
        fail_idx = c.fail_idx + jnp.sum(due.astype(jnp.int32))
        # in-flight semantics on a down transition: packets still queued
        # at the dying port are trimmed back (header NACK — the switch
        # drains its dead egress queue), packets already on the wire are
        # black-holed (P_LOST -> sender RTO); the analytic queue empties.
        # ``pport`` is the port a P_QUEUED packet waits at and a P_PROP
        # packet left from (phop advances only on arrival).
        cur0 = c.pport
        hit = went_down[jnp.clip(cur0, 0, NP_ - 1)]
        killq = (c.pstate == P_QUEUED) & hit
        killp = (c.pstate == P_PROP) & hit
        nack_at0 = t + rem_ticks[c.pflow, c.ppath,
                                 jnp.minimum(c.phop,
                                             rem_ticks.shape[2] - 1)]
        pstate0 = jnp.where(killq, P_NACKWAIT,
                            jnp.where(killp, P_LOST, c.pstate))
        pevent0 = jnp.where(killq, nack_at0, c.pevent)
        trims0 = c.trims + jnp.zeros(F + 1, jnp.int32).at[
            jnp.where(killq, c.pflow, F)].add(1)[:F]
        q_tail0 = jnp.where(went_down, jnp.minimum(c.q_tail, t),
                            c.q_tail)
        if not HAS_RATE:
            return (new_up, c.port_ivl, c.last_svc, fail_idx, q_tail0,
                    pstate0, pevent0, trims0)
        # rate application: only up-events (ivl > 0) change the live
        # interval — a down port keeps its pre-outage interval, matching
        # FailurePlan.port_ivl_at.  Where the interval changes on a live
        # port, the analytic backlog rescales so the k-th queued packet's
        # slot moves from t + k*old to t + k*new (exact integer math; the
        # backlog is slot-uniform by induction).  last_svc is reset to
        # t - new_ivl so a service at the event tick itself is legal.
        applied = last >= 0
        ivl_ev = fev_ivl[jnp.maximum(last, 0)]
        new_ivl = jnp.where(applied & (ivl_ev > 0), ivl_ev, c.port_ivl)
        resc = applied & new_up & (new_ivl != c.port_ivl)
        backlog = jnp.maximum(q_tail0 - t, 0)
        q_tail0 = jnp.where(
            resc, t + _ceildiv(backlog * new_ivl, c.port_ivl), q_tail0)
        cur_s = jnp.clip(cur0, 0, NP_ - 1)
        presc = (pstate0 == P_QUEUED) & resc[cur_s]
        rel = jnp.maximum(pevent0 - t, 0)
        pevent0 = jnp.where(
            presc,
            t + _ceildiv(rel * new_ivl[cur_s], c.port_ivl[cur_s]),
            pevent0)
        last_svc = jnp.where(applied, t - new_ivl, c.last_svc)
        return (new_up, new_ivl, last_svc, fail_idx, q_tail0, pstate0,
                pevent0, trims0)

    def flow_sums_fn(pflow):
        """Per-flow sums as ONE one-hot GEMM instead of per-mask scatters
        (XLA CPU scatter walks updates serially; the [K,N]x[N,F] product
        vectorizes).  The GEMM runs at ``Precision.HIGHEST``: a TPU's
        default precision multiplies in bf16, exact for integers only up
        to 256, and a delivered PSN exceeds that; at full f32 precision
        values and sums < 2^24 are exact on every backend.  Beyond the
        one-hot cell budget (paper-scale F x N) fall back to one
        multi-column segment scatter-add; with ``use_kernels`` the
        Pallas flow_agg kernel streams the same GEMM in [K, block] tiles
        without materializing [N, F] — exact either way."""
        if use_kernels:
            def flow_sums(rows):                             # [K, N] -> [K, F]
                return KOPS.flow_agg(rows.astype(jnp.int32), pflow,
                                     n_flows=F)
        elif use_gemm_sums:
            flow_oh = (pflow[:, None]
                       == jnp.arange(F, dtype=jnp.int32)[None, :]
                       ).astype(jnp.float32)                 # [N, F]

            def flow_sums(rows):                             # [K, N] -> [K, F]
                return jnp.dot(rows.astype(jnp.float32), flow_oh,
                               precision=jax.lax.Precision.HIGHEST
                               ).astype(jnp.int32)
        else:
            def flow_sums(rows):
                # one scatter pass over all K columns (integer adds are
                # order-independent: bit-identical to the GEMM path)
                return jnp.zeros((F, rows.shape[0]), jnp.int32).at[
                    pflow].add(rows.T.astype(jnp.int32)).T
        return flow_sums

    def collect_feedback(c: Carry, pstate0, pevent0, t, flow_sums):
        """A: feedback arrivals + timeouts -> per-flow counts and the
        representative event per flow (priority TO > NACK > ECN > OK;
        min packet index within the winning class) via ONE composite
        scatter-min: key = (3 - class) * N + index, and the class codes
        are ordered so that class == FB code."""
        ack_m = (pstate0 == P_ACKWAIT) & (pevent0 == t)
        nack_m = (pstate0 == P_NACKWAIT) & (pevent0 == t)
        inflight_states = ((pstate0 == P_QUEUED) | (pstate0 == P_PROP)
                           | (pstate0 == P_LOST))
        to_m = inflight_states & (t - c.psent > spec.rto_ticks)

        ecn_ack = ack_m & c.pecn
        sums = flow_sums(jnp.stack([
            ack_m, ecn_ack, nack_m, to_m,
            (ack_m | nack_m) & c.pexp,
            (ecn_ack | nack_m) & c.pexp,
        ]))                                                  # [6, F]

        fb_m = ack_m | nack_m | to_m
        fb_cat = jnp.where(to_m, FB_TIMEOUT,
                           jnp.where(nack_m, FB_NACK,
                                     jnp.where(ecn_ack, FB_ACK_ECN,
                                               FB_ACK_OK)))
        ckey = (FB_TIMEOUT - fb_cat) * N + jnp.arange(N, dtype=jnp.int32)
        BIGK = jnp.int32((FB_TIMEOUT + 1) * N)
        kmin = jnp.full(F + 1, BIGK, jnp.int32).at[
            jnp.where(fb_m, c.pflow, F)].min(
            jnp.where(fb_m, ckey, BIGK))[:F]
        has_fb = kmin < BIGK
        rep_idx = jnp.where(has_fb, kmin % N, N)
        ppath_x = _padded(c.ppath, 0)  # idx N pad
        fb_type = jnp.where(has_fb, FB_TIMEOUT - kmin // N, FB_NONE)
        fb_ev = jnp.where(has_fb, ppath_x[jnp.minimum(rep_idx, N)], 0)
        return ack_m, nack_m, to_m, sums, fb_ev, fb_type

    def cc_round(c: Carry, n_ack, n_mark, n_nack, n_to):
        """CC (DCTCP + SMaRTT-style QuickAdapt/FastIncrease).  ECN marks
        drive the DCTCP alpha cut; QuickAdapt fires only on heavy
        *trimming* (real loss), resetting cwnd to the delivered bytes of
        the last window — SMaRTT semantics.  Conflating marks with trims
        nukes cwnd on any briefly-marked round, which penalizes
        path-pinned senders (Scout) far beyond the paper's CC."""
        cwnd, alpha = c.cwnd, c.alpha
        r_acks = c.round_acks + n_ack + n_nack
        r_marks = c.round_marks + n_mark + n_nack
        r_nacks = c.round_nacks + n_nack
        round_thr = jnp.maximum(1, jnp.minimum(c.round_size,
                                               cwnd.astype(jnp.int32)))
        round_done = r_acks >= round_thr
        frac = r_marks / jnp.maximum(r_acks, 1)
        frac_trim = r_nacks / jnp.maximum(r_acks, 1)
        alpha_new = (1 - spec.dctcp_g) * alpha + spec.dctcp_g * frac
        alpha = jnp.where(round_done, alpha_new, alpha)
        cw_cut = jnp.maximum(1.0, cwnd * (1 - alpha / 2))
        cw_qa = jnp.maximum(1.0, (r_acks - r_nacks).astype(jnp.float32))
        cw_fi = jnp.minimum(spec.cwnd_max, cwnd * 1.25)
        cw_round = jnp.where(
            (frac_trim > 0.5) & spec.quick_adapt, jnp.minimum(cw_qa, cw_cut),
            jnp.where(r_marks > 0, cw_cut,
                      jnp.where(spec.fast_increase, cw_fi, cwnd)))
        cwnd = jnp.where(round_done, cw_round, cwnd)
        r_size = jnp.where(round_done,
                           jnp.maximum(cwnd.astype(jnp.int32), 1),
                           c.round_size)
        r_acks = jnp.where(round_done, 0, r_acks)
        r_marks = jnp.where(round_done, 0, r_marks)
        r_nacks = jnp.where(round_done, 0, r_nacks)
        # additive increase per clean ACK; hard reset only on timeout
        cwnd = jnp.minimum(spec.cwnd_max,
                           cwnd + n_ack / jnp.maximum(cwnd, 1.0))
        cwnd = jnp.where(n_to > 0, 1.0, cwnd)
        return cwnd, alpha, r_acks, r_marks, r_nacks, r_size

    def tick(c: Carry, t, lane: Lane | None = None):
        k_path, k_mark = _tick_keys(c.rng, t)
        t = t.astype(jnp.int32)

        # ------------- A0. failure timeline events (DESIGN.md §10) ----------
        with jax.named_scope("spritz.A0_failures"):
            (port_up, port_ivl, last_svc, fail_idx, q_tail0, pstate0,
             pevent0, trims0) = apply_failure_events(c, t)

        # load signal fed to the sender-policy layer: ticks-to-drain, so a
        # degraded port (interval > 1) advertises proportionally higher
        # load for the same packet backlog — adaptive schemes steer away
        # from brownouts through the same occ/ECN path as congestion.
        occ = jnp.maximum(q_tail0 - t, 0)
        if batched:
            scheme = lane.scheme
            weights = lane.weights
            static_path = lane.static_path
        else:
            weights = spec_weights
            static_path = spec_static

        # ---------------- A. feedback arrivals + timeouts -------------------
        with jax.named_scope("spritz.A_feedback"):
            flow_sums = flow_sums_fn(c.pflow)
            ack_m, nack_m, to_m, sums, fb_ev, fb_type = collect_feedback(
                c, pstate0, pevent0, t, flow_sums)
            n_ack, n_mark, n_nack, n_to, n_exp, n_exp_bad = sums
            g2 = spec.dctcp_g
            exp_alpha = jnp.where(
                n_exp > 0,
                (1 - g2) * c.exp_alpha + g2 * n_exp_bad / jnp.maximum(n_exp, 1),
                c.exp_alpha)

            cwnd, alpha, r_acks, r_marks, r_nacks, r_size = cc_round(
                c, n_ack, n_mark, n_nack, n_to)

            # --- sender-policy feedback (one switch over registry branches) ---
            fb_ctx = PB.FeedbackCtx(t=t, ev=fb_ev, fb_type=fb_type,
                                    ecn_rate=exp_alpha, n_mark=n_mark,
                                    n_nack=n_nack, n_to=n_to)
            with jax.named_scope("spritz.policy_feedback"):
                if batched:
                    policy = jax.lax.switch(jnp.clip(scheme, 0, n_pol - 1),
                                            fb_brs, c.policy, fb_ctx)
                else:
                    policy = fb_brs[scheme_code](c.policy, fb_ctx)

            acked = c.acked + n_ack
            inflight = c.inflight - n_ack - n_nack - n_to
            retx_pend = c.retx_pend + n_nack + n_to
            done_now = (acked >= size_pkts) & (c.fct < 0)
            fct = jnp.where(done_now, t - start_tick, c.fct)

            # free finished packet slots
            pstate = jnp.where(ack_m | nack_m | to_m, P_FREE, pstate0)

        # ---------------- B. service (dequeue) ------------------------------
        with jax.named_scope("spritz.B_service"):
            # the hop cache holds the port, the delivery flag and the
            # delay of every queued packet: no path-table lookup over [N]
            svc = (pstate == P_QUEUED) & (pevent0 == t)
            cur_port = c.pport
            at_delivery = c.pnport == NP_
            deliver = svc & at_delivery
            forward = svc & ~at_delivery

            # OOO accounting at delivery (<=1 delivery per flow per tick);
            # sum == value since one packet delivers, via the same flow sums
            dsums = flow_sums(jnp.stack([
                jnp.where(deliver, c.ppsn, 0),
                deliver.astype(jnp.int32),
            ]))
            dpsn, has_del = dsums[0], dsums[1] > 0
            is_ooo = has_del & (dpsn != c.exp_psn)
            ooo = c.ooo + is_ooo.astype(jnp.int32)
            exp_psn = jnp.where(has_del, jnp.maximum(c.exp_psn, dpsn + 1),
                                c.exp_psn)

            # conformance counter: a service event must never cross a down port
            # (the A0 kill rule + enqueue mask conspire to make this impossible)
            cur_s = jnp.clip(cur_port, 0, NP_ - 1)
            viol = c.viol + jnp.sum((svc & ~port_up[cur_s]).astype(jnp.int32))
            rviol = c.rviol
            if HAS_RATE:
                # rate audit: services on one port must be >= its scheduled
                # interval apart — throughput never exceeds the scheduled rate
                rviol = rviol + jnp.sum(
                    (svc & (t - last_svc[cur_s] < port_ivl[cur_s])
                     ).astype(jnp.int32))
                last_svc = jnp.concatenate(
                    [last_svc, jnp.full((1,), _NEVER_SVC, jnp.int32)]).at[
                    jnp.where(svc, cur_port, NP_)].max(t)[:NP_]

            pevent = jnp.where(svc, t + c.pdelay, pevent0)
            pstate = jnp.where(deliver, P_ACKWAIT, pstate)
            pstate = jnp.where(forward, P_PROP, pstate)

        # ---------------- C. propagation arrivals ---------------------------
        with jax.named_scope("spritz.C_propagation"):
            arrive = (pstate == P_PROP) & (pevent == t)
            phop = jnp.where(arrive, c.phop + 1, c.phop)

        # ---------------- D. injection --------------------------------------
        with jax.named_scope("spritz.D_injection"):
            work_left = (c.next_seq < size_pkts) | (retx_pend > 0)
            eligible = (t >= start_tick) & (acked < size_pkts) & work_left & \
                       (inflight < jnp.floor(cwnd).astype(jnp.int32)) & (c.fct < 0)
            if has_dep:
                fct_x = _padded(fct, 0)
                dep_done = (dep < 0) | (fct_x[jnp.maximum(dep, -1)] >= 0)
                # dep == -1 gathers fct_x[-1] == trash; masked by dep < 0 above
                eligible = eligible & dep_done
            # endpoint arbitration: one flow per source endpoint per tick
            prio = ((t * jnp.int32(40503) + jnp.arange(F, dtype=jnp.int32) * 9973)
                    & 0xffff) + 1
            prio = jnp.where(eligible, prio, 0)
            key = prio * F + (F - 1 - jnp.arange(F, dtype=jnp.int32))  # unique
            ep_best = jnp.zeros(n_eps, jnp.int32).at[src_ep].max(key)
            win = eligible & (key == ep_best[src_ep])

            # free-slot allocation: k-th winner takes the k-th free slot, found
            # by searchsorted over the free-count prefix (no N-sized scatter)
            free_m = pstate == P_FREE
            n_free = jnp.cumsum(free_m.astype(jnp.int32))
            win_rank = jnp.cumsum(win.astype(jnp.int32)) - 1
            have_slot = win & (win_rank < n_free[-1])
            flow_slot = jnp.searchsorted(
                n_free, jnp.maximum(win_rank, 0) + 1, side="left"
            ).astype(jnp.int32)  # [F]; == N when out of slots (masked by tgt)

            # --- path choice: one switch over the registry's choose_path
            # branches.  Every policy's sampler consumes k_path through the
            # identical uniform draw (policies.base.weighted_sample_rows), so
            # the batched select and the specialized solo branch produce
            # bit-identical choices per scheme (DESIGN.md §5/§11).
            send_ctx = PB.SendCtx(rng=k_path, t=t, active=have_slot, occ=occ,
                                  weights=weights, static_path=static_path)
            with jax.named_scope("spritz.policy_send"):
                if batched:
                    path_sel, explored, policy = jax.lax.switch(
                        jnp.clip(scheme, 0, n_pol - 1), send_brs, policy, send_ctx)
                else:
                    path_sel, explored, policy = send_brs[scheme_code](policy,
                                                                       send_ctx)
                if has_bg:  # background jobs stay on static ECMP paths (paper §V-B)
                    path_sel = jnp.where(bg_mask, static_path, path_sel)

            # write new packets (scatter via trash row N)
            tgt = jnp.where(have_slot, flow_slot, N)

            def scatter_new(arr, val):
                big = jnp.concatenate([arr, jnp.zeros((1,), arr.dtype)])
                big = big.at[tgt].set(val.astype(arr.dtype))
                return big[:N]

            pflow = scatter_new(c.pflow, jnp.arange(F, dtype=jnp.int32))
            ppath = scatter_new(c.ppath, path_sel)
            phop = scatter_new(phop, jnp.zeros(F, jnp.int32))
            psent = scatter_new(c.psent, jnp.full(F, t, jnp.int32))
            ppsn = scatter_new(c.ppsn, c.inj_cnt)
            pecn = scatter_new(c.pecn, jnp.zeros(F, bool))
            pexp = scatter_new(c.pexp, explored)
            pstate = scatter_new(pstate, jnp.full(F, P_PROP, jnp.int32))  # placeholder
            pevent = scatter_new(pevent, jnp.full(F, t, jnp.int32))
            pport = scatter_new(c.pport, path_ports[
                jnp.arange(F, dtype=jnp.int32), path_sel, 0])
            # injected packets "arrive" at hop-0 port this tick:
            injected_pkt = jnp.zeros(N + 1, bool).at[tgt].set(True)[:N]

            is_retx = have_slot & (retx_pend > 0)
            retx_pend = retx_pend - is_retx.astype(jnp.int32)
            next_seq = c.next_seq + (have_slot & ~is_retx).astype(jnp.int32)
            inj_cnt = c.inj_cnt + have_slot.astype(jnp.int32)
            inflight = inflight + have_slot.astype(jnp.int32)
            retx_stat = c.retx + is_retx.astype(jnp.int32)

        # ---------------- E. enqueue (arrivals + injections) ----------------
        with jax.named_scope("spritz.E_enqueue"):
            enq0 = arrive | injected_pkt
            # the port each enqueue joins: an arrival's cached next hop, an
            # injection's hop-0 port (written in D)
            eport_n = jnp.where(arrive, c.pnport,
                                jnp.where(injected_pkt, pport, NP_))
            pport = jnp.where(enq0, eport_n, pport)
            failed = enq0 & (eport_n < NP_) & \
                ~port_up[jnp.minimum(eport_n, NP_ - 1)]
            enq = enq0 & ~failed
            pstate = jnp.where(failed, P_LOST, pstate)

            # compact the <= M enqueues of this tick (M = n_ports + n_eps + 8:
            # each port services <= 1 pkt/tick with a constant per-port latency,
            # so forwarded arrivals are <= n_ports; endpoint arbitration admits
            # <= 1 injection per endpoint) — all FIFO/RED/trim math and the
            # hop resolution of the enqueued packets run in [M] instead of
            # [N].
            n_enq = jnp.cumsum(enq.astype(jnp.int32))
            cidx = jnp.searchsorted(
                n_enq, jnp.arange(M, dtype=jnp.int32) + 1, side="left"
            ).astype(jnp.int32)  # [M]; == N past the last enqueue
            valid = cidx < N
            cidx_s = jnp.minimum(cidx, N)
            cflow = _padded(pflow, F)[cidx_s]
            cpath = _padded(ppath, 0)[cidx_s]
            chop = _padded(phop, 0)[cidx_s]
            cport = _padded(eport_n, NP_)[cidx_s]

            # FIFO rank among same-tick arrivals per port (compacted)
            rank = _enqueue_rank(cport)

            tail_e = q_tail0[jnp.minimum(cport, NP_ - 1)]
            if HAS_RATE:
                # backlog in *packets* (buffer occupancy): ticks-to-drain
                # divided by the port's service interval.  Trim/RED compare
                # against packet thresholds (qsize/kmin/kmax), so a degraded
                # port holds the same number of packets but drains slower.
                # (kernel dispatch bypasses to jnp under HAS_RATE: red_ecn
                # models the full-rate slot math only — DESIGN.md §14)
                ivl_e = port_ivl[jnp.minimum(cport, NP_ - 1)]
                occ_at = _ceildiv(jnp.maximum(tail_e - t, 0), ivl_e) + rank
            elif use_kernels:
                ivl_e = None
                occ_at, trim, mark, slot = KOPS.red_ecn(
                    cport, rank, valid, jax.random.uniform(k_mark, (M,)),
                    q_tail0, t, qsize=spec.qsize, kmin=spec.kmin,
                    kmax=spec.kmax, n_ports=NP_)
            else:
                ivl_e = None
                occ_at = jnp.maximum(tail_e - t, 0) + rank
            if HAS_RATE or not use_kernels:
                trim = valid & (occ_at >= spec.qsize)
                # RED / ECN marking probability between kmin..kmax
                pr = jnp.clip((occ_at.astype(jnp.float32) - spec.kmin)
                              * mark_scale(spec.kmin, spec.kmax), 0.0, 1.0)
                mark = (valid & ~trim) & (jax.random.uniform(k_mark, (M,)) < pr)
                if HAS_RATE:
                    # service slots stride by the interval: rank-k accept
                    # departs at max(tail, t) + (k+1)*ivl — rate 1/ivl by
                    # construction
                    slot = jnp.maximum(tail_e, t) + (rank + 1) * ivl_e
                else:
                    slot = jnp.maximum(tail_e, t) + rank + 1
            accept = valid & ~trim
            pecn = pecn | jnp.zeros(N + 1, bool).at[
                jnp.where(mark, cidx_s, N)].set(True)[:N]
            # trimmed: header continues + NACK returns (priority, prop-only)
            cflow_s = jnp.minimum(cflow, F - 1)
            nack_at = t + rem_ticks[cflow_s, cpath,
                                    jnp.minimum(chop, rem_ticks.shape[2] - 1)]
            new_state = jnp.where(trim, P_NACKWAIT, P_QUEUED)
            new_event = jnp.where(trim, nack_at, slot)
            # resolve the next hop of every enqueued packet (hop cache)
            c_last = chop == path_len[cflow_s, cpath] - 1
            new_nport = jnp.where(
                c_last, NP_,
                path_ports[cflow_s, cpath,
                           jnp.minimum(chop + 1, path_ports.shape[2] - 1)])
            new_delay = jnp.where(c_last, ret_ticks[cflow_s, cpath],
                                  port_lat[jnp.minimum(cport, NP_ - 1)])
            ctgt = jnp.where(valid, cidx_s, N)

            def scatter_enq(arr, val):
                return _padded(arr, 0).at[ctgt].set(val)[:N]

            pstate = scatter_enq(pstate, new_state)
            pevent = scatter_enq(pevent, new_event)
            pnport = scatter_enq(c.pnport, new_nport)
            pdelay = scatter_enq(c.pdelay, new_delay)

            trims = trims0 + jnp.zeros(F + 1, jnp.int32).at[
                jnp.where(trim, cflow, F)].add(1)[:F]
            timeouts = c.timeouts + n_to
            delivered = c.delivered + n_ack

            # q_tail advances by ticks-of-service added: ivl per accepted
            # packet (1 at full rate — the pre-rate scalar bump)
            n_acc = jnp.zeros(NP_ + 1, jnp.int32).at[
                jnp.where(accept, cport, NP_)].add(
                1 if not HAS_RATE else ivl_e)[:NP_]
            q_tail = jnp.where(n_acc > 0, jnp.maximum(q_tail0, t) + n_acc,
                               q_tail0)

        return Carry(
            rng=c.rng, q_tail=q_tail,
            port_up=port_up, port_ivl=port_ivl, last_svc=last_svc,
            fail_idx=fail_idx, viol=viol, rviol=rviol,
            pstate=pstate, pflow=pflow, ppath=ppath, phop=phop, pevent=pevent,
            pecn=pecn, pexp=pexp, psent=psent, ppsn=ppsn,
            pport=pport, pnport=pnport, pdelay=pdelay,
            next_seq=next_seq, acked=acked, retx_pend=retx_pend,
            inflight=inflight, inj_cnt=inj_cnt, exp_psn=exp_psn,
            cwnd=cwnd, alpha=alpha, exp_alpha=exp_alpha,
            round_acks=r_acks, round_marks=r_marks, round_nacks=r_nacks,
            round_size=r_size, policy=policy,
            fct=fct, delivered=delivered, trims=trims, timeouts=timeouts,
            ooo=ooo, retx=retx_stat,
        )

    def _enqueue_rank(cport):
        """FIFO rank among same-tick enqueues per port, in compacted space.

        Small fabrics: segmented scatter-add rank — a prefix histogram of
        one-hot port indicators (cumsum of scatter contributions) read back
        at each packet's own port.  Large fabrics: stable argsort over the
        M-compacted set (still ~N/M cheaper than the old table-wide sort).
        With ``use_kernels`` the Pallas tick_rank kernel counts earlier
        same-port entries pairwise over [block, block] tiles.  All
        paths produce the identical rank for valid entries: position among
        this tick's enqueues of the same port, ordered by packet-table
        index (invalid/sentinel entries are masked by callers).
        """
        if use_kernels:
            return KOPS.tick_rank(cport, n_ports=NP_)
        if use_onehot_rank:
            oh = cport[:, None] == jnp.arange(NP_, dtype=jnp.int32)[None, :]
            pos = jnp.cumsum(oh.astype(jnp.int32), axis=0) * oh
            return jnp.maximum(pos.sum(-1) - 1, 0)
        order = jnp.argsort(cport)
        sorted_port = cport[order]
        pos = jnp.arange(M, dtype=jnp.int32)
        is_start = jnp.concatenate([jnp.ones(1, bool),
                                    sorted_port[1:] != sorted_port[:-1]])
        seg_start = jax.lax.associative_scan(jnp.maximum,
                                             jnp.where(is_start, pos, 0))
        rank_sorted = pos - seg_start
        return jnp.zeros(M, jnp.int32).at[order].set(rank_sorted)

    return tick


def build_horizon(spec: SimSpec):
    """Returns ``horizon(carry, t) -> next event tick > t`` (DESIGN.md §4).

    The horizon is the min over every tick at which the dense stepper could
    change state: scheduled packet events, RTO deadlines, injection
    eligibility (gated on a free table slot), pending flow starts, and
    deferred CC round closure.  Every tick strictly inside (t, horizon) is
    a provable no-op of the transition, so jumping is bit-exact.
    """
    size_pkts = jnp.asarray(spec.size_pkts, jnp.int32)
    start_tick = jnp.asarray(spec.start_tick, jnp.int32)
    dep = jnp.asarray(spec.dep, jnp.int32)
    has_dep = bool((spec.dep >= 0).any())
    rto1 = jnp.int32(spec.rto_ticks + 1)
    # failure timeline (DESIGN.md §10): the next unapplied event tick is a
    # provable event — compression must never jump over a failure/recovery.
    E_EV = len(spec.fail_event_tick)
    fev_tick_x = jnp.concatenate([
        jnp.asarray(spec.fail_event_tick, jnp.int32),
        jnp.full((1,), INF_TICK, jnp.int32)])

    def horizon(c: Carry, t):
        live = ((c.pstate == P_QUEUED) | (c.pstate == P_PROP)
                | (c.pstate == P_ACKWAIT) | (c.pstate == P_NACKWAIT))
        ev_pkt = jnp.min(jnp.where(live, c.pevent, INF_TICK))
        to_states = ((c.pstate == P_QUEUED) | (c.pstate == P_PROP)
                     | (c.pstate == P_LOST))
        ev_rto = jnp.min(jnp.where(to_states, c.psent + rto1, INF_TICK))
        # injection: an eligible flow with a free table slot injects at
        # every tick, so the next injection tick is max(start, t+1)
        work_left = (c.next_seq < size_pkts) | (c.retx_pend > 0)
        elig = (c.acked < size_pkts) & work_left & (c.fct < 0) & \
               (c.inflight < jnp.floor(c.cwnd).astype(jnp.int32))
        if has_dep:
            fct_x = _padded(c.fct, 0)
            dep_done = (dep < 0) | (fct_x[jnp.maximum(dep, -1)] >= 0)
            elig = elig & dep_done
        any_free = jnp.any(c.pstate == P_FREE)
        ev_inj = jnp.where(
            any_free,
            jnp.min(jnp.where(elig, jnp.maximum(start_tick, t + 1),
                              INF_TICK)),
            INF_TICK)
        # deferred CC round closure: a cwnd collapse can pull round_thr at
        # or below already-banked round_acks, making the *next* tick fire
        # the round with no new feedback
        round_thr = jnp.maximum(1, jnp.minimum(c.round_size,
                                               c.cwnd.astype(jnp.int32)))
        pend_round = jnp.any((c.round_acks >= round_thr) & (c.fct < 0))
        ev_cc = jnp.where(pend_round, t + 1, INF_TICK)
        h = jnp.minimum(jnp.minimum(ev_pkt, ev_rto),
                        jnp.minimum(ev_inj, ev_cc))
        if E_EV:
            h = jnp.minimum(h, fev_tick_x[jnp.minimum(c.fail_idx, E_EV)])
        return jnp.maximum(t + 1, h)

    return horizon


def init_carry(spec: SimSpec, seed: int = 0,
               weights: np.ndarray | None = None,
               static_path: np.ndarray | None = None) -> Carry:
    F, N = spec.n_flows, spec.n_pkt
    w = spec.weights if weights is None else weights
    sp = spec.static_path if static_path is None else static_path
    # timeline events at tick <= 0 are initial conditions (DESIGN.md §10):
    # folding them here makes a t=0 plan bit-identical — including
    # steps_executed — to a static ``failed_links`` build.
    port_up0 = ~np.asarray(spec.port_failed, bool)
    port_ivl0 = np.ones(spec.n_ports, np.int32)
    ivl0 = _event_ivls(spec)
    n0 = int(np.searchsorted(spec.fail_event_tick, 0, side="right"))
    if n0:
        port_up0 = port_up0.copy()
        for i in range(n0):
            port_up0[spec.fail_event_port[i]] = bool(spec.fail_event_up[i])
            if ivl0[i] > 0:
                port_ivl0[spec.fail_event_port[i]] = int(ivl0[i])
    carry = Carry(
        rng=jax.random.PRNGKey(seed),
        q_tail=jnp.zeros(spec.n_ports, jnp.int32),
        port_up=jnp.asarray(port_up0),
        port_ivl=jnp.asarray(port_ivl0),
        last_svc=jnp.full(spec.n_ports, _NEVER_SVC, jnp.int32),
        fail_idx=jnp.int32(n0), viol=jnp.int32(0), rviol=jnp.int32(0),
        pstate=jnp.zeros(N, jnp.int32), pflow=jnp.zeros(N, jnp.int32),
        ppath=jnp.zeros(N, jnp.int32), phop=jnp.zeros(N, jnp.int32),
        pevent=jnp.zeros(N, jnp.int32), pecn=jnp.zeros(N, bool),
        pexp=jnp.zeros(N, bool),
        psent=jnp.zeros(N, jnp.int32), ppsn=jnp.zeros(N, jnp.int32),
        pport=jnp.zeros(N, jnp.int32), pnport=jnp.zeros(N, jnp.int32),
        pdelay=jnp.zeros(N, jnp.int32),
        next_seq=jnp.zeros(F, jnp.int32), acked=jnp.zeros(F, jnp.int32),
        retx_pend=jnp.zeros(F, jnp.int32), inflight=jnp.zeros(F, jnp.int32),
        inj_cnt=jnp.zeros(F, jnp.int32), exp_psn=jnp.zeros(F, jnp.int32),
        cwnd=jnp.full(F, spec.cwnd_init, jnp.float32),
        alpha=jnp.zeros(F, jnp.float32),
        exp_alpha=jnp.zeros(F, jnp.float32),
        round_acks=jnp.zeros(F, jnp.int32), round_marks=jnp.zeros(F, jnp.int32),
        round_nacks=jnp.zeros(F, jnp.int32),
        round_size=jnp.full(F, max(int(spec.cwnd_init), 1), jnp.int32),
        policy=REG.init_state(np.asarray(w, np.float32),
                              np.asarray(sp, np.int32)),
        fct=jnp.full(F, -1, jnp.int32), delivered=jnp.zeros(F, jnp.int32),
        trims=jnp.zeros(F, jnp.int32), timeouts=jnp.zeros(F, jnp.int32),
        ooo=jnp.zeros(F, jnp.int32), retx=jnp.zeros(F, jnp.int32),
    )
    # the runner donates the carry; aliased leaves (e.g. SpritzState.w and
    # w_orig come from the same no-op astype) would be donated twice
    return jax.tree.map(jnp.copy, carry)


def _make_loop(spec: SimSpec, *, dense: bool, batched: bool):
    """Device-side driver: while_loop until budget exhausted or all watched
    flows complete.  ``dense=True`` steps every tick (reference stepper);
    otherwise the next tick is the event horizon.

    ``t0``/``steps0`` seed the loop counters (-1/0 for a fresh run; a
    checkpoint's values on resume) and ``limit`` is the segment bound:
    the loop stops at the first state whose tick has reached ``limit``.
    Because a segment stops *between* body iterations, its final
    ``(carry, t, steps)`` is exactly an intermediate state of the
    unsegmented run — resume is bit-identical by construction (the
    alternative, rebuilding the spec with a smaller ``n_ticks``, would
    clamp a horizon event landing exactly on the boundary out of the
    segment and lose it on resume).  All three are traced scalars, so
    segment boundaries never retrace the driver.
    """
    tick = build_tick(spec, batched=batched)
    hor = None if dense else build_horizon(spec)
    n_ticks = jnp.int32(spec.n_ticks)

    def loop(carry: Carry, watch, t0, steps0, limit,
             lane: Lane | None = None):
        def cond(s):
            c, t, steps = s
            done = jnp.all(jnp.where(watch, c.fct >= 0, True))
            return (t < n_ticks) & (t < limit) & ~done

        def body(s):
            c, t, steps = s
            if dense:
                h = t + 1
            else:
                with jax.named_scope("spritz.horizon"):
                    h = hor(c, t)
            h = jnp.minimum(h, n_ticks)
            ex = h < n_ticks
            c2 = tick(c, jnp.minimum(h, n_ticks - 1), lane)
            c = _tree_select(ex, c2, c)
            return (c, jnp.where(ex, h, n_ticks), steps + ex.astype(jnp.int32))

        return jax.lax.while_loop(cond, body, (carry, t0, steps0))

    return loop


_RUNNER_CACHE: dict = {}
_RUNNER_CACHE_MAX = 32


def _spec_key(spec: SimSpec) -> tuple:
    """Content fingerprint of a spec: identical specs share one compiled
    driver (jax.jit caches per wrapper object, so a fresh jit per run()
    call would otherwise retrace every time)."""
    h = hashlib.blake2b(digest_size=16)
    scalars = []
    for f in dataclasses.fields(spec):
        v = getattr(spec, f.name)
        if isinstance(v, np.ndarray):
            h.update(f.name.encode())
            h.update(str(v.shape).encode() + str(v.dtype).encode())
            h.update(np.ascontiguousarray(v).tobytes())
        elif f.name != "name":
            scalars.append((f.name, v))
    return (tuple(scalars), h.hexdigest())


def _runner(spec: SimSpec, *, dense: bool, batched: bool, shard: int = 0):
    # _ONEHOT_CELLS keys the cache too: tests monkeypatch the threshold to
    # force the fallback paths, which changes the traced program without
    # changing the spec fingerprint
    key = (_spec_key(spec), dense, batched, shard, _ONEHOT_CELLS)
    runner = _RUNNER_CACHE.get(key)
    if runner is None:
        loop = _make_loop(spec, dense=dense, batched=batched)
        if batched:
            # per-lane loop counters (t0/steps0) so a batched resume can
            # restart every lane from its own stopped tick; the segment
            # limit is shared
            vloop = jax.vmap(lambda c, w, t0, s0, lim, ln:
                             loop(c, w, t0, s0, lim, ln),
                             in_axes=(0, None, 0, 0, None, 0))
            if shard > 1:
                # split the lane axis across devices (DESIGN.md §5): each
                # device runs the identical vmapped driver over its lane
                # slice, so per-lane results are bit-identical to the
                # unsharded (and solo) runs — lanes never communicate.
                from jax.sharding import Mesh
                from jax.sharding import PartitionSpec as PS
                mesh = Mesh(np.asarray(jax.devices()[:shard]), ("lanes",))
                vloop = jax.shard_map(
                    vloop, mesh=mesh,
                    in_specs=(PS("lanes"), PS(), PS("lanes"), PS("lanes"),
                              PS(), PS("lanes")),
                    out_specs=(PS("lanes"), PS("lanes"), PS("lanes")),
                    check_vma=False)
            runner = jax.jit(vloop, donate_argnums=(0,))
        else:
            runner = jax.jit(lambda c, w, t0, s0, lim:
                             loop(c, w, t0, s0, lim), donate_argnums=(0,))
        if len(_RUNNER_CACHE) >= _RUNNER_CACHE_MAX:
            _RUNNER_CACHE.pop(next(iter(_RUNNER_CACHE)))
        _RUNNER_CACHE[key] = runner
    return runner


def _watch_mask(spec: SimSpec, stop_flows) -> np.ndarray:
    if stop_flows is None:
        return np.ones(spec.n_flows, bool)
    m = np.zeros(spec.n_flows, bool)
    m[np.asarray(stop_flows)] = True
    return m


class _ResultLeaves(NamedTuple):
    """The carry leaves a :class:`SimResult` is read from."""

    fct: np.ndarray
    delivered: np.ndarray
    trims: np.ndarray
    timeouts: np.ndarray
    ooo: np.ndarray
    retx: np.ndarray
    viol: np.ndarray
    rviol: np.ndarray


def _fetch_results(carry: Carry, t, steps):
    """``(leaves, t, steps)`` on the host, in one ``jax.device_get`` of
    the ten arrays a result reads (lane-stacked in a batched carry).
    ``device_get`` starts every transfer before it waits on any, and
    copies a lane-sharded array shard by shard from its own chip: no
    op runs on the device, so nothing compiles here."""
    leaves = _ResultLeaves(*(getattr(carry, f) for f in _ResultLeaves._fields))
    return jax.device_get((leaves, t, steps))


def _result(leaves: _ResultLeaves, t, steps) -> SimResult:
    """One lane's result from its host arrays (see ``_fetch_results``)."""
    return SimResult(
        fct_ticks=leaves.fct,
        delivered=leaves.delivered,
        trims=leaves.trims,
        timeouts=leaves.timeouts,
        ooo=leaves.ooo,
        retx=leaves.retx,
        done=leaves.fct >= 0,
        ticks_simulated=int(t),
        steps_executed=int(steps),
        down_violations=int(leaves.viol),
        rate_violations=int(leaves.rviol),
    )


def live_carry_bytes(carry: Carry) -> int:
    """Bytes of live donated carry state (pytree leaf sum) — the number
    ``bench_engine`` reports as the engine's resident footprint.  The
    carry is occupancy-bounded (packet table + per-flow/per-port vectors,
    DESIGN.md §14): no leaf scales with n_ports x n_flows."""
    return int(sum(x.size * x.dtype.itemsize
                   for x in jax.tree.leaves(carry)))


def _carry_state(carry: Carry) -> dict:
    """Final carry, fetched to the host, as nested NumPy dicts — the
    observability hook the conservation/conformance property suites
    audit.  The stacked policy dict lands under ``"policy"``;
    ``"spritz"`` stays a top-level alias for pre-refactor callers."""
    state: dict = {}
    for k, v in carry._asdict().items():
        if k == "policy":
            state["policy"] = {
                fam: {f: np.asarray(x) for f, x in sub._asdict().items()}
                for fam, sub in v.items()}
        else:
            state[k] = np.asarray(v)
    state["spritz"] = state["policy"]["spritz"]
    return state


class Checkpoint(NamedTuple):
    """A resumable engine snapshot: the nested-NumPy carry state (the
    ``_carry_state`` form ``return_carry=True`` emits) plus the loop
    counters.  ``run(spec, resume=cp)`` continues the while_loop from
    exactly this state; segmenting a long-horizon run over
    ``until_tick`` boundaries is bit-identical to the unsegmented run
    (pinned by tests/test_arrivals.py)."""

    state: dict   # nested numpy carry (incl. the stacked policy dict)
    t: int        # ticks simulated so far (the loop's current tick)
    steps: int    # horizon steps executed so far


def checkpoint(res: SimResult, state: dict) -> Checkpoint:
    """Pair a ``return_carry=True`` result with its carry state."""
    return Checkpoint(state=state, t=int(res.ticks_simulated),
                      steps=int(res.steps_executed))


def _carry_from_state(spec: SimSpec, state: dict) -> Carry:
    """Rebuild a device carry from a checkpoint's nested-NumPy state:
    an ``init_carry`` template supplies structure and dtypes, the
    stored arrays supply values (fresh buffers — safe to donate)."""
    tmpl = init_carry(spec, 0)

    def leaf(arr, ref):
        a = np.asarray(arr)
        if a.shape != ref.shape:
            raise ValueError(
                f"checkpoint leaf shape {a.shape} != spec's {ref.shape} "
                "— resume requires the identical SimSpec")
        return jnp.asarray(a, ref.dtype)

    vals = {}
    for k in Carry._fields:
        ref = getattr(tmpl, k)
        if k == "policy":
            vals[k] = {
                fam: type(sub)(**{f: leaf(state["policy"][fam][f],
                                          getattr(sub, f))
                                  for f in sub._fields})
                for fam, sub in ref.items()}
        else:
            vals[k] = leaf(state[k], ref)
    return Carry(**vals)


def run(spec: SimSpec, seed: int = 0, chunk: int | None = None,
        stop_flows: np.ndarray | None = None,
        reference: bool = False, return_carry: bool = False,
        until_tick: int | None = None,
        resume: Checkpoint | None = None):
    """Run the simulation for up to ``spec.n_ticks`` virtual ticks.

    The driver is a single donated device-side while_loop that stops as
    soon as every flow — or every flow in ``stop_flows`` — completed.
    ``reference=True`` selects the dense tick-by-tick stepper (the
    bit-exact oracle for the event-compressed default).  ``chunk`` is
    accepted for backwards compatibility and ignored: there is no chunked
    host loop any more.  ``return_carry=True`` additionally returns the
    final :class:`Carry` as nested NumPy dicts (``tests/test_failures.py``
    audits conservation/conformance through it).

    ``until_tick`` stops the segment once the loop's tick reaches it
    (a traced bound — no recompile per boundary); ``resume`` continues
    from a :class:`Checkpoint` built over the *same* spec.  Pair them
    to segment a long-horizon open-loop run::

        res, st = run(spec, seed, until_tick=W, return_carry=True)
        res, st = run(spec, resume=checkpoint(res, st),
                      until_tick=2 * W, return_carry=True)

    which is bit-identical to one unsegmented call (DESIGN.md §15).
    """
    del chunk
    watch = jnp.asarray(_watch_mask(spec, stop_flows))
    runner = _runner(spec, dense=reference, batched=False)
    if resume is not None:
        carry0 = _carry_from_state(spec, resume.state)
        t0, steps0 = int(resume.t), int(resume.steps)
    else:
        carry0, t0, steps0 = init_carry(spec, seed), -1, 0
    limit = spec.n_ticks if until_tick is None else int(until_tick)
    with warnings.catch_warnings():
        # donation is a no-op on CPU; the advisory warning is noise there
        warnings.filterwarnings(
            "ignore", message="Some donated buffers were not usable")
        carry, t, steps = runner(carry0, watch, jnp.int32(t0),
                                 jnp.int32(steps0), jnp.int32(limit))
    res = _result(*_fetch_results(carry, t, steps))
    if return_carry:
        return res, _carry_state(jax.device_get(carry))
    return res


run_reference = partial(run, reference=True)


def lane_arrays(spec: SimSpec, scheme) -> tuple[np.ndarray, np.ndarray]:
    """Derive a scheme lane's (weights, static_path) from a base spec —
    a thin delegate to the registry's host lane rules (DESIGN.md §5/§11):

    * ``uniform_weights`` schemes (SPRAY_U/OPS_U/REPS) sample uniformly
      over each flow's live paths;
    * ``pin_minimal`` schemes (MINIMAL) pin foreground flows to the
      minimal route;
    * everything else reuses the base spec's Eq.-1 weights / ECMP draw.

    The base spec must therefore be built with a *weighted* scheme
    (anything except the uniform/minimal ones) so its weights and static
    paths carry the generic values.
    """
    return REG.lane_arrays(spec, scheme)


def run_batch(spec: SimSpec | Sequence[SimSpec],
              schemes: Sequence[int | str] | None = None,
              seeds: Sequence[int] = (0,),
              stop_flows: np.ndarray | None = None,
              reference: bool = False,
              return_carry: bool = False,
              shard: bool | None = None,
              until_tick: int | None = None,
              resume: Sequence[Checkpoint] | None = None):
    """Batched driver: one compiled program for a scheme x seed sweep.

    Either pass one base ``spec`` plus ``schemes`` (registry names or
    integer codes; lane weights/static paths derived via
    :func:`lane_arrays`), or a sequence of per-scheme specs that share
    every static field except scheme/weights/static_path.  Lanes are
    vmapped over the whole while_loop driver — scheme-major, seed-minor
    order — and results come back as a flat list of ``SimResult`` of
    length ``len(schemes) * len(seeds)``.  ``return_carry=True`` returns
    ``(results, states)`` with one nested-NumPy carry dict per lane.

    ``shard`` splits the lane axis across the process's devices with
    ``shard_map`` (DESIGN.md §5): ``None`` auto-enables when more than
    one device is visible, ``False`` forces the single-device vmap.  The
    lane count is padded to a device multiple by replicating lane 0 (pad
    results are dropped); per-lane results are bit-identical either way
    because lanes never communicate.

    ``until_tick`` bounds the segment for every lane (lanes stop at
    their own first tick past the bound — horizon jumps differ per
    lane); ``resume`` takes one :class:`Checkpoint` per lane, in the
    same scheme-major, seed-minor order, from a previous segmented call
    with the identical spec/schemes/seeds.  Segmenting is bit-identical
    to one unsegmented call, exactly as in :func:`run`.
    """
    with TraceAnnotation("spritz.run_batch") as call:
        with TraceAnnotation("spritz.assemble_lanes"):
            base, n_lanes, n_shard, args = _batch_inputs(
                spec, schemes, seeds, stop_flows=stop_flows, shard=shard,
                until_tick=until_tick, resume=resume)
        call.set_metadata(lanes=n_lanes,
                          pad_lanes=len(args[-1].scheme) - n_lanes,
                          shards=n_shard)
        with TraceAnnotation("spritz.dispatch") as span:
            runner = _runner(base, dense=reference, batched=True,
                             shard=n_shard)
            n_traced = runner._cache_size()
            with warnings.catch_warnings():
                # donation is a no-op on CPU; the advisory warning is noise
                warnings.filterwarnings(
                    "ignore", message="Some donated buffers were not usable")
                carry, t, steps = runner(*args)
            span.set_metadata(
                runner_built=int(runner._cache_size() > n_traced))
        with TraceAnnotation("spritz.results") as span:
            leaves, t, steps = _fetch_results(carry, t, steps)
            transfers = len(leaves) + 2
            if return_carry:
                carry = jax.device_get(carry)
                transfers += len(jax.tree.leaves(carry))
            out, states, copied = [], [], 0
            for i in range(n_lanes):  # pad lanes (lane-0 replicas) dropped
                # a copy per lane: no lane's arrays alias another's
                lane = _ResultLeaves(*(np.array(x[i]) for x in leaves))
                out.append(_result(lane, t[i], steps[i]))
                copied += sum(v.nbytes for v in out[-1]
                              if isinstance(v, np.ndarray))
                if return_carry:
                    lane_carry = jax.tree.map(lambda x: np.array(x[i]), carry)
                    states.append(_carry_state(lane_carry))
                    copied += live_carry_bytes(lane_carry)
            span.set_metadata(copied_bytes=copied, transfers=transfers,
                              steps=max(r.steps_executed for r in out))
    if return_carry:
        return out, states
    return out


def _batch_inputs(spec, schemes, seeds, *, stop_flows=None, shard=None,
                  until_tick=None, resume=None):
    """``run_batch``'s lane assembly: returns ``(base spec, n_lanes,
    n_shard, args)`` where ``args`` is the batched runner's argument
    tuple, its lane axis padded to a multiple of ``n_shard`` devices."""
    if isinstance(spec, SimSpec):
        if schemes is None:
            schemes = [spec.scheme]
        codes = [REG.as_code(s) for s in schemes]
        base = spec
        lane_specs = []
        for s in codes:
            if s == base.scheme:
                lane_specs.append((s, np.asarray(base.weights, np.float32),
                                   np.asarray(base.static_path, np.int32)))
            else:
                w, sp = lane_arrays(base, s)
                lane_specs.append((s, w, sp))
    else:
        specs = list(spec)
        if schemes is not None:
            raise ValueError("pass schemes only with a single base spec")
        base = specs[0]
        for s in specs[1:]:
            if (s.n_pkt, s.n_ports, s.n_flows, s.n_ticks) != \
               (base.n_pkt, base.n_ports, base.n_flows, base.n_ticks):
                raise ValueError("lane specs must share static shapes")
        lane_specs = [(s.scheme, np.asarray(s.weights, np.float32),
                       np.asarray(s.static_path, np.int32)) for s in specs]

    lanes_flat = [(s, w, p, seed)
                  for (s, w, p) in lane_specs for seed in seeds]
    n_lanes = len(lanes_flat)
    if resume is not None and len(resume) != n_lanes:
        raise ValueError(f"resume needs one Checkpoint per lane: got "
                         f"{len(resume)} for {n_lanes} lanes")
    cps = list(resume) if resume is not None else None
    ndev = jax.device_count()
    if shard is None:
        shard = ndev > 1 and n_lanes > 1
    n_shard = ndev if shard else 0
    if n_shard > 1 and n_lanes % n_shard:
        pad = -n_lanes % n_shard
        lanes_flat = lanes_flat + lanes_flat[:1] * pad
        if cps is not None:
            cps = cps + cps[:1] * pad
    lanes = Lane(
        scheme=jnp.asarray([s for s, _, _, _ in lanes_flat], jnp.int32),
        weights=jnp.asarray(np.stack([w for _, w, _, _ in lanes_flat])),
        static_path=jnp.asarray(np.stack([p for _, _, p, _ in lanes_flat])),
    )
    if cps is not None:
        carries = [_carry_from_state(base, cp.state) for cp in cps]
        t0 = np.asarray([cp.t for cp in cps], np.int32)
        steps0 = np.asarray([cp.steps for cp in cps], np.int32)
    else:
        carries = [init_carry(base, seed, weights=w, static_path=p)
                   for (_, w, p, seed) in lanes_flat]
        t0 = np.full(len(lanes_flat), -1, np.int32)
        steps0 = np.zeros(len(lanes_flat), np.int32)
    carry0 = jax.tree.map(lambda *xs: jnp.stack(xs), *carries)
    watch = jnp.asarray(_watch_mask(base, stop_flows))
    limit = base.n_ticks if until_tick is None else int(until_tick)
    return base, n_lanes, n_shard, (carry0, watch, jnp.asarray(t0),
                                    jnp.asarray(steps0), jnp.int32(limit),
                                    lanes)


def batch_lanes(schemes: Sequence[int | str], seeds: Sequence[int]
                ) -> list[tuple[int | str, int]]:
    """The (scheme, seed) order ``run_batch`` returns results in."""
    return [(s, seed) for s in schemes for seed in seeds]
