"""The benchmark's plain reference simulator: one tick after another, in
straightforward ``jax.numpy``, for the schemes the benchmark's traffic
uses.  It imports nothing of the program and is given nothing the
program made: it builds its own fabric and path tables
(``reference.fabric``) from the configuration and the flows.

Semantics of one tick ``t`` (the network is static):

A. feedback: ACKs and NACKs due at ``t`` and packets in flight longer
   than the RTO come back; per flow their counts drive DCTCP-style
   congestion control (float32 window arithmetic) and the sender policy
   sees one representative event (timeout > NACK > ECN-ACK > ACK, lowest
   packet slot first).
B. service: each packet whose departure slot is ``t`` leaves its port;
   at the last port it is delivered (out-of-order counted by PSN) and
   its ACK returns after the path's propagation time, otherwise it
   propagates to the next switch.
C. arrival: propagated packets reach their next hop.
D. injection: eligible flows contend per source endpoint (a hashed
   priority picks one), winners take free packet slots in flow order and
   choose a path by their scheme.
E. enqueue: arrivals and injections join their port's FIFO in slot
   order; a full queue trims the packet (a NACK returns), RED marks ECN
   between ``kmin`` and ``kmax`` with one uniform draw per enqueue
   position.

Randomness is positional: tick ``t`` of a lane seeded ``s`` uses
``split(fold_in(PRNGKey(s), t), 2)`` as (path key, mark key); a path
sample draws ``uniform(path key, (F, 1))`` against a sequential prefix
sum of the weights.

``dtype`` selects the precision of every float step: the congestion
window arithmetic, the path sampler's prefix sums and draw, UGAL's
load products and RED's mark probability.  float32 is what the
configuration states; the benchmark's control runs this reference with
bfloat16 in its place.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from reference.fabric import H_MAX, Tables

FREE, QUEUED, PROP, ACKWAIT, NACKWAIT, LOST = range(6)
ACK_OK, ACK_ECN, NACK, TIMEOUT, NO_FB = range(5)
SCHEMES = ("ecmp", "ugal_l", "spritz_spray_w")
FIELDS = ("fct_ticks", "delivered", "trims", "timeouts", "ooo", "retx",
          "done", "ticks_simulated", "down_violations", "rate_violations")


@dataclasses.dataclass(frozen=True)
class Sizes:
    """Queue, window and table sizes derived from the configuration."""

    bdp: int
    n_ticks: int
    n_pkt: int
    rto: int
    n_enq: int               # enqueue positions drawn per tick
    n_eps: int

    @property
    def qsize(self):
        return self.bdp

    @property
    def kmin(self):
        return 0.2 * self.bdp

    @property
    def kmax(self):
        return 0.8 * self.bdp

    @property
    def cwnd_max(self):
        return 1.5 * self.bdp

    @property
    def explore_threshold(self):
        return max(4, self.bdp // 2)


def sizes(tables: Tables, flows: dict, *, bdp: int, n_ticks: int,
          n_ports: int, pkt_slots: int) -> Sizes:
    """``pkt_slots`` caps the packet table: the flows' first windows and
    64 slots more, or the cap where that is fewer."""
    cwnd_max = 1.5 * bdp
    n_pkt = int(min(pkt_slots, sum(min(int(s), int(cwnd_max) + 4)
                                   for s in flows["size"]) + 64))
    rto = int(2.5 * (bdp * int(tables.length.max()) + int(tables.ret.max())))
    n_eps = int(np.max(flows["src"])) + 1
    return Sizes(bdp=bdp, n_ticks=n_ticks, n_pkt=n_pkt, rto=rto,
                 n_enq=int(min(n_pkt, n_ports + n_eps + 8)), n_eps=n_eps)


def _prefix(w):
    cols = [w[:, 0]]
    for j in range(1, w.shape[1]):
        cols.append(cols[-1] + w[:, j])
    return jnp.stack(cols, axis=1)


def _sample(key, w, dtype):
    """Index of the first path whose running weight reaches u * total."""
    c = _prefix(w.astype(dtype))
    u = jax.random.uniform(key, (w.shape[0], 1)).astype(dtype) * \
        jnp.maximum(c[:, -1:], 1e-30)
    return jnp.minimum(jnp.sum((c < u).astype(jnp.int32), 1), w.shape[1] - 1)


def _per_flow(mask, flow, F, value=None):
    v = mask.astype(jnp.int32) if value is None else jnp.where(mask, value, 0)
    return jnp.zeros(F + 1, jnp.int32).at[jnp.where(mask, flow, F)].add(v)[:F]


def make_lane_runner(tables: Tables, flows: dict, sz: Sizes, n_ports: int,
                     scheme: str, dtype=jnp.float32):
    """A jitted ``run(seeds[L]) -> dict of [L, ...] results`` for lanes of
    one scheme."""
    if scheme not in SCHEMES:
        raise ValueError(f"the reference simulates {SCHEMES}, not {scheme!r}")
    F, N, P = len(flows["size"]), sz.n_pkt, tables.ports.shape[1]
    ports = jnp.asarray(tables.ports)
    plen = jnp.asarray(tables.length)
    ret_t = jnp.asarray(tables.ret)
    rem_t = jnp.asarray(tables.rem)
    plat = jnp.asarray(tables.port_lat)
    eq1 = jnp.asarray(tables.eq1)
    valiant = jnp.asarray(tables.valiant)
    ecmp = jnp.asarray(tables.ecmp)
    minimal = jnp.asarray(tables.minimal)
    size = jnp.asarray(flows["size"], jnp.int32)
    start = jnp.asarray(flows["start"], jnp.int32)
    src = jnp.asarray(flows["src"], jnp.int32)
    fidx = jnp.arange(F, dtype=jnp.int32)
    g = 1.0 / 16.0
    cwnd_max, qsize, kmin = sz.cwnd_max, sz.qsize, sz.kmin
    red_scale = 1.0 / max(sz.kmax - sz.kmin, 1e-9)
    one = jnp.asarray(1.0, dtype)

    def init(seed):
        z = lambda n, dt=jnp.int32: jnp.zeros(n, dt)  # noqa: E731
        return dict(
            key=jax.random.PRNGKey(seed), t=jnp.int32(-1),
            q_tail=z(n_ports),
            pstate=z(N), pflow=z(N), ppath=z(N), phop=z(N), pevent=z(N),
            pecn=z(N, bool), pexp=z(N, bool), psent=z(N), ppsn=z(N),
            next_seq=z(F), acked=z(F), retx_pend=z(F), inflight=z(F),
            inj_cnt=z(F), exp_psn=z(F),
            cwnd=jnp.full(F, cwnd_max, dtype), alpha=z(F, dtype),
            exp_alpha=z(F, dtype),
            r_acks=z(F), r_marks=z(F), r_nacks=z(F),
            r_size=jnp.full(F, max(int(cwnd_max), 1), jnp.int32),
            w=eq1, buffer=jnp.full((F, 8), -1, jnp.int32), pkt_count=z(F),
            blocked=jnp.zeros((F, P), jnp.int32),
            fct=jnp.full(F, -1, jnp.int32), delivered=z(F), trims=z(F),
            timeouts=z(F), ooo=z(F), retx=z(F))

    def congestion(s, n_ack, n_mark, n_nack, n_to):
        cwnd, alpha = s["cwnd"], s["alpha"]
        acks = s["r_acks"] + n_ack + n_nack
        marks = s["r_marks"] + n_mark + n_nack
        nacks = s["r_nacks"] + n_nack
        done = acks >= jnp.maximum(1, jnp.minimum(s["r_size"],
                                                  cwnd.astype(jnp.int32)))
        frac = (marks / jnp.maximum(acks, 1)).astype(dtype)
        frac_trim = nacks / jnp.maximum(acks, 1)
        alpha = jnp.where(done, (1 - g) * alpha + g * frac, alpha)
        cut = jnp.maximum(one, cwnd * (1 - alpha / 2))
        quick = jnp.maximum(one, (acks - nacks).astype(dtype))
        grow = jnp.minimum(cwnd_max, cwnd * 1.25)
        new = jnp.where(frac_trim > 0.5, jnp.minimum(quick, cut),
                        jnp.where(marks > 0, cut, grow))
        cwnd = jnp.where(done, new, cwnd)
        r_size = jnp.where(done, jnp.maximum(cwnd.astype(jnp.int32), 1),
                           s["r_size"])
        zero = lambda x: jnp.where(done, 0, x)  # noqa: E731
        cwnd = jnp.minimum(cwnd_max, cwnd + n_ack / jnp.maximum(cwnd, one))
        cwnd = jnp.where(n_to > 0, one, cwnd).astype(dtype)
        return dict(cwnd=cwnd, alpha=alpha.astype(dtype), r_acks=zero(acks),
                    r_marks=zero(marks), r_nacks=zero(nacks), r_size=r_size)

    def choose(s, key, t, active, occ):
        """(path, explored, policy updates) for every flow."""
        if scheme == "ecmp":
            return ecmp, jnp.ones(F, bool), {}
        if scheme == "ugal_l":
            cand = _sample(key, valiant, dtype)
            q_min = occ[ports[fidx, minimal, 0]].astype(dtype)
            q_val = occ[ports[fidx, cand, 0]].astype(dtype)
            h_min = plen[fidx, minimal].astype(dtype)
            h_val = plen[fidx, cand].astype(dtype)
            path = jnp.where(q_min * h_min <= q_val * h_val, minimal, cand)
            return path, jnp.ones(F, bool), {}
        # Spritz-Spray, Eq.-1 weights: reuse the good-path buffer's front
        # (consuming it) until every explore_threshold-th packet samples
        w_eff = jnp.where(t < s["blocked"], 0.0,
                          jnp.where(s["w"] == 0.0, eq1, s["w"]))
        explore = s["pkt_count"] >= sz.explore_threshold
        front = s["buffer"][:, 0]
        have = front >= 0
        front_blocked = have & (s["blocked"][fidx, jnp.maximum(front, 0)] > t)
        use_buf = ~explore & have & ~front_blocked
        path = jnp.where(use_buf, front, _sample(key, w_eff, dtype))
        pop = ~explore & have & active
        shifted = jnp.concatenate([s["buffer"][:, 1:],
                                   jnp.full((F, 1), -1, jnp.int32)], 1)
        count = jnp.where(explore, 0, s["pkt_count"] + 1)
        return path, ~use_buf, dict(
            buffer=jnp.where(pop[:, None], shifted, s["buffer"]),
            pkt_count=jnp.where(active, count, s["pkt_count"]))

    def feedback(s, ev, fb, ecn_rate, t):
        if scheme != "spritz_spray_w":
            return {}
        ev = jnp.clip(ev, 0, P - 1)
        hit = jnp.arange(P)[None, :] == ev[:, None]
        buf = s["buffer"]
        fill = jnp.sum((buf >= 0).astype(jnp.int32), 1)
        push = (fb == ACK_OK) & (fill < 8)
        buf = jnp.where(push[:, None] & (jnp.arange(8)[None, :] == fill[:, None]),
                        ev[:, None], buf)
        to = (fb == TIMEOUT)[:, None] & hit
        blocked = jnp.where(to, t + (1 << 18), s["blocked"])
        w = jnp.where(to, 0.0, s["w"])
        bias = (ecn_rate > 0.9) & (fb != NO_FB)
        w = w.at[:, 0].set(jnp.where(bias, 8.0, w[:, 0]))
        return dict(buffer=buf, blocked=blocked, w=w)

    def tick(s, t):
        k_path, k_mark = jax.random.split(jax.random.fold_in(s["key"], t), 2)
        s = dict(s)
        occ = jnp.maximum(s["q_tail"] - t, 0)
        pstate, pflow, ppath, phop = s["pstate"], s["pflow"], s["ppath"], s["phop"]

        # A. feedback
        ack = (pstate == ACKWAIT) & (s["pevent"] == t)
        nack = (pstate == NACKWAIT) & (s["pevent"] == t)
        flying = (pstate == QUEUED) | (pstate == PROP) | (pstate == LOST)
        to = flying & (t - s["psent"] > sz.rto)
        ecn = ack & s["pecn"]
        n_ack, n_mark = _per_flow(ack, pflow, F), _per_flow(ecn, pflow, F)
        n_nack, n_to = _per_flow(nack, pflow, F), _per_flow(to, pflow, F)
        n_exp = _per_flow((ack | nack) & s["pexp"], pflow, F)
        n_exp_bad = _per_flow((ecn | nack) & s["pexp"], pflow, F)
        kind = jnp.where(to, TIMEOUT, jnp.where(nack, NACK,
                                                jnp.where(ecn, ACK_ECN, ACK_OK)))
        any_fb = ack | nack | to
        rank_key = (TIMEOUT - kind) * N + jnp.arange(N, dtype=jnp.int32)
        best = jnp.full(F + 1, 4 * N, jnp.int32).at[
            jnp.where(any_fb, pflow, F)].min(jnp.where(any_fb, rank_key, 4 * N))[:F]
        got = best < 4 * N
        fb = jnp.where(got, TIMEOUT - best // N, NO_FB)
        ev = jnp.where(got, ppath[jnp.minimum(best % N, N - 1)], 0)
        exp_alpha = jnp.where(
            n_exp > 0,
            (1 - g) * s["exp_alpha"] + (g * n_exp_bad / jnp.maximum(n_exp, 1)
                                        ).astype(dtype),
            s["exp_alpha"]).astype(dtype)
        s.update(congestion(s, n_ack, n_mark, n_nack, n_to))
        s["exp_alpha"] = exp_alpha
        s.update(feedback(s, ev, fb, exp_alpha, t))
        acked = s["acked"] + n_ack
        inflight = s["inflight"] - n_ack - n_nack - n_to
        retx_pend = s["retx_pend"] + n_nack + n_to
        old_fct = s["fct"]
        fct = jnp.where((acked >= size) & (old_fct < 0), t - start, old_fct)
        pstate = jnp.where(any_fb, FREE, pstate)

        # B. service
        served = (pstate == QUEUED) & (s["pevent"] == t)
        port = ports[pflow, ppath, phop]
        last = phop == plen[pflow, ppath] - 1
        deliver, forward = served & last, served & ~last
        n_del = _per_flow(deliver, pflow, F)
        psn = _per_flow(deliver, pflow, F, s["ppsn"])
        has = n_del > 0
        ooo = s["ooo"] + (has & (psn != s["exp_psn"])).astype(jnp.int32)
        exp_psn = jnp.where(has, jnp.maximum(s["exp_psn"], psn + 1), s["exp_psn"])
        pevent = jnp.where(deliver, t + ret_t[pflow, ppath], s["pevent"])
        pstate = jnp.where(deliver, ACKWAIT, pstate)
        pevent = jnp.where(forward, t + plat[port], pevent)
        pstate = jnp.where(forward, PROP, pstate)

        # C. arrival at the next hop
        arrive = (pstate == PROP) & (pevent == t)
        phop = phop + arrive.astype(jnp.int32)

        # D. injection
        eligible = ((t >= start) & (acked < size)
                    & ((s["next_seq"] < size) | (retx_pend > 0))
                    & (inflight < jnp.floor(s["cwnd"]).astype(jnp.int32))
                    & (old_fct < 0))
        prio = jnp.where(eligible, ((t * 40503 + fidx * 9973) & 0xffff) + 1, 0)
        key = prio * F + (F - 1 - fidx)
        win = eligible & (key == jnp.zeros(sz.n_eps, jnp.int32).at[src].max(key)[src])
        free = pstate == FREE
        n_free = jnp.cumsum(free.astype(jnp.int32))
        order = jnp.cumsum(win.astype(jnp.int32)) - 1
        sends = win & (order < n_free[-1])
        slot = jnp.searchsorted(n_free, jnp.maximum(order, 0) + 1).astype(jnp.int32)
        path, explored, upd = choose(s, k_path, t, sends, occ)
        s.update(upd)
        tgt = jnp.where(sends, slot, N)

        def put(arr, val):
            return jnp.concatenate([arr, arr[:1]]).at[tgt].set(
                val.astype(arr.dtype))[:N]

        retry = sends & (retx_pend > 0)
        pflow = put(pflow, fidx)
        ppath = put(ppath, path)
        phop = put(phop, jnp.zeros(F, jnp.int32))
        psent = put(s["psent"], jnp.full(F, t))
        ppsn = put(s["ppsn"], s["inj_cnt"])
        pecn = put(s["pecn"], jnp.zeros(F, bool))
        pexp = put(s["pexp"], explored)
        new_pkt = jnp.zeros(N + 1, bool).at[tgt].set(True)[:N]

        # E. enqueue, FIFO per port in slot order, trim, RED/ECN mark
        enq = arrive | new_pkt
        eport = jnp.where(enq, ports[pflow, ppath, phop], n_ports)
        pos = jnp.cumsum(enq.astype(jnp.int32)) - 1
        order = jnp.argsort(eport, stable=True)
        sp = eport[order]
        seg = jnp.concatenate([jnp.ones(1, bool), sp[1:] != sp[:-1]])
        idx = jnp.arange(N, dtype=jnp.int32)
        first = jax.lax.cummax(jnp.where(seg, idx, 0))
        rank = jnp.zeros(N, jnp.int32).at[order].set(idx - first)
        tail = s["q_tail"][jnp.minimum(eport, n_ports - 1)]
        backlog = jnp.maximum(tail - t, 0) + rank
        trim = enq & (backlog >= qsize)
        p_mark = jnp.clip((backlog.astype(dtype) - kmin) * red_scale, 0.0, 1.0)
        u = jax.random.uniform(k_mark, (sz.n_enq,))[jnp.clip(pos, 0, sz.n_enq - 1)]
        mark = enq & ~trim & (u < p_mark)
        accept = enq & ~trim
        nack_at = t + rem_t[pflow, ppath, jnp.minimum(phop, H_MAX - 1)]
        pstate = jnp.where(enq, jnp.where(trim, NACKWAIT, QUEUED), pstate)
        pevent = jnp.where(new_pkt, t, pevent)
        pevent = jnp.where(enq, jnp.where(trim, nack_at,
                                          jnp.maximum(tail, t) + rank + 1),
                           pevent)
        n_acc = jnp.zeros(n_ports + 1, jnp.int32).at[
            jnp.where(accept, eport, n_ports)].add(1)[:n_ports]
        s.update(
            t=t, q_tail=jnp.where(n_acc > 0, jnp.maximum(s["q_tail"], t) + n_acc,
                                  s["q_tail"]),
            pstate=pstate, pflow=pflow, ppath=ppath, phop=phop, pevent=pevent,
            pecn=pecn | mark, pexp=pexp, psent=psent, ppsn=ppsn,
            next_seq=s["next_seq"] + (sends & ~retry).astype(jnp.int32),
            acked=acked, retx_pend=retx_pend - retry.astype(jnp.int32),
            inflight=inflight + sends.astype(jnp.int32),
            inj_cnt=s["inj_cnt"] + sends.astype(jnp.int32),
            exp_psn=exp_psn, fct=fct, delivered=s["delivered"] + n_ack,
            trims=s["trims"] + _per_flow(trim, pflow, F),
            timeouts=s["timeouts"] + n_to, ooo=ooo,
            retx=s["retx"] + retry.astype(jnp.int32))
        return s

    def lane(seed):
        def more(s):
            return (s["t"] + 1 < sz.n_ticks) & jnp.any(s["fct"] < 0)

        s = jax.lax.while_loop(more, lambda s: tick(s, s["t"] + 1), init(seed))
        t_end = jnp.where(jnp.any(s["fct"] < 0), sz.n_ticks, s["t"])
        return dict(fct_ticks=s["fct"], delivered=s["delivered"],
                    trims=s["trims"], timeouts=s["timeouts"], ooo=s["ooo"],
                    retx=s["retx"], done=s["fct"] >= 0, ticks_simulated=t_end)

    return jax.jit(jax.vmap(lane))


def simulate(tables: Tables, flows: dict, sz: Sizes, n_ports: int, lanes,
             dtype=jnp.float32) -> list[dict]:
    """Results (numpy, one dict per lane, in ``lanes`` order) for
    ``lanes``, a list of (scheme, seed); ``flows`` holds the ``src``,
    ``dst``, ``size`` and ``start`` arrays."""
    out: list = [None] * len(lanes)
    devices, runs = jax.devices(), []
    for scheme in SCHEMES:
        idx = [i for i, (sc, _) in enumerate(lanes) if sc == scheme]
        if not idx:
            continue
        # each scheme on a device of its own where there are several: every
        # run is dispatched before any result is read, so they overlap
        with jax.default_device(devices[len(runs) % len(devices)]):
            run = make_lane_runner(tables, flows, sz, n_ports, scheme, dtype)
            runs.append((idx, run(jnp.asarray([lanes[i][1] for i in idx],
                                              jnp.int32))))
    for idx, res in runs:
        res = jax.device_get(res)
        for j, i in enumerate(idx):
            lane = {k: np.asarray(v[j]) for k, v in res.items()}
            lane["down_violations"] = np.int32(0)
            lane["rate_violations"] = np.int32(0)
            out[i] = lane
    if any(r is None for r in out):
        raise ValueError(f"the reference simulates {SCHEMES} only")
    return out
