"""Compiled fleet-simulator backend: ``lax.scan`` over time bins, ``vmap``
over Monte Carlo seeds, ``vmap`` over candidate configs.

The numpy simulator (``repro.fleet.simulator``) is the reference
implementation; its inner loop is a Python ``for t in range(T)`` with a
data-dependent cohort pour per bin, so a tuning round pays Python dispatch
``n_candidates x n_bins`` times. This module re-expresses the per-bin update
as a pure function of fixed-shape arrays and compiles the whole
(candidate, seed, bin) lattice into one XLA program:

* **time** is a ``lax.scan`` whose carry is the queue/fleet state
  (per-class cumulative admitted+served curves, ready/cold-starting replicas,
  the pending-launch ledger, policy-kernel state);
* **the cohort pour** becomes a binary search: cohort service order is a
  static permutation of (class, arrival-bin) cohorts
  (``discipline.cohort_tables``), so "pour ``amount`` in key order" is
  "find the minimal global-order prefix whose admitted mass covers
  ``amount``" — ~log2(C*T) fixed iterations instead of a while loop;
* **scale-down cancellation** (newest pending launches first) becomes a
  reverse-cumsum water-fill over the pending-launch window;
* **the policy** runs as a functional kernel (``repro.fleet.kernels``), its
  tunable knobs passed as arrays — which is what lets a whole racing round
  (every candidate x every seed) batch into ONE jitted call.

Everything runs in float64 via a scoped ``jax.enable_x64`` so the compiled path
agrees with the numpy reference to float rounding; candidate batches are
padded to power-of-two sizes so racing's shrinking rounds reuse a handful of
compiled programs instead of recompiling per round.
"""
from __future__ import annotations

import time

import numpy as np

from repro.fleet import telemetry

_EPS = 1e-12


def available() -> bool:
    """True when jax is importable (the compiled backend can run)."""
    try:
        import jax  # noqa: F401
        return True
    except Exception:
        return False


# One compiled core per (kernel, static-shape) signature; kernels are cached
# by config (kernels._KERNEL_CACHE), so repeated rounds of one tuning run —
# and repeated simulations of one scenario — all hit the same entry.
_CORE_CACHE: dict = {}

# (core id, padded shape signature) pairs that have dispatched at least once:
# a first dispatch pays XLA compilation (cold), repeats are pure dispatch
# (warm) — the classifier behind the compile-vs-dispatch timing split.
_DISPATCHED: set = set()

# Persistent (on-disk) XLA compilation cache bookkeeping: disk hit/miss
# tallies fed by jax's monitoring events. Where the cache lives is
# ``repro.compile_cache``'s decision; this module only counts.
_PCACHE = {"hits": 0, "misses": 0, "listener": False}

_PCACHE_EVENTS = {"/jax/compilation_cache/cache_hits": "hit",
                  "/jax/compilation_cache/cache_misses": "miss"}


def _pcache_event(event: str, **kw) -> None:
    result = _PCACHE_EVENTS.get(event)
    if result is None:
        return
    _PCACHE["hits" if result == "hit" else "misses"] += 1
    telemetry.counter("jaxsim_compile_cache_disk_total", result=result)


def _attach_cache_listener() -> None:
    """Feed disk hit/miss tallies to ``persistent_cache_stats()`` and the
    ``jaxsim_compile_cache_disk_total`` telemetry counter (a no-op unless a
    telemetry session is active, so the wiring stays bit-exact)."""
    if not _PCACHE["listener"]:
        import jax
        jax.monitoring.register_event_listener(_pcache_event)
        _PCACHE["listener"] = True


def persistent_cache_stats() -> dict:
    """Disk-cache tallies since process start: ``{hits, misses, dir}``
    (``dir`` is None while no persistent cache is in use)."""
    import jax
    return {"hits": int(_PCACHE["hits"]), "misses": int(_PCACHE["misses"]),
            "dir": jax.config.jax_compilation_cache_dir}


def clear_compiled() -> list:
    """Evict every compiled core and jit executable (``jax.clear_caches``),
    so the next dispatch recompiles — through the persistent on-disk cache
    when one is wired, which is how a warm-cache rebuild is measured.
    Returns the evicted core callables: a caller timing a cold rebuild must
    hold these references until it is done, otherwise a newly built core can
    reuse a freed core's ``id()`` and masquerade as already-dispatched in
    the cold/warm classifier."""
    evicted = list(_CORE_CACHE.values())
    _CORE_CACHE.clear()
    _DISPATCHED.clear()
    import jax
    jax.clear_caches()
    return evicted


def _build_core(kernel, *, T, C, P, Tpad, W, dt, order, t_fixed, t_unit,
                max_b, max_queue, n_substeps=1, preemptive=False, tput=()):
    import jax
    import jax.numpy as jnp
    from jax import lax

    CT = C * T
    n_rank_iters = max(int(np.ceil(np.log2(CT + 1))), 1)
    arange_c = jnp.arange(C)

    def serve(Acum, done, amt, cnt, cls_rank):
        """Pour ``amt`` into cohorts in global key order: binary-search the
        minimal prefix rank whose admitted mass covers ``amt``, serve every
        cohort below it fully and the marginal cohort partially. ``Acum`` is
        the (C, T+1) cumulative-admitted curve (leading zero), ``done`` the
        (C,) served totals; returns the (C,) per-class split."""
        def take(r):
            j = cnt[:, r]                       # class-c cohorts in prefix r
            a = jnp.take_along_axis(Acum, j[:, None], axis=1)[:, 0]
            return jnp.clip(a - done, 0.0, None)

        full = take(CT)
        amt = jnp.minimum(jnp.maximum(amt, 0.0), full.sum())

        def bisect(_, lohi):
            lo, hi = lohi
            mid = (lo + hi) // 2
            ge = take(mid).sum() >= amt
            return (jnp.where(ge, lo, mid + 1), jnp.where(ge, mid, hi))

        lo, _ = lax.fori_loop(0, n_rank_iters, bisect,
                              (jnp.int32(0), jnp.int32(CT)))
        rm1 = jnp.maximum(lo - 1, 0)
        base = take(rm1)
        marginal = cls_rank[rm1]
        served = base + jnp.maximum(amt - base.sum(), 0.0) \
            * (arange_c == marginal)
        return jnp.where(lo > 0, served, jnp.zeros(C))

    def sim_one(arr, rate, rate_sum, jb, cnt, cls_rank, drop_rank, key_rank,
                kp, min_rep, max_rep, init_ready):
        """One (candidate, seed) trajectory. arr (T, C) float arrivals;
        rate (T, C) / rate_sum (T,) are the per-class and aggregate arrival
        rates divided by dt on the HOST — XLA rewrites division by a
        constant into an inexact reciprocal multiply, which would shift
        rates by an ulp and flip policy ceil()s vs the numpy reference;
        jb (T, P) int launch-landing offsets; tables/params per candidate.
        ``key_rank`` feeds only the substep core (``sim_one_fine``)."""
        col = jnp.arange(T + 1)

        def step(carry, x):
            ready, in_flight, pend, done, Acum, pstate = carry
            arr_c, rate_c, rate_sum, jb_t, t = x
            matured = pend[t]
            ready = ready + matured
            in_flight = in_flight - matured

            total_prev = Acum[:, T]
            drop = jnp.zeros(C)
            if max_queue is not None:
                over = jnp.maximum((total_prev - done).sum() + arr_c.sum()
                                   - max_queue, 0.0)
                order_t = drop_rank[t]
                for rank in range(C):
                    c = order_t[rank]
                    d = jnp.minimum(arr_c[c], over)
                    drop = drop.at[c].add(d)
                    over = over - d
            adm_c = arr_c - drop
            new_total = total_prev + adm_c
            Acum = jnp.where(col[None, :] >= t + 1, new_total[:, None], Acum)

            remaining = (new_total - done).sum()
            capacity = 0.0
            slot_split, slot_bt, slot_served = [], [], []
            for p in order:                       # static drain order
                n = jnp.maximum(ready[p], 0.0)
                has = n > 0
                b = jnp.clip(jnp.where(
                    has, jnp.ceil(remaining / jnp.where(has, n, 1.0)), 0.0),
                    1.0, max_b[p])
                bt = jnp.maximum(t_fixed[p] + b * t_unit[p], _EPS)
                cap = jnp.where(has, n * b / bt, 0.0) * dt
                split = serve(Acum, done, jnp.minimum(remaining, cap),
                              cnt, cls_rank)
                done = done + split
                s_p = split.sum()
                remaining = remaining - s_p
                capacity = capacity + cap
                slot_split.append(split)
                slot_bt.append(bt)
                slot_served.append(s_p)

            # fold sub-eps float residue of a drained class into "empty" —
            # the numpy pour's _MASS_EPS behaviour; without it a ~1e-11
            # leftover queue can flip a policy ceil() on the next bin
            done = jnp.where(new_total - done <= 1e-9 + 1e-12 * new_total,
                             new_total, done)
            queue_c = jnp.maximum(new_total - done, 0.0)
            served = sum(slot_served)
            util = jnp.where(capacity > 0, served / capacity, 0.0)
            from repro.fleet.kernels import KernelObs
            obs = KernelObs(
                t_s=(t + 1) * dt, dt_s=dt, arrival_rate=rate_sum,
                queue=queue_c.sum(), replicas=ready.sum(),
                in_flight=in_flight.sum(), utilization=util,
                pool_replicas=ready, pool_in_flight=in_flight,
                class_queue=queue_c, class_arrival_rate=rate_c,
                min_replicas=min_rep, max_replicas=max_rep)
            pool_rep = ready                      # pre-decision (serving) fleet
            pstate, target = kernel.step(kp, pstate, obs)
            target = jnp.clip(target, min_rep, max_rep)

            # scale down: cancel pending launches newest-first (reverse
            # water-fill over the cold-start window), then shrink ready
            excess = jnp.maximum(ready + in_flight - target, 0.0)
            zero = jnp.int32(0)
            window = lax.dynamic_slice(pend, (t + 1, zero), (W, P))
            newer = jnp.cumsum(window[::-1, :], axis=0)[::-1, :] - window
            cut = jnp.clip(excess[None, :] - newer, 0.0, window)
            window = window - cut
            canceled = cut.sum(axis=0)
            pend = lax.dynamic_update_slice(pend, window, (t + 1, zero))
            in_flight = in_flight - canceled
            ready = jnp.maximum(ready - (excess - canceled), 0.0)
            grow = jnp.maximum(target - ready - in_flight, 0.0)
            pend = pend.at[t + 1 + jb_t, jnp.arange(P)].add(grow)
            in_flight = in_flight + grow
            billed = pool_rep + in_flight

            ys = {"slot_split": jnp.stack(slot_split),    # (P, C) rank order
                  "slot_bt": jnp.stack(slot_bt),          # (P,)
                  "slot_served": jnp.stack(slot_served),  # (P,)
                  "admitted_c": adm_c, "dropped_c": drop,
                  "queue_c": queue_c, "pool_rep": pool_rep,
                  "billed": billed, "util": util}
            return (ready, in_flight, pend, done, Acum, pstate), ys

        carry0 = (init_ready, jnp.zeros(P), jnp.zeros((Tpad, P)),
                  jnp.zeros(C), jnp.zeros((C, T + 1)), kernel.init())
        xs = (arr, rate, rate_sum, jb, jnp.arange(T, dtype=jnp.int32))
        _, ys = lax.scan(step, carry0, xs)
        return ys

    n_sub = int(n_substeps)
    dt_sub = dt / n_sub                     # host float, matches numpy

    def sim_one_fine(arr, rate, rate_sum, jb, cnt, cls_rank, drop_rank,
                     key_rank, kp, min_rep, max_rep, init_ready):
        """The substep (fine-Δt, checkpoint-resume, optionally preemptive)
        trajectory — the compiled twin of the numpy
        ``_simulate_fleet_substep`` engine. Substeps are unrolled inside the
        scan step (``n_substeps`` is small and static), the batch residue
        rides in the carry, and every float op mirrors the numpy engine's
        operation order so the two agree bit-for-bit."""
        col = jnp.arange(T + 1)

        def take(Acum, done, r):
            j = cnt[:, r]
            a = jnp.take_along_axis(Acum, j[:, None], axis=1)[:, 0]
            return jnp.clip(a - done, 0.0, None)

        def pour(Acum, done, amt):
            """``serve`` + the largest cohort key touched (the batch's
            preemption rank; -inf when nothing poured)."""
            full = take(Acum, done, CT)
            amt = jnp.minimum(jnp.maximum(amt, 0.0), full.sum())

            def bisect(_, lohi):
                lo, hi = lohi
                mid = (lo + hi) // 2
                ge = take(Acum, done, mid).sum() >= amt
                return (jnp.where(ge, lo, mid + 1), jnp.where(ge, mid, hi))

            lo, _ = lax.fori_loop(0, n_rank_iters, bisect,
                                  (jnp.int32(0), jnp.int32(CT)))
            rm1 = jnp.maximum(lo - 1, 0)
            base = take(Acum, done, rm1)
            marginal = cls_rank[rm1]
            split = base + jnp.maximum(amt - base.sum(), 0.0) \
                * (arange_c == marginal)
            split = jnp.where(lo > 0, split, jnp.zeros(C))
            key = jnp.where(lo > 0, key_rank[rm1], -jnp.inf)
            return split, key

        def head_key(Acum, done):
            """Key of the head-of-queue cohort; +inf when empty."""
            total = take(Acum, done, CT).sum()

            def bisect(_, lohi):
                lo, hi = lohi
                mid = (lo + hi) // 2
                ge = take(Acum, done, mid).sum() > 0.0
                return (jnp.where(ge, lo, mid + 1), jnp.where(ge, mid, hi))

            lo, _ = lax.fori_loop(0, n_rank_iters, bisect,
                                  (jnp.int32(0), jnp.int32(CT)))
            return jnp.where(total > 0.0, key_rank[jnp.maximum(lo - 1, 0)],
                             jnp.inf)

        def step(carry, x):
            (ready, in_flight, pend, done, Acum, busy_m, busy_w, busy_k,
             held_m, held_w, held_k, pstate) = carry
            arr_c, rate_c, rate_sum, jb_t, t = x
            matured = pend[t]
            ready = ready + matured
            in_flight = in_flight - matured

            total_prev = Acum[:, T]
            drop = jnp.zeros(C)
            if max_queue is not None:
                out_c0 = (total_prev - done) + busy_m.sum(axis=0) \
                    + held_m.sum(axis=0)
                over = jnp.maximum(out_c0.sum() + arr_c.sum() - max_queue,
                                   0.0)
                order_t = drop_rank[t]
                for rankc in range(C):
                    c = order_t[rankc]
                    d = jnp.minimum(arr_c[c], over)
                    drop = drop.at[c].add(d)
                    over = over - d
            adm_c = arr_c - drop
            new_total = total_prev + adm_c
            Acum = jnp.where(col[None, :] >= t + 1, new_total[:, None], Acum)

            served_bin = 0.0
            pre_n = 0.0
            pre_w = 0.0
            sub_split, sub_bt, sub_served = [], [], []
            for _ in range(n_sub):                 # static unroll
                slot_split_i, slot_bt_i, slot_served_i = [], [], []
                for p in order:                    # static drain order
                    n_rep = jnp.maximum(ready[p], 0.0)
                    has = n_rep > 0
                    tau = dt_sub
                    comp_m = jnp.zeros(C)
                    comp_btw = 0.0
                    hk = head_key(Acum, done)
                    bm, bw, bk = busy_m[p], busy_w[p], busy_k[p]
                    hm, hw, hkey = held_m[p], held_w[p], held_k[p]
                    if preemptive:
                        pr = (bw > 0.0) & (hk < bk)
                        hm = hm + jnp.where(pr, bm, 0.0)
                        hw = hw + jnp.where(pr, bw, 0.0)
                        hkey = jnp.where(pr, jnp.maximum(hkey, bk), hkey)
                        pre_n = pre_n + pr
                        pre_w = pre_w + jnp.where(pr, bw, 0.0)
                        bm = jnp.where(pr, 0.0, bm)
                        bw = jnp.where(pr, 0.0, bw)
                        bk = jnp.where(pr, -jnp.inf, bk)
                    # progress the in-flight batch
                    w = bw
                    tau0 = tau
                    fin = (w > 0.0) & (w <= tau0)
                    run = w > tau0
                    comp_m = comp_m + jnp.where(fin, bm, 0.0)
                    comp_btw = comp_btw + jnp.where(
                        fin, bm.sum() * ((dt_sub - tau0) + w), 0.0)
                    bw = jnp.where(run, w - tau0, 0.0)
                    bm = jnp.where(fin, jnp.zeros(C), bm)
                    bk = jnp.where(fin, -jnp.inf, bk)
                    tau = jnp.where(fin, tau0 - w,
                                    jnp.where(run, 0.0, tau0))
                    # resume a checkpoint, else form a new batch
                    idle = bw == 0.0
                    res = idle & (hw > 0.0) & (hk >= hkey)
                    bm = jnp.where(res, hm, bm)
                    bw = jnp.where(res, hw, bw)
                    bk = jnp.where(res, hkey, bk)
                    hm = jnp.where(res, jnp.zeros(C), hm)
                    hw = jnp.where(res, 0.0, hw)
                    hkey = jnp.where(res, -jnp.inf, hkey)

                    backlog = (new_total - done).sum()
                    form = idle & (~res) & (backlog > 0.0) & (tau > 0.0) \
                        & has
                    b = jnp.clip(jnp.where(has, jnp.ceil(
                        backlog / jnp.where(has, n_rep, 1.0)), 0.0),
                        1.0, max_b[p])
                    bt_b = jnp.maximum(t_fixed[p] + b * t_unit[p], _EPS)
                    amt = jnp.where(form, jnp.minimum(backlog, n_rep * b),
                                    0.0)
                    split, _ = pour(Acum, done, amt)
                    done = done + split
                    bm = jnp.where(form, split, bm)
                    bw = jnp.where(form, bt_b, bw)
                    # preemption rank = head key at formation (the numpy
                    # engine's convention: rank by the batch's most urgent
                    # cohort, so urgent mass is never checkpointed behind a
                    # max-key resume gate)
                    bk = jnp.where(form, hk, bk)
                    # progress the resumed/formed batch
                    w2 = bw
                    tau0 = tau
                    fin2 = (w2 > 0.0) & (w2 <= tau0)
                    run2 = w2 > tau0
                    comp_m = comp_m + jnp.where(fin2, bm, 0.0)
                    comp_btw = comp_btw + jnp.where(
                        fin2, bm.sum() * ((dt_sub - tau0) + w2), 0.0)
                    bw = jnp.where(run2, w2 - tau0, 0.0)
                    bm = jnp.where(fin2, jnp.zeros(C), bm)
                    bk = jnp.where(fin2, -jnp.inf, bk)
                    tau = jnp.where(fin2, tau0 - w2,
                                    jnp.where(run2, 0.0, tau0))
                    # fluid tail (the coarse within-bin convention)
                    idle2 = bw == 0.0
                    backlog2 = (new_total - done).sum()
                    b2 = jnp.clip(jnp.where(has, jnp.ceil(
                        backlog2 / jnp.where(has, n_rep, 1.0)), 0.0),
                        1.0, max_b[p])
                    bt2 = jnp.maximum(t_fixed[p] + b2 * t_unit[p], _EPS)
                    tail = idle2 & (tau > 0.0) & has
                    cap = jnp.where(tail, n_rep * b2 / bt2, 0.0) * tau
                    amt2 = jnp.minimum(jnp.maximum(backlog2, 0.0), cap)
                    split2, _ = pour(Acum, done, amt2)
                    done = done + split2
                    pour_tot = split2.sum()
                    comp_tot = comp_m.sum()
                    busy_m = busy_m.at[p].set(bm)
                    busy_w = busy_w.at[p].set(bw)
                    busy_k = busy_k.at[p].set(bk)
                    held_m = held_m.at[p].set(hm)
                    held_w = held_w.at[p].set(hw)
                    held_k = held_k.at[p].set(hkey)
                    slot_split_i.append(comp_m)
                    slot_split_i.append(split2)
                    slot_bt_i.append(jnp.where(
                        comp_tot > 0,
                        comp_btw / jnp.where(comp_tot > 0, comp_tot, 1.0),
                        0.0))
                    slot_bt_i.append(jnp.where(pour_tot > 0.0,
                                               (dt_sub - tau) + bt2, 0.0))
                    slot_served_i.append(comp_tot)
                    slot_served_i.append(pour_tot)
                    served_bin = served_bin + comp_tot
                    served_bin = served_bin + pour_tot
                # fold sub-eps float residue once per substep (the numpy
                # engine's _MASS_EPS behaviour)
                done = jnp.where(new_total - done <= 1e-9 + 1e-12 * new_total,
                                 new_total, done)
                sub_split.append(jnp.stack(slot_split_i))   # (2P, C)
                sub_bt.append(jnp.stack(slot_bt_i))
                sub_served.append(jnp.stack(slot_served_i))

            out_c = jnp.maximum(new_total - done, 0.0) + busy_m.sum(axis=0) \
                + held_m.sum(axis=0)
            queue = out_c.sum()
            capacity = 0.0
            for p in range(P):
                capacity = capacity + jnp.maximum(ready[p], 0.0) \
                    * tput[p] * dt
            util = jnp.where(capacity > 0, served_bin / capacity, 0.0)
            util = jnp.minimum(util, 1.0)
            from repro.fleet.kernels import KernelObs
            obs = KernelObs(
                t_s=(t + 1) * dt, dt_s=dt, arrival_rate=rate_sum,
                queue=queue, replicas=ready.sum(),
                in_flight=in_flight.sum(), utilization=util,
                pool_replicas=ready, pool_in_flight=in_flight,
                class_queue=out_c, class_arrival_rate=rate_c,
                min_replicas=min_rep, max_replicas=max_rep)
            pool_rep = ready
            pstate, target = kernel.step(kp, pstate, obs)
            target = jnp.clip(target, min_rep, max_rep)

            excess = jnp.maximum(ready + in_flight - target, 0.0)
            zero = jnp.int32(0)
            window = lax.dynamic_slice(pend, (t + 1, zero), (W, P))
            newer = jnp.cumsum(window[::-1, :], axis=0)[::-1, :] - window
            cut = jnp.clip(excess[None, :] - newer, 0.0, window)
            window = window - cut
            canceled = cut.sum(axis=0)
            pend = lax.dynamic_update_slice(pend, window, (t + 1, zero))
            in_flight = in_flight - canceled
            ready = jnp.maximum(ready - (excess - canceled), 0.0)
            grow = jnp.maximum(target - ready - in_flight, 0.0)
            pend = pend.at[t + 1 + jb_t, jnp.arange(P)].add(grow)
            in_flight = in_flight + grow
            billed = pool_rep + in_flight
            residue = busy_w.sum() + held_w.sum()

            ys = {"slot_split": jnp.stack(sub_split),    # (n_sub, 2P, C)
                  "slot_bt": jnp.stack(sub_bt),          # (n_sub, 2P)
                  "slot_served": jnp.stack(sub_served),  # (n_sub, 2P)
                  "served_bin": served_bin,
                  "admitted_c": adm_c, "dropped_c": drop,
                  "queue_c": out_c, "pool_rep": pool_rep,
                  "billed": billed, "util": util,
                  "pre_n": pre_n, "pre_w": pre_w, "residue": residue}
            return (ready, in_flight, pend, done, Acum, busy_m, busy_w,
                    busy_k, held_m, held_w, held_k, pstate), ys

        carry0 = (init_ready, jnp.zeros(P), jnp.zeros((Tpad, P)),
                  jnp.zeros(C), jnp.zeros((C, T + 1)),
                  jnp.zeros((P, C)), jnp.zeros(P), jnp.full(P, -jnp.inf),
                  jnp.zeros((P, C)), jnp.zeros(P), jnp.full(P, -jnp.inf),
                  kernel.init())
        xs = (arr, rate, rate_sum, jb, jnp.arange(T, dtype=jnp.int32))
        _, ys = lax.scan(step, carry0, xs)
        return ys

    core_one = sim_one if n_sub == 1 and not preemptive else sim_one_fine
    over_seeds = jax.vmap(core_one,
                          in_axes=(0, 0, 0, 0, None, None, None, None, None,
                                   None, None, None))
    over_cands = jax.vmap(over_seeds,
                          in_axes=(None, None, None, None, 0, 0, 0, 0, 0, 0,
                                   0, 0))
    return jax.jit(over_cands)


def _core_for(kernel, **statics):
    key = (id(kernel),) + tuple(sorted(
        (k, tuple(v) if isinstance(v, (list, np.ndarray)) else v)
        for k, v in statics.items()))
    core = _CORE_CACHE.get(key)
    telemetry.counter("jaxsim_core_cache_total",
                      result="hit" if core is not None else "miss")
    if core is None:
        core = _build_core(kernel, **statics)
        _CORE_CACHE[key] = core
    return core


def _pad_pow2(n: int) -> int:
    return 1 << max(int(np.ceil(np.log2(max(n, 1)))), 0)


def run_dynamics(kernel, *, arrivals, jb, dt, order, t_fixed, t_unit, max_b,
                 max_queue, tables, kp, min_rep, max_rep, init_ready,
                 max_cold_bins, tput=(), n_substeps: int = 1,
                 preemptive: bool = False, tile: int = None,
                 _pad_to: int = None, _tile_idx: tuple = None) -> dict:
    """Run the compiled dynamics for a stacked batch of candidates against a
    shared seed batch; one jitted dispatch covers the whole lattice.

    arrivals (S, T, C) and jb (S, T, P) are shared across candidates (the
    paired common-random-numbers design); ``tables`` (stacked
    ``cohort_tables``), ``kp`` (stacked kernel params), quota bounds and
    initial fleets are per-candidate with leading dim N. Returns numpy
    arrays with leading dims (N, S, T). Candidate batches are padded to the
    next power of two (padding replays candidate 0) so racing's shrinking
    rounds hit a handful of compiled programs.

    ``tile`` streams candidate slates wider than the (pow2-rounded) tile
    through fixed-shape chunks: every chunk — the tail included — pads to
    the full tile width, so the whole stream shares ONE compiled program
    and every dispatch after the first is warm. That is what bounds device
    memory and compile count when a racing round carries thousands of LHS
    candidates. Results are bit-identical to the untiled dispatch (padding
    rows are discarded per chunk).
    """
    import jax

    _attach_cache_listener()

    arrivals = np.asarray(arrivals, np.float64)
    S, T, C = arrivals.shape
    P = len(order)
    N = len(min_rep)
    if tile is not None:
        tile_w = _pad_pow2(int(tile))
        if N > tile_w:
            n_tiles = int(np.ceil(N / tile_w))
            kp = {k: np.asarray(v) for k, v in kp.items()}
            min_rep, max_rep, init_ready = (np.asarray(min_rep),
                                            np.asarray(max_rep),
                                            np.asarray(init_ready))
            outs = []
            for i in range(n_tiles):
                sl = slice(i * tile_w, min((i + 1) * tile_w, N))
                outs.append(run_dynamics(
                    kernel, arrivals=arrivals, jb=jb, dt=dt, order=order,
                    t_fixed=t_fixed, t_unit=t_unit, max_b=max_b,
                    max_queue=max_queue,
                    tables={k: v[sl] for k, v in tables.items()},
                    kp={k: v[sl] for k, v in kp.items()},
                    min_rep=min_rep[sl], max_rep=max_rep[sl],
                    init_ready=init_ready[sl], max_cold_bins=max_cold_bins,
                    tput=tput, n_substeps=n_substeps, preemptive=preemptive,
                    _pad_to=tile_w, _tile_idx=(i, n_tiles)))
            telemetry.counter("jaxsim_tiles_total", n_tiles)
            return {k: np.concatenate([o[k] for o in outs], axis=0)
                    for k in outs[0]}
    Npad = _pad_pow2(N) if _pad_to is None else int(_pad_to)

    def pad(a):
        a = np.asarray(a)
        if Npad == N:
            return a
        reps = np.repeat(a[:1], Npad - N, axis=0)
        return np.concatenate([a, reps], axis=0)

    core = _core_for(
        kernel, T=T, C=C, P=P, Tpad=T + max_cold_bins + 2,
        W=max_cold_bins + 1, dt=float(dt), order=tuple(order),
        t_fixed=tuple(float(v) for v in t_fixed),
        t_unit=tuple(float(v) for v in t_unit),
        max_b=tuple(float(v) for v in max_b),
        max_queue=None if max_queue is None else float(max_queue),
        n_substeps=int(n_substeps), preemptive=bool(preemptive),
        tput=tuple(float(v) for v in tput))
    # host-side divisions: XLA folds constant divisors into inexact
    # reciprocal multiplies, but policy ceil()s must see the exact IEEE
    # quotients the numpy reference sees
    rate = arrivals / float(dt)
    rate_sum = arrivals.sum(axis=2) / float(dt)
    # cold = this (compiled core, input shapes) pair has never dispatched, so
    # this call pays XLA compilation; the split is what the sim benchmark and
    # the tuner timing breakdown report as compile-vs-dispatch seconds
    sig = (id(core), Npad, S, T, C, P)
    cold = sig not in _DISPATCHED
    attrs = dict(kind="cold" if cold else "warm",
                 candidates=N, padded=Npad, seeds=S, bins=T)
    if _tile_idx is not None:
        attrs.update(tile=_tile_idx[0], n_tiles=_tile_idx[1])
    t0 = time.perf_counter()
    with telemetry.span("jaxsim.dispatch", **attrs):
        with jax.enable_x64(True):
            out = core(arrivals, rate, rate_sum, np.asarray(jb, np.int32),
                       pad(tables["cnt"]), pad(tables["cls_of_rank"]),
                       pad(tables["drop_rank"]), pad(tables["key_of_rank"]),
                       {k: pad(v) for k, v in kp.items()},
                       pad(np.asarray(min_rep, np.float64)),
                       pad(np.asarray(max_rep, np.float64)),
                       pad(np.asarray(init_ready, np.float64)))
            out = jax.device_get(out)
    _DISPATCHED.add(sig)
    kind = "cold" if cold else "warm"
    telemetry.counter("jaxsim_dispatch_total", kind=kind)
    telemetry.counter("jaxsim_dispatch_seconds_total",
                      time.perf_counter() - t0, kind=kind)
    return {k: np.asarray(v)[:N] for k, v in out.items()}
