"""Pallas megakernel for the cluster event scan (base pull configuration).

One ``pl.pallas_call`` program per batched cell (grid over the batch axis):
the cell's carry stays resident in VMEM across the whole ``fori_loop`` over
events and the per-dispatch records accumulate in VMEM rows, so a cell's
entire event history is one kernel launch instead of ``n_steps``
host-visible scan iterations.

Scope: the **base pull** regime only -- late-binding queue, one controller
estimator ring, optional FC pull counts (``use_fc``); no frozen-priority
(``freeze``), capacity dynamics, heterogeneity, hedging, cold-start or
duplicate machinery.  Everything else dispatches to the pure-jnp oracle in
``repro.kernels.ops.event_step`` (which *is* the fused CPU path).  The
kernel body mirrors the oracle's step op-for-op against the same
:class:`repro.core.fastpath._PlaneLayout` carry, with mechanical
substitutions the TPU compiler (Mosaic) accepts:

* the packed carry planes are unpacked once, outside the kernel, into
  2-D tiles: per-function vectors as ``(F, 1)`` columns, per-node vectors
  as ``(NN, 1)`` columns, event-axis vectors as ``(1, n1p)`` rows padded
  to a lane multiple (``t`` pads with +inf, so no padded row is ever an
  event), and every batch-indexed block has a squeezed batch dimension;
* every dynamic gather is a one-hot masked reduction (exact -- the sum adds
  a single selected value to zeros), ``searchsorted`` is a ``sum(t <= v)``
  count (identical on the sorted arrival stream), and ``argmin``/``argmax``
  are first-index min reductions, ``min(where(x == best, ids, big))``;
* the per-dispatch records are masked row updates carried through the loop
  and stored once at the end (Mosaic cannot store at a dynamic lane);
* ``cores``/``nodes`` arrive as scalar-prefetch operands in SMEM.

Rows ``[:n]`` of the outputs are therefore the oracle's; row ``n`` is the
shared garbage sentinel both paths scribble no-op events into.  The parity
suite runs this kernel under ``interpret=True`` on CPU, and
``tests/test_tpu_compile.py`` compiles it for a described TPU v5e.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANES = 128


def event_step_supported(*, freeze, use_fc, fc_push, dyn, het, hedge, cold,
                         dup, stream=False, **_static) -> bool:
    """True when the static feature set falls inside the Pallas kernel's
    scope (base pull, with or without FC pull counts).  ``stream`` (the
    chunked carry-handoff variant) always falls back to the jnp oracle: the
    Pallas body predates the t_stop gate / CSR fn_ev / qcnt carry."""
    return not (freeze or fc_push or dyn or het or hedge or cold or dup
                or stream)


def _pick(vec, ids, i):
    """``vec[i]`` as a ``(1, 1)`` one-hot masked reduction (no dynamic
    gather); exact -- one selected value summed with zeros."""
    return jnp.sum(jnp.where(ids == i, vec, jnp.zeros_like(vec)),
                   keepdims=True)


def _first(mask, ids, big):
    """First index where ``mask`` holds (``big`` when none), as ``(1, 1)``."""
    return jnp.min(jnp.where(mask, ids, big), keepdims=True)


def _event_kernel(cores_ref, nodes_ref, t_ref, fnid_ref, p_ref, cost_ref,
                  coef_ref, fnev_ref, *refs, n, n1p, n_nodes, n_slots,
                  window, n_fns, kq, use_fc, horizon, n_steps, ft):
    if use_fc:
        cumf_ref, *refs = refs
    (ai_ref, head_ref, fin_ref, idx_ref, busy_ref, chan_ref, ring_ref,
     rsum_ref, rlen_ref, rpos_ref, last_ref, prev_ref, narr_ref,
     start_ref, finish_ref, prio_ref, node_ref) = refs
    b = pl.program_id(0)
    cores = cores_ref[b]
    nodes = nodes_ref[b]
    t_arr = t_ref[...]                     # (1, n1p) rows
    fnid = fnid_ref[...]
    p = p_ref[...]
    cost = cost_ref[...]
    coef = coef_ref[...]                   # (1, ncoef)
    fn_ev = fnev_ref[...]                  # (F, kq)
    cumf = cumf_ref[...] if use_fc else None   # (F, n1p)
    c0, c1, c2, c3 = (coef[:, k:k + 1] for k in range(4))

    i32 = jnp.int32
    inf = jnp.asarray(jnp.inf, dtype=ft)
    ev = lax.broadcasted_iota(i32, (1, n1p), 1)
    fn_col = lax.broadcasted_iota(i32, (n_fns, 1), 0)
    node_col = lax.broadcasted_iota(i32, (n_nodes, 1), 0)
    slot_row = lax.broadcasted_iota(i32, (1, n_slots), 1)
    win_row = lax.broadcasted_iota(i32, (1, window), 1)
    kq_row = lax.broadcasted_iota(i32, (1, kq), 1)
    slot_ns = lax.broadcasted_iota(i32, (n_nodes, n_slots), 1)
    flat_ids = (lax.broadcasted_iota(i32, (n_nodes, n_slots), 0) * n_slots
                + slot_ns)
    active = node_col < nodes
    kmax = kq - 1

    def step(_, carry):
        (ai, head, fin_s, idx_s, busy, chan, ring, rsum, rlen, rpos, last_t,
         prev_t, narr, o_start, o_fin, o_prio, o_node) = carry

        # -- event selection: arrival vs earliest completion (arrival wins
        # exact ties, matching the oracle's first-min argmin precedence)
        t_a = _pick(t_arr, ev, ai)
        t_c = jnp.min(fin_s, keepdims=True)
        kflat = _first(fin_s == t_c, flat_ids, n_nodes * n_slots)
        now = jnp.minimum(t_a, t_c)
        none_left = now == inf
        do_arr = (t_a <= t_c) & ~none_left
        do_comp = (t_c < t_a) & ~none_left

        # -- completion: free the slot, feed the controller ring ------------
        m_k = flat_ids == kflat
        kn = kflat // n_slots
        j_done = _pick(idx_s, flat_ids, kflat)
        f_done = _pick(fnid, ev, j_done)
        m_fd = fn_col == f_done
        m_cf = m_fd & do_comp                    # (F, 1): en_c == 0
        pos = _pick(rpos, fn_col, f_done)
        v = _pick(p, ev, j_done)
        old = jnp.sum(jnp.where(m_fd & (win_row == pos), ring,
                                jnp.zeros_like(ring)), keepdims=True)
        full = _pick(rlen, fn_col, f_done) == window
        rsum = jnp.where(m_cf, rsum + v - jnp.where(full, old, 0.0), rsum)
        ring = jnp.where(m_cf & (win_row == pos), v, ring)
        rlen = jnp.where(m_cf & ~full, rlen + 1, rlen)
        rpos = jnp.where(m_cf, (rpos + 1) % window, rpos)
        m_kn = (node_col == kn) & do_comp
        busy = jnp.where(m_kn, busy - 1, busy)
        fin_s = jnp.where(m_k & do_comp, inf, fin_s)

        # -- arrival: enqueue, observe on the controller estimator ----------
        i_ins = jnp.minimum(ai, n)
        f_i = _pick(fnid, ev, i_ins)
        first = _pick(narr, fn_col, f_i) == 0
        prev_used = jnp.where(first, now, _pick(last_t, fn_col, f_i))
        m_af = (fn_col == f_i) & do_arr
        prev_t = jnp.where(m_af, prev_used, prev_t)
        last_t = jnp.where(m_af, now, last_t)
        narr = jnp.where(m_af, narr + 1, narr)
        ai = ai + do_arr.astype(i32)

        # -- dispatch: most-free invoker pulls the global best head ---------
        fs = jnp.where(active, cores - busy, -1)
        k_d = _first(fs == jnp.max(fs, keepdims=True), node_col, n_nodes)
        est_f = jnp.where(rlen > 0, rsum / jnp.maximum(rlen, 1), 0.0)
        hm = jnp.minimum(head, kmax)
        idx_f = jnp.sum(jnp.where(kq_row == hm, fn_ev,
                                  jnp.zeros_like(fn_ev)),
                        axis=1, keepdims=True)           # (F, 1)
        valid = head < narr
        if use_fc:
            # searchsorted(t_arr, v, "right") == count of entries <= v on
            # the sorted stream; the +inf sentinel/padding keeps k0 <= n
            # whenever v is finite
            k0 = jnp.sum((t_arr <= now - horizon).astype(i32),
                         keepdims=True)
            row_a = jnp.sum(jnp.where(ev == ai, cumf, jnp.zeros_like(cumf)),
                            axis=1, keepdims=True)
            row_0 = jnp.sum(jnp.where(ev == k0, cumf, jnp.zeros_like(cumf)),
                            axis=1, keepdims=True)
            cnt_f = (row_a - row_0).astype(jnp.float32)
            w_est = c2 + c3 * cnt_f
        else:
            w_est = c2
        base_f = c1 * prev_t + w_est * est_f
        t_idx = jnp.sum(jnp.where(idx_f == ev, t_arr, jnp.zeros_like(t_arr)),
                        axis=1, keepdims=True)           # (F, 1)
        prio_f = jnp.where(valid, c0 * t_idx + base_f, inf)
        best = jnp.min(prio_f, keepdims=True)
        j = _first(valid & (prio_f == best), idx_f, n)
        has_q = j < n
        can = ~none_left & (_pick(busy, node_col, k_d) < cores) & has_q
        cost_j = _pick(cost, ev, j)
        exec_start = jnp.maximum(now, _pick(chan, node_col, k_d)) + cost_j
        m_kd = node_col == k_d
        chan = jnp.where(m_kd & can, exec_start, chan)
        fin_j = exec_start + _pick(p, ev, j)
        fin_kd = jnp.sum(jnp.where(m_kd, fin_s, jnp.zeros_like(fin_s)),
                         axis=0, keepdims=True)          # (1, NS)
        slot_free = (fin_kd == inf) & (slot_row < cores)
        s = _first(slot_free, slot_row, n_slots)
        s = jnp.where(s == n_slots, 0, s)                # argmax of all-False
        m_ds = m_kd & (slot_ns == s) & can
        fin_s = jnp.where(m_ds, fin_j, fin_s)
        idx_s = jnp.where(m_ds, j, idx_s)
        busy = jnp.where(m_kd & can, busy + 1, busy)
        head = jnp.where((fn_col == _pick(fnid, ev, j)) & can, head + 1, head)

        # -- per-dispatch record: masked row updates (no-ops hit row n) -----
        m_jn = ev == jnp.where(can, j, n)
        o_start = jnp.where(m_jn, exec_start, o_start)
        o_fin = jnp.where(m_jn, fin_j, o_fin)
        o_prio = jnp.where(m_jn, best, o_prio)
        o_node = jnp.where(m_jn, k_d, o_node)
        return (ai, head, fin_s, idx_s, busy, chan, ring, rsum, rlen, rpos,
                last_t, prev_t, narr, o_start, o_fin, o_prio, o_node)

    # never-dispatched rows (none exist for a filled cell) read as the
    # oracle's scatter zeros
    zf = jnp.zeros((1, n1p), dtype=ft)
    init = (ai_ref[...], head_ref[...], fin_ref[...], idx_ref[...],
            busy_ref[...], chan_ref[...], ring_ref[...], rsum_ref[...],
            rlen_ref[...], rpos_ref[...], last_ref[...], prev_ref[...],
            narr_ref[...], zf, zf, zf, jnp.zeros((1, n1p), dtype=i32))
    out = lax.fori_loop(0, n_steps, step, init)
    start_ref[...] = out[-4]
    finish_ref[...] = out[-3]
    prio_ref[...] = out[-2]
    node_ref[...] = out[-1]


def _kernel_operands(clk, ctr, inp, *, layout, n1p, use_fc):
    """Batched kernel operands: the carry planes unpacked into 2-D tiles and
    the event-axis inputs padded to ``n1p`` lanes (see the module doc)."""
    B, n1 = inp["t"].shape
    st = jax.vmap(layout.unpack)(clk, ctr)

    def row(x, fill):                    # (B, n1) -> (B, 1, n1p)
        x = jnp.pad(x, ((0, 0), (0, n1p - n1)), constant_values=fill)
        return x[:, None, :]

    def col(x):                          # (B, [1,] L) -> (B, L, 1)
        return x.reshape(B, -1, 1)

    ops = [row(inp["t"], jnp.inf), row(inp["fnid"], 0), row(inp["p"], 0),
           row(inp["cost"], 0), inp["coef"][:, None, :], inp["fn_ev"]]
    if use_fc:
        # (B, n1, F) -> (B, F, n1p): per-function counts as lane rows
        ops.append(jnp.pad(jnp.swapaxes(inp["cumf"], 1, 2),
                           ((0, 0), (0, 0), (0, n1p - n1))))
    ops += [st["ai"].reshape(B, 1, 1), col(st["head"]), st["fin_s"],
            st["idx_s"], col(st["busy"]), col(st["chan"]),
            st["ring"][:, 0], col(st["rsum"]), col(st["rlen"]),
            col(st["rpos"]), col(st["last_t"]), col(st["prev_t"]),
            col(st["narr"])]
    return ops


def event_step_pallas(clk, ctr, inp, *, interpret=False, n_nodes, n_slots,
                      window, use_fc, horizon, n_steps, n_copies=1,
                      fc_ring=1, **_static):
    """Batched base-pull event scan as one Pallas launch per cell.

    Same contract as the oracle path of ``repro.kernels.ops.event_step``:
    ``clk``/``ctr`` are the packed ``(B, f_len)`` / ``(B, i_len)`` carry
    planes, ``inp`` the batched bucket input dict; returns the
    ``(start, finish, prio, node, aux)`` tuple with ``aux == {}``."""
    from ..core import fastpath as _fp     # lazy: core is heavy

    B, n1 = inp["t"].shape
    n = n1 - 1
    n1p = -(-n1 // _LANES) * _LANES
    n_fns, kq = inp["fn_ev"].shape[1], inp["fn_ev"].shape[2]
    ft = inp["t"].dtype

    spec = {k: jax.ShapeDtypeStruct(v.shape[1:], v.dtype)
            for k, v in inp.items()}
    layout = _fp._carry_layout(spec, n_nodes=n_nodes, n_slots=n_slots,
                               window=window, freeze=False, fc_push=False,
                               dyn=False, het=False, hedge=False,
                               cold=False, dup=False, n_copies=n_copies,
                               fc_ring=fc_ring)
    ops = _kernel_operands(clk, ctr, inp, layout=layout, n1p=n1p,
                           use_fc=use_fc)

    def block(x):
        # squeezed batch dim; the trailing dims are the whole array, which
        # satisfies the TPU (8, 128) block rule at any size
        return pl.BlockSpec((None,) + x.shape[1:],
                            lambda b, *_: (b,) + (0,) * (x.ndim - 1))

    out_row = pl.BlockSpec((None, 1, n1p), lambda b, *_: (b, 0, 0))
    kernel = partial(_event_kernel, n=n, n1p=n1p, n_nodes=n_nodes,
                     n_slots=n_slots, window=window, n_fns=n_fns, kq=kq,
                     use_fc=use_fc, horizon=horizon, n_steps=n_steps, ft=ft)
    start, finish, prio, node = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,                       # cores, nodes
            grid=(B,),
            in_specs=[block(x) for x in ops],
            out_specs=[out_row] * 4),
        out_shape=[jax.ShapeDtypeStruct((B, 1, n1p), ft)] * 3
        + [jax.ShapeDtypeStruct((B, 1, n1p), jnp.int32)],
        interpret=interpret,
        name="event_step",
    )(inp["cores"], inp["nodes"], *ops)
    return (start[:, 0, :n1], finish[:, 0, :n1], prio[:, 0, :n1],
            node[:, 0, :n1], {})
