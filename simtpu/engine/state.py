"""Functional scheduling state — the scan carry.

Replaces the reference's mutable scheduler cache + assume-cache + node
annotations (`vendor/.../scheduler/internal/cache/cache.go:57`,
`pkg/simulator/plugin/open-local.go:174-253`) with a pytree of dense arrays
threaded through `lax.scan`. No locks, no event bus: every placement is a pure
state transition (SURVEY.md §2.3).
"""

from __future__ import annotations

import os
from functools import partial
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..core.tensorize import COUNT_DTYPE, MASK_DTYPE
from ..native import scatter_add_rows

# plane height up to which the one-hot matmul forms pay: the matmul touches
# the WHOLE plane (fine for the rounds engine's ROW_BUDGET-bounded carried
# planes and the [K, N] domain map), while a tall plane (the serial scan's
# full [T, N] count state) is cheaper through the classic gather/scatter,
# which touches only the addressed rows
_MATMUL_ROWS = 512


def take_rows(plane: jnp.ndarray, rows: jnp.ndarray) -> jnp.ndarray:
    """`plane[rows]` for a [K, N] plane and a small [Tc] int row vector.
    Negative row ids yield ZERO rows, subsuming the
    `where(valid, plane[clip(rows)], 0)` masking idiom at the call sites.

    For short planes this is a one-hot matmul: dynamic row gathers along
    the major axis lower to latency-bound kernels on TPU (measured ~4 ms
    for a 1.6 MB gather at 100k nodes — the single hottest op in a bulk
    round), while the [Tc, K] @ [K, N] product rides the MXU at memory
    bandwidth. Precision is pinned to HIGHEST: the TPU's default bf16
    matmul would round counts/domain ids above 256, while the f32-exact
    passes keep one-hot selection bit-identical to the gather. Tall planes
    keep the masked gather (the matmul would read the whole plane)."""
    if plane.shape[0] <= _MATMUL_ROWS:
        oh = jax.nn.one_hot(rows, plane.shape[0], dtype=jnp.float32)
        return jnp.matmul(
            oh, plane.astype(jnp.float32), precision=jax.lax.Precision.HIGHEST
        )
    safe = jnp.clip(rows, 0)
    return jnp.where(
        (rows >= 0)[:, None], plane[safe].astype(jnp.float32), 0.0
    )


def take_rows_i32(plane: jnp.ndarray, rows: jnp.ndarray) -> jnp.ndarray:
    """Integer-plane row gather via take_rows; exact for values below 2^24
    (domain ids). Negative row ids yield 0 — callers that need a -1
    sentinel for invalid rows must mask separately."""
    if plane.shape[0] <= _MATMUL_ROWS:
        return take_rows(plane, rows).astype(jnp.int32)
    safe = jnp.clip(rows, 0)
    return jnp.where((rows >= 0)[:, None], plane[safe], 0)


def add_rows(plane: jnp.ndarray, rows: jnp.ndarray, delta: jnp.ndarray) -> jnp.ndarray:
    """`plane.at[rows].add(delta)`: duplicate and negative row ids behave
    like scatter-add with masked rows. Short planes use the full-plane
    matmul add (row scatters cost milliseconds each on TPU; the
    [T, Tc] @ [Tc, N] product plus a full-plane add runs at bandwidth —
    the rounds engine's carried planes are ROW_BUDGET-bounded, ~100 MB).
    Tall planes (the serial scan's full count state) keep the row scatter,
    which touches only the addressed rows."""
    if plane.shape[0] <= _MATMUL_ROWS:
        oh = jax.nn.one_hot(rows, plane.shape[0], dtype=delta.dtype)
        return plane + jnp.matmul(
            oh.T, delta, precision=jax.lax.Precision.HIGHEST
        )
    safe = jnp.clip(rows, 0)
    return plane.at[safe].add(jnp.where((rows >= 0)[:, None], delta, 0.0))


def interpod_term_index(tensors) -> np.ndarray:
    """[T] → row in the compacted interpod ("own") count planes, -1 when the
    term appears in no group's required/preferred (anti-)affinity. Ascending
    term order; shared by statics_from and build_state so plane rows agree.
    Memoized on the tensors object — the rounds engine's chunked dispatch
    asks per chunk."""
    cached = getattr(tensors, "_ip_of_cache", None)
    if cached is not None:
        return cached
    t = tensors.n_terms
    if not t:
        ip_of = np.zeros(0, np.int32)
    else:
        used = (
            tensors.a_aff_req.any(axis=0)
            | tensors.a_anti_req.any(axis=0)
            | (tensors.w_aff_pref != 0).any(axis=0)
            | (tensors.w_anti_pref != 0).any(axis=0)
        )
        ip_of = np.full(t, -1, np.int32)
        ip_of[used] = np.arange(int(used.sum()), dtype=np.int32)
    object.__setattr__(tensors, "_ip_of_cache", ip_of)
    return ip_of


def _add_at_rows(dst: np.ndarray, idx: np.ndarray, src: np.ndarray) -> None:
    """dst[idx[i], :] += src[i, :] — native C scatter when built, else
    np.add.at (which is ~50x slower on large placement logs)."""
    if dst.size == 0 or len(idx) == 0:
        return
    if not scatter_add_rows(dst, idx, src):
        np.add.at(dst, idx, src)


class SchedState(NamedTuple):
    """Mutable-under-scan cluster state.

    Topology counts are stored **per node**, not per domain: `cnt_*[t, n]` is
    the count in node n's domain for term t's topology key (0 where the node
    misses the key). Placing a pod updates them with one vectorized
    same-domain compare (`dom_sub == dom_sub[:, chosen]`) — no gather or
    scatter appears anywhere in the scan step, which is what keeps the step
    fast on TPU (gathers over the domain axis were the dominant cost), and
    the [T, N] layout shards over the node axis with everything else.

    free:            [N, R] remaining allocatable per node
    cnt_match:       [T, N] placed pods matching term t in node n's domain
    cnt_total:       [T] cluster-wide matching count per term (pods placed on
                     nodes carrying the key — interpod first-pod escape)

    The four "own" planes live on the compacted interpod axis (Ti rows,
    `interpod_term_index`): only terms appearing in some group's required or
    preferred (anti-)affinity have a row — T grows with the number of
    workloads (SelectorSpread interns ~2 terms per controller), while Ti
    stays at the handful that actually need owner bookkeeping, which is what
    keeps the state within single-chip HBM at 100k nodes.

    cnt_own_anti:    [Ti, N] placed pods owning required anti-affinity term
    cnt_own_aff:     [Ti, N] placed pods owning required affinity term
    w_own_aff_pref:  [Ti, N] summed preferred-affinity weights of placed owners
    w_own_anti_pref: [Ti, N] summed preferred-anti-affinity weights
    vg_free:         [N, V] free LVM volume-group space (Open-Local)
    sdev_free:       [N, SD] exclusive storage devices still unallocated
    gpu_free:        [N, GD] free GPU memory per device (GPU-share)
    ports_used:      [N, P] in-use (protocol, hostPort) pairs (NodePorts)
    vols_any:        [N, W] users of exclusive volume w (VolumeRestrictions)
    vols_rw:         [N, W] read-write users of exclusive volume w
    """

    free: jnp.ndarray
    cnt_match: jnp.ndarray
    cnt_total: jnp.ndarray
    cnt_own_anti: jnp.ndarray
    cnt_own_aff: jnp.ndarray
    w_own_aff_pref: jnp.ndarray
    w_own_anti_pref: jnp.ndarray
    vg_free: jnp.ndarray
    sdev_free: jnp.ndarray
    gpu_free: jnp.ndarray
    ports_used: jnp.ndarray
    vols_any: jnp.ndarray
    vols_rw: jnp.ndarray


def build_state(
    tensors,
    placed_group: np.ndarray,
    placed_node: np.ndarray,
    placed_req: np.ndarray,
    placed_ext: dict = None,
) -> SchedState:
    """Reconstruct the full scan carry from the host-side placement log.

    Called at the start of every app batch (group/term vocabularies may have
    grown since the last batch, so counts are recomputed from scratch — the
    reference equivalently recounts topology pairs from the live cache every
    PreFilter, `plugins/interpodaffinity/filtering.go`). O(P·T) numpy work.
    """
    n, r = tensors.alloc.shape
    t, d = tensors.n_terms, tensors.n_domains
    ip_of = interpod_term_index(tensors)
    ti = int(ip_of.max()) + 1 if t else 0
    ext = tensors.ext
    if not len(placed_group) and (placed_ext is None or not len(placed_ext.get("node", ()))):
        # empty log (fresh engine / first batch): everything derives from
        # the cluster tensors alone, and the count planes are zeros —
        # allocate them ON DEVICE rather than materializing ~hundreds of MB
        # host-side and transferring (this path is on the bench's critical
        # start-up, once per fresh engine)
        return SchedState(
            free=jnp.asarray(tensors.alloc.astype(np.float32)),
            cnt_match=jnp.zeros((t, n), jnp.float32),
            cnt_total=jnp.zeros(t, jnp.float32),
            # distinct buffers: the scan donates the carry, and donating one
            # buffer aliased into several fields is invalid
            cnt_own_anti=jnp.zeros((ti, n), jnp.float32),
            cnt_own_aff=jnp.zeros((ti, n), jnp.float32),
            w_own_aff_pref=jnp.zeros((ti, n), jnp.float32),
            w_own_anti_pref=jnp.zeros((ti, n), jnp.float32),
            vg_free=jnp.asarray((ext.vg_cap - ext.vg_req0).astype(np.float32)),
            sdev_free=jnp.asarray((ext.sdev_cap > 0) & ~ext.sdev_alloc0),
            gpu_free=jnp.asarray(ext.gpu_dev_total.astype(np.float32)),
            ports_used=jnp.zeros((n, tensors.n_ports), jnp.float32),
            vols_any=jnp.zeros((n, tensors.n_vols), jnp.float32),
            vols_rw=jnp.zeros((n, tensors.n_vols), jnp.float32),
        )
    free = tensors.alloc.astype(np.float32).copy()
    vg_free = (ext.vg_cap - ext.vg_req0).astype(np.float32)
    sdev_free = (ext.sdev_cap > 0) & ~ext.sdev_alloc0
    gpu_free = ext.gpu_dev_total.astype(np.float32).copy()
    if placed_ext and len(placed_ext.get("node", ())):
        pn = np.asarray(placed_ext["node"], np.int32)
        _add_at_rows(vg_free, pn, -np.asarray(placed_ext["vg_alloc"], np.float32))
        np.minimum.at(
            sdev_free, pn, ~np.asarray(placed_ext["sdev_take"], bool)
        )
        _add_at_rows(
            gpu_free,
            pn,
            -np.asarray(placed_ext["gpu_shares"], np.float32)
            * np.asarray(placed_ext["gpu_mem"], np.float32)[:, None],
        )
    ports_used = np.zeros((n, tensors.n_ports), np.float32)
    if len(placed_group) and tensors.n_ports:
        _add_at_rows(
            ports_used,
            placed_node,
            tensors.ports[placed_group].astype(np.float32),
        )
    vols_any = np.zeros((n, tensors.n_vols), np.float32)
    vols_rw = np.zeros((n, tensors.n_vols), np.float32)
    if len(placed_group) and tensors.n_vols:
        rw = tensors.vol_rw[placed_group]
        present = rw | tensors.vol_ro[placed_group] | tensors.vol_att[placed_group]
        _add_at_rows(vols_any, placed_node, present.astype(np.float32))
        _add_at_rows(vols_rw, placed_node, rw.astype(np.float32))
    if len(placed_group):
        req = placed_req
        if req.shape[1] < r:  # resource vocab grew after this pod was logged
            req = np.pad(req, ((0, 0), (0, r - req.shape[1])))
        _add_at_rows(free, placed_node, -req)
    # Topology counts rebuild via group-level aggregation — the count of
    # term t in node n's domain is Σ_g incid[g, t] · (placements of group g
    # in that domain), so ONE [P]-length (group, node) scatter plus a
    # per-domain segment sum per topology key replaces any per-placement
    # per-term work (the previous [P, T] formulation allocated tens of GB
    # at million-pod log sizes). Per-term rows then accumulate over the
    # sparse (group, term) incidence pairs.
    ip_terms = np.flatnonzero(ip_of >= 0)  # ascending = plane row order
    cnt_match = np.zeros((t, n), np.float32)
    own_n = np.zeros((4, len(ip_terms), n), np.float32)
    cnt_total = np.zeros(t, np.float32)
    if t and len(placed_group):
        g_n = len(tensors.groups)
        term_topo = tensors.term_topo_key
        key_valid = tensors.node_dom >= 0  # [K, N]
        # one [P]-length scatter via bincount (np.add.at's buffered path is
        # ~10x slower at million-entry logs)
        flat = placed_group.astype(np.int64) * n + placed_node
        cnt_gn = (
            np.bincount(flat, minlength=g_n * n)
            .reshape(g_n, n)
            .astype(np.float32)
        )
        # per-key [D, G] domain aggregates and cached safe domain indices
        # (rows without the key carry 0)
        cnt_dg, safe_k = {}, {}
        for k in {int(x) for x in term_topo[:t]}:
            safe_k[k] = np.where(key_valid[k], tensors.node_dom[k], 0)
            src = np.where(key_valid[k][None, :], cnt_gn, 0.0).T.copy()  # [N, G]
            buf = np.zeros((d, g_n), np.float32)
            _add_at_rows(buf, safe_k[k], src)
            cnt_dg[k] = buf
        tot_kg = {k: buf.sum(axis=0) for k, buf in cnt_dg.items()}

        row_cache = {}  # (key, group) → expanded [N] domain-count row

        def group_row(k, g_i):
            got = row_cache.get((k, g_i))
            if got is None:
                got = np.where(key_valid[k], cnt_dg[k][safe_k[k], g_i], 0.0)
                row_cache[(k, g_i)] = got
            return got

        def fill_rows(dst, term_ids, incid, totals=None):
            """dst[i] += Σ_g incid[g, term_ids[i]] · domain-count row of g;
            `totals` accumulates the per-term cluster-wide sum in the same
            pass over the sparse incidence pairs. Rows are cached per
            (topology key, group) — one group commonly matches many terms
            sharing a key (SelectorSpread interns several per controller)."""
            sub = incid if term_ids is None else incid[:, term_ids]
            for g_i, t_i in zip(*np.nonzero(sub)):
                tid = t_i if term_ids is None else term_ids[t_i]
                k = int(term_topo[tid])
                w = float(sub[g_i, t_i])
                dst[t_i] += w * group_row(k, g_i)
                if totals is not None:
                    totals[tid] += w * tot_kg[k][g_i]

        fill_rows(cnt_match, None, tensors.s_match, totals=cnt_total)
        for s_i, mat in enumerate(
            (
                tensors.a_anti_req,
                tensors.a_aff_req,
                tensors.w_aff_pref,
                tensors.w_anti_pref,
            )
        ):
            fill_rows(own_n[s_i], ip_terms, mat)
    return SchedState(
        free=jnp.asarray(free),
        cnt_match=jnp.asarray(cnt_match),
        cnt_total=jnp.asarray(cnt_total),
        cnt_own_anti=jnp.asarray(own_n[0]),
        cnt_own_aff=jnp.asarray(own_n[1]),
        w_own_aff_pref=jnp.asarray(own_n[2]),
        w_own_anti_pref=jnp.asarray(own_n[3]),
        vg_free=jnp.asarray(vg_free),
        sdev_free=jnp.asarray(sdev_free),
        gpu_free=jnp.asarray(gpu_free),
        ports_used=jnp.asarray(ports_used),
        vols_any=jnp.asarray(vols_any),
        vols_rw=jnp.asarray(vols_rw),
    )


# -- batch apply / undo of placement deltas ----------------------------------
#
# The functional analog of the scheduler cache's AddPod/RemovePod pair
# (`internal/cache/cache.go`): one compiled scan folds a batch of signed
# placement-log entries into the carried state — sign +1 re-places, -1
# evicts, 0 is a padding no-op — without rebuilding the state from the full
# log.  Drives incremental preemption (Engine._apply_saved_delta applies an
# eviction and its undo as the same call with opposite signs) and any other
# consumer that needs to roll a batch of placements forward or back.


def placement_delta_step(statics, state: SchedState, entry):
    """Apply one placement-log entry to the state with weight w (+1 =
    re-place, -1 = evict): exactly `schedule_step`'s state-update block,
    without filters or node choice. Drives incremental preemption — a full
    build_state from a million-entry log per eviction costs more than the
    whole preemption."""
    g, node, w, req, vg_alloc, sdev_take, gpu_vec = entry
    safe = jnp.clip(node, 0)
    updates = {"free": state.free.at[safe].add(-req * w)}
    if state.ports_used.shape[1]:
        updates["ports_used"] = state.ports_used.at[safe].add(
            statics.ports_req[g] * w
        )
    if state.vols_any.shape[1]:
        v_rw = statics.vol_rw_req[g]
        v_present = v_rw | statics.vol_ro_req[g] | statics.vol_att_req[g]
        updates["vols_any"] = state.vols_any.at[safe].add(v_present * w)
        updates["vols_rw"] = state.vols_rw.at[safe].add(v_rw * w)
    if state.vg_free.shape[1]:
        updates["vg_free"] = state.vg_free.at[safe].add(-vg_alloc * w)
    if state.sdev_free.shape[1]:
        # boolean devices: w>0 consumes (clear), w<0 releases (set)
        row = state.sdev_free[safe]
        row = jnp.where(w > 0, row & ~sdev_take, row | sdev_take)
        updates["sdev_free"] = state.sdev_free.at[safe].set(row)
    if state.gpu_free.shape[1]:
        updates["gpu_free"] = state.gpu_free.at[safe].add(-gpu_vec * w)
    t_cap = statics.g_terms.shape[1]
    if t_cap:
        terms_g = statics.g_terms[g]
        tvalid = terms_g >= 0
        tsafe = jnp.clip(terms_g, 0)
        dom_sub = take_rows_i32(
            statics.node_dom, jnp.where(tvalid, statics.term_topo[tsafe], -1)
        )
        valid_sub = (dom_sub >= 0) & tvalid[:, None]
        dom_chosen = dom_sub[:, safe]
        valid_chosen = (dom_chosen >= 0) & tvalid
        same = valid_sub & (dom_sub == dom_chosen[:, None]) & valid_chosen[:, None]
        inc = jnp.where(same, w, 0.0)

        updates["cnt_match"] = add_rows(
            state.cnt_match, terms_g, statics.s_match[g][:, None] * inc
        )
        updates["cnt_total"] = state.cnt_total.at[tsafe].add(
            statics.s_match[g] * jnp.where(valid_chosen, w, 0.0)
        )
        ip_eff = jnp.where(tvalid, statics.ip_of[tsafe], -1)

        def bump_ip(arr, vals):
            return add_rows(arr, ip_eff, vals[:, None] * inc)

        updates["cnt_own_anti"] = bump_ip(
            state.cnt_own_anti, statics.a_anti_req[g].astype(jnp.float32)
        )
        updates["cnt_own_aff"] = bump_ip(
            state.cnt_own_aff, statics.a_aff_req[g].astype(jnp.float32)
        )
        updates["w_own_aff_pref"] = bump_ip(state.w_own_aff_pref, statics.w_aff_pref[g])
        updates["w_own_anti_pref"] = bump_ip(
            state.w_own_anti_pref, statics.w_anti_pref[g]
        )
    return state._replace(**updates), ()


@partial(jax.jit, donate_argnums=(1,))
def apply_placement_deltas(statics, state: SchedState, entries):
    """Scan `placement_delta_step` over padded entry arrays (w = 0 rows are
    no-ops).  Entries with w = -1 undo what the same entries with w = +1
    applied — the batch-apply/undo pair behind preemption's eviction and
    restore paths and the fault sweep's scenario drains
    (simtpu/faults/)."""
    state, _ = jax.lax.scan(partial(placement_delta_step, statics), state, entries)
    return state


def pack_delta_entries(entries, n_resources: int, vg_w: int, sd_w: int, gd_w: int,
                       sign: float, pad_to: int = None):
    """Padded entry arrays for `apply_placement_deltas` from saved
    placement-log records in `Engine.remove_placements`' layout
    ((g, node, req, ext_node, vg_alloc, sdev_take, gpu_shares, gpu_mem) per
    entry).  Rows beyond len(entries) carry w = 0 and are exact no-ops
    through `placement_delta_step`; `pad_to` overrides the default
    pow2-bounded padding (the fault sweep pads every scenario of a batch
    to one shared length so all scenarios compile one executable).  The
    single packing used by the engine's eviction/undo path and the
    scenario sweep — shared code is what keeps their delta arithmetic
    bit-identical."""
    v = len(entries)
    v_pad = pad_to if pad_to is not None else 1 << max(v - 1, 0).bit_length()
    g_a = np.zeros(v_pad, np.int32)
    n_a = np.zeros(v_pad, np.int32)
    w_a = np.zeros(v_pad, np.float32)
    req_a = np.zeros((v_pad, n_resources), np.float32)
    vg_a = np.zeros((v_pad, vg_w), np.float32)
    sd_a = np.zeros((v_pad, sd_w), bool)
    gp_a = np.zeros((v_pad, gd_w), np.float32)
    for i, (g, node, req, _enode, vg, sdev, gpu_sh, gpu_mem) in enumerate(entries):
        g_a[i], n_a[i], w_a[i] = g, node, sign
        req_a[i, : req.shape[0]] = req
        vg_a[i] = vg
        sd_a[i] = sdev
        gp_a[i] = np.asarray(gpu_sh) * gpu_mem
    return (g_a, n_a, w_a, req_a, vg_a, sd_a, gp_a)


# -- append-only vocabulary growth (warm-engine serving) ---------------------
#
# Between place() calls the pod/term vocabulary only ever APPENDS (Interners
# never reassign ids), so a carried state can follow a grown vocabulary with
# a device-side extension instead of the O(P·T) host rebuild build_state
# performs: new term rows are computed host-side from the SAME group-level
# aggregation build_state uses (bit-identity by shared math — counts are
# integer-valued f32, and per-row contributions accumulate over the sparse
# (group, term) pairs in the same ascending-group order), the compacted
# interpod planes are re-laid-out by one gather (an old term newly marked
# interpod-used INSERTS a row mid-plane; its values are zero — only groups
# interned after the mark own it, and they have no placements yet), and
# everything else passes through.
#
# To bound recompiles, a grow-mode engine carries its term axes PRE-PADDED
# to pow2 shape buckets: cnt_match/cnt_total live at [T_cap, N]/[T_cap] and
# the own planes at [Ti_cap, N] with zero rows above the live watermark.
# Every consumer addresses term rows by id (< T), so padding rows are never
# read or written — dispatch executables, the delta apply/undo path and the
# chunked scan all key on the BUCKET shape and stay warm while the
# vocabulary grows within it.  Growth events trace `_extend_terms_kernel`
# once per (old bucket, new bucket, appended-row bucket) signature — the
# `compile.grow` trace-once-per-bucket contract (tests/test_grow.py).
# Grow-mode carries stay dense (compression re-derives its plan from the
# tensors' exact term partition and would re-trace per vocabulary size).


def snap_pow2(x: int, floor: int = 1) -> int:
    """Next power of two ≥ x (at least `floor`) — the shape-bucket snap for
    grow-mode carried planes and appended-row batches."""
    return max(floor, 1 << max(int(x) - 1, 0).bit_length())


#: counter names surfaced in the `engine.grow` response/CLI block — the
#: registry family tests/test_grow.py and `make bench-grow` pin.  Lives
#: here (not in simtpu.serve) so `apply --json` can report it without
#: importing the daemon (the off-path zero-cost pin, tests/test_serve.py).
GROW_COUNTERS = (
    "grow.extends",
    "grow.bucket_promotions",
    "grow.node_extends",
    "grow.rebuilds",
    "grow.retensorize_fallbacks",
    "compile.grow",
)


def grow_counters_doc() -> dict:
    """The append-only-growth counter block (process registry — monotone
    across queries, like the `compile.*` family), `grow.` prefix
    stripped.  serve/session.py's `grow_doc` layers the per-session
    warm/bucket fields on top."""
    from ..obs.metrics import REGISTRY

    snap = REGISTRY.snapshot()
    return {
        name.split("grow.", 1)[-1] if name.startswith("grow.") else name:
            int(snap.get(name, 0))
        for name in GROW_COUNTERS
    }


def _count_grow_trace() -> None:
    """Python-side trace counter: executes once per (re)trace of a growth
    kernel, never at run time — the `compile.grow` registry family
    (engine/scan.py COMPILE_COUNT_KINDS)."""
    from ..obs.metrics import REGISTRY

    REGISTRY.counter("compile.grow").inc()


@partial(jax.jit, static_argnums=(0, 1), donate_argnums=(2,))
def _pad_terms_kernel(t_cap: int, ti_cap: int, state: SchedState) -> SchedState:
    """Copy a freshly built exact-shape state into its pow2 term buckets
    (zero rows above the live watermark) — the grow-mode entry copy, once
    per (exact shape, bucket) pair."""
    _count_grow_trace()

    def pad_rows(plane, cap):
        if plane.shape[0] == cap:
            return plane
        return (
            jnp.zeros((cap,) + plane.shape[1:], plane.dtype)
            .at[: plane.shape[0]]
            .set(plane)
        )

    return state._replace(
        cnt_match=pad_rows(state.cnt_match, t_cap),
        cnt_total=pad_rows(state.cnt_total, t_cap),
        cnt_own_anti=pad_rows(state.cnt_own_anti, ti_cap),
        cnt_own_aff=pad_rows(state.cnt_own_aff, ti_cap),
        w_own_aff_pref=pad_rows(state.w_own_aff_pref, ti_cap),
        w_own_anti_pref=pad_rows(state.w_own_anti_pref, ti_cap),
    )


@partial(jax.jit, static_argnums=(5, 6), donate_argnums=(0,))
def _extend_terms_kernel(
    state: SchedState, new_ids, new_rows, new_tot, own_perm,
    t_cap: int, ti_cap: int,
) -> SchedState:
    """Device-side term-axis extension: promote the count planes into the
    target buckets, scatter the host-computed appended term rows (padded
    ids are -1 → masked to zero adds), and re-gather the own planes
    through the new interpod layout (`own_perm[j]` = old row feeding new
    row j, -1 = fresh zero row)."""
    _count_grow_trace()
    cm, ct = state.cnt_match, state.cnt_total
    if cm.shape[0] != t_cap:
        cm = jnp.zeros((t_cap, cm.shape[1]), cm.dtype).at[: cm.shape[0]].set(cm)
        ct = jnp.zeros((t_cap,), ct.dtype).at[: ct.shape[0]].set(ct)
    if new_ids.shape[0]:
        safe = jnp.clip(new_ids, 0)
        live = new_ids >= 0
        cm = cm.at[safe].add(jnp.where(live[:, None], new_rows, 0.0))
        ct = ct.at[safe].add(jnp.where(live, new_tot, 0.0))

    def permute(plane):
        if not ti_cap:
            return plane
        if not plane.shape[0]:
            return jnp.zeros((ti_cap, state.cnt_match.shape[1]), plane.dtype)
        return jnp.where(
            (own_perm >= 0)[:, None],
            plane[jnp.clip(own_perm, 0)],
            jnp.zeros((), plane.dtype),
        )

    return state._replace(
        cnt_match=cm,
        cnt_total=ct,
        cnt_own_anti=permute(state.cnt_own_anti),
        cnt_own_aff=permute(state.cnt_own_aff),
        w_own_aff_pref=permute(state.w_own_aff_pref),
        w_own_anti_pref=permute(state.w_own_anti_pref),
    )


@partial(jax.jit, static_argnums=(7, 8), donate_argnums=(0,))
def _extend_nodes_kernel(
    state: SchedState, free_rows, cnt_cols, own_cols,
    vg_rows, sdev_rows, gpu_rows, n_ports: int, n_vols: int,
) -> SchedState:
    """Device-side node-axis extension: append the new nodes' free/storage
    rows and the host-computed count-plane columns (pods already placed in
    a domain the new node joins are visible from it immediately)."""
    _count_grow_trace()
    a = free_rows.shape[0]

    def cat_cols(plane, cols):
        return jnp.concatenate([plane, cols.astype(plane.dtype)], axis=1)

    return state._replace(
        free=jnp.concatenate([state.free, free_rows]),
        cnt_match=cat_cols(state.cnt_match, cnt_cols),
        cnt_own_anti=cat_cols(state.cnt_own_anti, own_cols[0]),
        cnt_own_aff=cat_cols(state.cnt_own_aff, own_cols[1]),
        w_own_aff_pref=cat_cols(state.w_own_aff_pref, own_cols[2]),
        w_own_anti_pref=cat_cols(state.w_own_anti_pref, own_cols[3]),
        vg_free=jnp.concatenate([state.vg_free, vg_rows]),
        sdev_free=jnp.concatenate([state.sdev_free, sdev_rows]),
        gpu_free=jnp.concatenate([state.gpu_free, gpu_rows]),
        ports_used=jnp.concatenate(
            [state.ports_used, jnp.zeros((a, n_ports), state.ports_used.dtype)]
        ),
        vols_any=jnp.concatenate(
            [state.vols_any, jnp.zeros((a, n_vols), state.vols_any.dtype)]
        ),
        vols_rw=jnp.concatenate(
            [state.vols_rw, jnp.zeros((a, n_vols), state.vols_rw.dtype)]
        ),
    )


def _grow_aggregates(tensors, placed_group, placed_node, keys):
    """The per-key [D, G] domain aggregates build_state derives its count
    rows from, restricted to the topology keys a growth event touches.
    One [P]-length bincount over the log plus a per-key row scatter —
    O(P) instead of build_state's O(P·T)."""
    n = tensors.alloc.shape[0]
    g_n = len(tensors.groups)
    d = tensors.n_domains
    key_valid = tensors.node_dom >= 0
    flat = placed_group.astype(np.int64) * n + placed_node
    cnt_gn = (
        np.bincount(flat, minlength=g_n * n).reshape(g_n, n).astype(np.float32)
    )
    cnt_dg, safe_k = {}, {}
    for k in keys:
        safe_k[k] = np.where(key_valid[k], tensors.node_dom[k], 0)
        src = np.where(key_valid[k][None, :], cnt_gn, 0.0).T.copy()
        buf = np.zeros((d, g_n), np.float32)
        _add_at_rows(buf, safe_k[k], src)
        cnt_dg[k] = buf
    return cnt_dg, safe_k, key_valid


def _term_rows_subset(tensors, placed_group, placed_node, term_ids):
    """Count rows + cluster totals for a SUBSET of terms, by the same
    aggregation build_state runs for all terms (integer-valued counts and
    identical ascending-group accumulation keep the rows bit-identical to
    a from-scratch rebuild's)."""
    n = tensors.alloc.shape[0]
    rows = np.zeros((len(term_ids), n), np.float32)
    tot = np.zeros(len(term_ids), np.float32)
    if not len(placed_group) or not len(term_ids):
        return rows, tot
    term_topo = tensors.term_topo_key
    keys = {int(term_topo[tid]) for tid in term_ids}
    cnt_dg, safe_k, key_valid = _grow_aggregates(
        tensors, placed_group, placed_node, keys
    )
    tot_kg = {k: buf.sum(axis=0) for k, buf in cnt_dg.items()}
    row_cache = {}

    def group_row(k, g_i):
        got = row_cache.get((k, g_i))
        if got is None:
            got = np.where(key_valid[k], cnt_dg[k][safe_k[k], g_i], 0.0)
            row_cache[(k, g_i)] = got
        return got

    sub = tensors.s_match[:, term_ids]
    for g_i, t_i in zip(*np.nonzero(sub)):
        tid = int(term_ids[t_i])
        k = int(term_topo[tid])
        w = float(sub[g_i, t_i])
        rows[t_i] += w * group_row(k, g_i)
        tot[t_i] += w * tot_kg[k][g_i]
    return rows, tot


def _node_cols_subset(tensors, placed_group, placed_node, node_ids):
    """Count-plane COLUMNS for appended nodes: cnt_match [T, a] plus the
    four own planes [Ti, a] evaluated at the new nodes' domains — a pod
    already placed in a zone a clone joins is counted on the clone."""
    t = tensors.n_terms
    ip_of = interpod_term_index(tensors)
    ip_terms = np.flatnonzero(ip_of >= 0)
    a = len(node_ids)
    cnt_cols = np.zeros((t, a), np.float32)
    own_cols = np.zeros((4, len(ip_terms), a), np.float32)
    if not len(placed_group) or not t:
        return cnt_cols, own_cols
    term_topo = tensors.term_topo_key
    keys = {int(x) for x in term_topo[:t]}
    cnt_dg, safe_k, key_valid = _grow_aggregates(
        tensors, placed_group, placed_node, keys
    )
    row_cache = {}

    def group_cols(k, g_i):
        got = row_cache.get((k, g_i))
        if got is None:
            got = np.where(
                key_valid[k][node_ids],
                cnt_dg[k][safe_k[k][node_ids], g_i],
                0.0,
            )
            row_cache[(k, g_i)] = got
        return got

    def fill(dst, term_ids, incid):
        sub = incid if term_ids is None else incid[:, term_ids]
        for g_i, t_i in zip(*np.nonzero(sub)):
            tid = t_i if term_ids is None else term_ids[t_i]
            k = int(term_topo[tid])
            dst[t_i] += float(sub[g_i, t_i]) * group_cols(k, g_i)

    fill(cnt_cols, None, tensors.s_match)
    for s_i, mat in enumerate(
        (
            tensors.a_anti_req,
            tensors.a_aff_req,
            tensors.w_aff_pref,
            tensors.w_anti_pref,
        )
    ):
        fill(own_cols[s_i], ip_terms, mat)
    return cnt_cols, own_cols


def grow_plan_terms(tensors, t_old: int, ip_terms_old, placed_group, placed_node):
    """Host-side plan for a term-axis growth event: appended term rows and
    totals (bucket-padded, ids -1 above the live count), the own-plane
    re-layout gather, and the target buckets.  `ip_terms_old` is the
    ascending term-id layout the carried own planes were built under."""
    t_new = tensors.n_terms
    ip_of = interpod_term_index(tensors)
    ip_terms_new = np.flatnonzero(ip_of >= 0)
    ti_new = len(ip_terms_new)
    m = t_new - t_old
    m_cap = snap_pow2(m) if m else 0
    ids = np.full(m_cap, -1, np.int32)
    rows = np.zeros((m_cap, tensors.alloc.shape[0]), np.float32)
    tot = np.zeros(m_cap, np.float32)
    if m:
        new_ids = np.arange(t_old, t_new, dtype=np.int32)
        ids[:m] = new_ids
        rows[:m], tot[:m] = _term_rows_subset(
            tensors, placed_group, placed_node, new_ids
        )
    ti_cap = snap_pow2(ti_new) if ti_new else 0
    perm = np.full(max(ti_cap, 1), -1, np.int32)[:ti_cap]
    pos_old = {int(tid): i for i, tid in enumerate(np.asarray(ip_terms_old))}
    for j, tid in enumerate(ip_terms_new):
        perm[j] = pos_old.get(int(tid), -1)
    return {
        "ids": ids,
        "rows": rows,
        "tot": tot,
        "perm": perm,
        "t": t_new,
        "ti": ti_new,
        "t_cap": snap_pow2(t_new) if t_new else 0,
        "ti_cap": ti_cap,
        "ip_terms": ip_terms_new,
    }


def extend_state(state: SchedState, plan: dict) -> SchedState:
    """Apply a `grow_plan_terms` plan to a grow-mode carried state — the
    jitted append-only alternative to build_state after a vocabulary
    growth (bit-identity pinned by tests/test_grow.py)."""
    return _extend_terms_kernel(
        state,
        jnp.asarray(plan["ids"]),
        jnp.asarray(plan["rows"]),
        jnp.asarray(plan["tot"]),
        jnp.asarray(plan["perm"]),
        plan["t_cap"],
        plan["ti_cap"],
    )


def grow_plan_nodes(tensors, n_old: int, placed_group, placed_node,
                    t_cap: int, ti_cap: int):
    """Host-side plan for a node-axis growth event (Tensorizer.add_clone_nodes
    appended rows [n_old:]): the new nodes' free/storage rows and the
    count-plane columns at the carried bucket heights."""
    n_new = tensors.alloc.shape[0]
    node_ids = np.arange(n_old, n_new)
    ext = tensors.ext
    cnt_cols, own_cols = _node_cols_subset(
        tensors, placed_group, placed_node, node_ids
    )
    t, ti = cnt_cols.shape[0], own_cols.shape[1]
    cnt_p = np.zeros((t_cap, len(node_ids)), np.float32)
    cnt_p[:t] = cnt_cols
    own_p = np.zeros((4, ti_cap, len(node_ids)), np.float32)
    own_p[:, :ti] = own_cols
    return {
        "free": tensors.alloc[n_old:].astype(np.float32),
        "cnt_cols": cnt_p,
        "own_cols": own_p,
        "vg": (ext.vg_cap[n_old:] - ext.vg_req0[n_old:]).astype(np.float32),
        "sdev": (ext.sdev_cap[n_old:] > 0) & ~ext.sdev_alloc0[n_old:],
        "gpu": ext.gpu_dev_total[n_old:].astype(np.float32),
        "n": n_new,
    }


def extend_state_nodes(state: SchedState, plan: dict, tensors) -> SchedState:
    """Apply a `grow_plan_nodes` plan: one jitted concatenate per plane."""
    return _extend_nodes_kernel(
        state,
        jnp.asarray(plan["free"]),
        jnp.asarray(plan["cnt_cols"]),
        jnp.asarray(plan["own_cols"]),
        jnp.asarray(plan["vg"]),
        jnp.asarray(plan["sdev"]),
        jnp.asarray(plan["gpu"]),
        tensors.n_ports,
        tensors.n_vols,
    )


def strip_term_padding(state: SchedState, t: int, ti: int) -> SchedState:
    """Exact-shape dense view of a grow-mode (bucket-padded) carry — what
    carried_state() hands to consumers expecting [T, N]/[Ti, N] planes."""
    if state.cnt_match.shape[0] == t and state.cnt_own_anti.shape[0] == ti:
        return state
    return state._replace(
        cnt_match=state.cnt_match[:t],
        cnt_total=state.cnt_total[:t],
        cnt_own_anti=state.cnt_own_anti[:ti],
        cnt_own_aff=state.cnt_own_aff[:ti],
        w_own_aff_pref=state.w_own_aff_pref[:ti],
        w_own_anti_pref=state.w_own_anti_pref[:ti],
    )


# -- compact carried state ---------------------------------------------------
#
# The carried count planes are [T, N] / [Ti, N] dense float32, but for every
# topology key with a small domain cardinality (key_kind == 1: zone / rack /
# region-sized keys, ≤ DOM_SMALL compact ids in node_dom_small) the per-node
# value is CONSTANT within a domain — cnt[t, n] is "matching pods in node n's
# domain", the same number for every node of the domain and 0 where the key
# is absent.  Those rows carry D_key ≤ DOM_SMALL numbers of information in N
# floats.  Between dispatches the state therefore travels in a domain-TABULAR
# form (CompactState): kind-1 term rows as [Rt, D] histograms indexed by
# node_dom_small, dense [N] rows only for unique-per-node keys (kind 2,
# where the row IS the information) and the scatter fallback (kind 0), with
# integer planes narrowed to COUNT_DTYPE (the conversion boundary documented
# in core/tensorize.py).  Expansion back to per-node form is ONE gather
# inside a jitted kernel (expand_state), so every filter/score/tie-break
# consumer sees bit-identical float32 planes; compression is a gather of one
# representative node per (key, domain) — no reduction, hence exact by
# construction (pinned by tests/test_compact.py round trips).
#
# SIMTPU_COMPACT=0 flips the engines back to carrying dense SchedState
# between dispatches — placements are bit-identical either way; the switch
# exists for A/B measurement (bench.py `state_bytes` / `make bench-layout`).


def compact_enabled() -> bool:
    """Default for Engine.compact: SIMTPU_COMPACT=0 disables the compact
    carried-state layout (1/unset = on)."""
    return os.environ.get("SIMTPU_COMPACT", "1") != "0"


class CompactSpecDev(NamedTuple):
    """Device-resident index arrays driving compress/expand (constant per
    tensors; memoized alongside the host spec)."""

    t_tab: jnp.ndarray  # [Rt] cnt_match rows with a kind-1 (tabular) key
    t_dense: jnp.ndarray  # [Rd] the rest (kind 0/2) — Rt + Rd == T
    t_keys: jnp.ndarray  # [Rt] topology key per tabular row
    t_rep: jnp.ndarray  # [Rt, D] representative node per domain (-1 none)
    ip_tab: jnp.ndarray  # [Rti] interpod-plane rows with a kind-1 key
    ip_dense: jnp.ndarray  # [Rdi] — Rti + Rdi == Ti
    ip_keys: jnp.ndarray  # [Rti]
    ip_rep: jnp.ndarray  # [Rti, D]


class CompactSpec(NamedTuple):
    """Host-side compaction plan for one frozen tensors object."""

    enabled: bool  # any tabular row exists (else carry dense SchedState)
    d: int  # histogram width (max small-domain count over kind-1 keys)
    dev: Optional[CompactSpecDev]


def compact_spec(tensors) -> CompactSpec:
    """The (memoized) compaction plan: partition the cnt_match and interpod
    plane rows by their topology key's reduction kind, and precompute one
    representative node per (kind-1 key, domain) for the exact
    representative-gather compression."""
    cached = getattr(tensors, "_compact_spec_cache", None)
    if cached is not None:
        return cached
    t = int(tensors.n_terms)
    kinds = (
        tensors.key_kind
        if tensors.key_kind is not None
        else np.zeros(0, np.int32)
    )
    nds = tensors.node_dom_small
    if not t or not kinds.shape[0]:
        spec = CompactSpec(False, 1, None)
        object.__setattr__(tensors, "_compact_spec_cache", spec)
        return spec
    term_keys = np.asarray(tensors.term_topo_key[:t], np.int32)
    tab_mask = kinds[term_keys] == 1
    t_tab = np.flatnonzero(tab_mask).astype(np.int32)
    t_dense = np.flatnonzero(~tab_mask).astype(np.int32)
    if not len(t_tab):
        spec = CompactSpec(False, 1, None)
        object.__setattr__(tensors, "_compact_spec_cache", spec)
        return spec
    d = 1
    for k in np.unique(term_keys[tab_mask]):
        d = max(d, int(nds[k].max(initial=-1)) + 1)
    # representative node per (key, small domain): the FIRST node carrying
    # the domain id — compression gathers the plane at it, which is exact
    # because kind-1 rows are domain-constant (the class invariant every
    # state update preserves; see the module comment)
    rep = np.full((kinds.shape[0], d), -1, np.int32)
    for k in range(kinds.shape[0]):
        if kinds[k] != 1:
            continue
        ids = nds[k]
        valid = np.flatnonzero(ids >= 0)
        rep[k, ids[valid][::-1]] = valid[::-1].astype(np.int32)
    ip_of = interpod_term_index(tensors)
    ip_terms = np.flatnonzero(ip_of >= 0)  # ascending = plane row order
    ip_tabm = tab_mask[ip_terms]
    ip_tab = np.flatnonzero(ip_tabm).astype(np.int32)
    ip_dense = np.flatnonzero(~ip_tabm).astype(np.int32)
    t_keys = term_keys[t_tab]
    ip_keys = term_keys[ip_terms[ip_tab]]
    dev = CompactSpecDev(
        t_tab=jnp.asarray(t_tab),
        t_dense=jnp.asarray(t_dense),
        t_keys=jnp.asarray(t_keys),
        t_rep=jnp.asarray(rep[t_keys]),
        ip_tab=jnp.asarray(ip_tab),
        ip_dense=jnp.asarray(ip_dense),
        ip_keys=jnp.asarray(ip_keys),
        ip_rep=jnp.asarray(rep[ip_keys]),
    )
    spec = CompactSpec(True, d, dev)
    object.__setattr__(tensors, "_compact_spec_cache", spec)
    return spec


def node_dom_small_for(tensors, n: int) -> jnp.ndarray:
    """tensors.node_dom_small as a device array whose node axis is padded to
    `n` with -1 (absent) — the sharded engines carry a shard-padded state,
    and padded (dead) nodes must expand to 0 exactly like key-less nodes.
    Memoized per width on the tensors object."""
    cache = getattr(tensors, "_nds_pad_cache", None)
    if cache is None:
        cache = {}
        object.__setattr__(tensors, "_nds_pad_cache", cache)
    got = cache.get(n)
    if got is None:
        nds = np.asarray(tensors.node_dom_small, np.int32)
        pad = n - nds.shape[1]
        if pad:
            nds = np.pad(nds, ((0, 0), (0, pad)), constant_values=-1)
        got = cache[n] = jnp.asarray(nds)
    return got


class CompactState(NamedTuple):
    """SchedState's between-dispatch form: domain-tabular count planes,
    COUNT_DTYPE integers, bool masks (see the section comment).  Field
    pairs (`*_tab`, `*_dense`) partition the corresponding SchedState
    plane's rows; continuous planes (free / vg_free / gpu_free) ride along
    unchanged."""

    free: jnp.ndarray  # [N, R] f32
    cm_tab: jnp.ndarray  # [Rt, D] cnt_match tabular rows
    cm_dense: jnp.ndarray  # [Rd, N] cnt_match dense rows
    cnt_total: jnp.ndarray  # [T]
    oa_tab: jnp.ndarray  # cnt_own_anti
    oa_dense: jnp.ndarray
    of_tab: jnp.ndarray  # cnt_own_aff
    of_dense: jnp.ndarray
    wa_tab: jnp.ndarray  # w_own_aff_pref
    wa_dense: jnp.ndarray
    wn_tab: jnp.ndarray  # w_own_anti_pref
    wn_dense: jnp.ndarray
    vg_free: jnp.ndarray  # [N, V] f32
    sdev_free: jnp.ndarray  # [N, SD] bool
    gpu_free: jnp.ndarray  # [N, GD] f32
    ports_used: jnp.ndarray  # [N, P]
    vols_any: jnp.ndarray  # [N, W]
    vols_rw: jnp.ndarray  # [N, W]


def _compress_rows(full, ids_tab, ids_dense, rep):
    """Split one [rows, N] plane into its ([Rt, D] histogram, [Rd, N] dense)
    carried pair.  The histogram is a representative-node GATHER (domains
    without a node read 0), not a reduction — exact for domain-constant
    rows by construction."""
    tab = jnp.take_along_axis(full[ids_tab], jnp.clip(rep, 0), axis=1)
    tab = jnp.where(rep >= 0, tab, 0.0)
    return tab.astype(COUNT_DTYPE), full[ids_dense].astype(COUNT_DTYPE)


def _expand_rows(tab, dense, ids_tab, ids_dense, keys, nds):
    """Rebuild the [rows, N] float32 plane: one gather of each histogram row
    through the key's node_dom_small ids, dense rows cast back.  Integer-
    valued casts both ways — bit-identical to never having compressed."""
    rows = tab.shape[0] + dense.shape[0]
    n = nds.shape[1]
    full = jnp.zeros((rows, n), jnp.float32)
    if tab.shape[0]:
        idx = nds[keys]  # [Rt, N]
        vals = jnp.take_along_axis(
            tab.astype(jnp.float32), jnp.clip(idx, 0), axis=1
        )
        full = full.at[ids_tab].set(jnp.where(idx >= 0, vals, 0.0))
    if dense.shape[0]:
        full = full.at[ids_dense].set(dense.astype(jnp.float32))
    return full


def _compress_state_fn(spec: CompactSpecDev, state: SchedState) -> CompactState:
    cm_tab, cm_dense = _compress_rows(
        state.cnt_match, spec.t_tab, spec.t_dense, spec.t_rep
    )
    oa = _compress_rows(state.cnt_own_anti, spec.ip_tab, spec.ip_dense, spec.ip_rep)
    of = _compress_rows(state.cnt_own_aff, spec.ip_tab, spec.ip_dense, spec.ip_rep)
    wa = _compress_rows(
        state.w_own_aff_pref, spec.ip_tab, spec.ip_dense, spec.ip_rep
    )
    wn = _compress_rows(
        state.w_own_anti_pref, spec.ip_tab, spec.ip_dense, spec.ip_rep
    )
    return CompactState(
        free=state.free,
        cm_tab=cm_tab,
        cm_dense=cm_dense,
        cnt_total=state.cnt_total.astype(COUNT_DTYPE),
        oa_tab=oa[0],
        oa_dense=oa[1],
        of_tab=of[0],
        of_dense=of[1],
        wa_tab=wa[0],
        wa_dense=wa[1],
        wn_tab=wn[0],
        wn_dense=wn[1],
        vg_free=state.vg_free,
        sdev_free=state.sdev_free.astype(MASK_DTYPE),
        gpu_free=state.gpu_free,
        ports_used=state.ports_used.astype(COUNT_DTYPE),
        vols_any=state.vols_any.astype(COUNT_DTYPE),
        vols_rw=state.vols_rw.astype(COUNT_DTYPE),
    )


def _expand_state_fn(
    spec: CompactSpecDev, cstate: CompactState, nds: jnp.ndarray
) -> SchedState:
    return SchedState(
        free=cstate.free,
        cnt_match=_expand_rows(
            cstate.cm_tab, cstate.cm_dense, spec.t_tab, spec.t_dense,
            spec.t_keys, nds,
        ),
        cnt_total=cstate.cnt_total.astype(jnp.float32),
        cnt_own_anti=_expand_rows(
            cstate.oa_tab, cstate.oa_dense, spec.ip_tab, spec.ip_dense,
            spec.ip_keys, nds,
        ),
        cnt_own_aff=_expand_rows(
            cstate.of_tab, cstate.of_dense, spec.ip_tab, spec.ip_dense,
            spec.ip_keys, nds,
        ),
        w_own_aff_pref=_expand_rows(
            cstate.wa_tab, cstate.wa_dense, spec.ip_tab, spec.ip_dense,
            spec.ip_keys, nds,
        ),
        w_own_anti_pref=_expand_rows(
            cstate.wn_tab, cstate.wn_dense, spec.ip_tab, spec.ip_dense,
            spec.ip_keys, nds,
        ),
        vg_free=cstate.vg_free,
        sdev_free=cstate.sdev_free,
        gpu_free=cstate.gpu_free,
        ports_used=cstate.ports_used.astype(jnp.float32),
        vols_any=cstate.vols_any.astype(jnp.float32),
        vols_rw=cstate.vols_rw.astype(jnp.float32),
    )


# Donation audit (docs/memory.md): neither conversion donates.  Compression
# CANNOT reuse the dense buffers it consumes — every narrowed plane changes
# dtype (f32 → COUNT_DTYPE), which XLA refuses to alias, so donate_argnums
# would only emit the donated-buffers-unusable warning (the dense planes are
# still freed at last use; the pass-through planes alias into the output
# with or without donation).  Expansion must not donate because the compact
# carry is routinely shared: the incremental planner copies one snapshot per
# probe and the fault sweep reads the engine's carry without owning it.
compress_state = jax.jit(_compress_state_fn)
expand_state = jax.jit(_expand_state_fn)


# -- direct compact-delta apply ----------------------------------------------
#
# Preemption's evict/restore pairs, timeline departure batches and fault
# drains replay small packed delta batches against the carried state.  With
# a compact carry the naive route is expand ([T, N] floats) → dense delta
# scan → recompress — three full-plane passes to move a handful of counts.
# The delta is instead applied STRAIGHT to the compact form: kind-1 term
# rows are domain-constant, so the dense update (add w on every node of the
# chosen node's domain) collapses to ONE histogram bucket add at
# [row, node_dom_small[key, node]]; dense (kind 0/2) rows and the continuous
# planes take the same per-row updates placement_delta_step issues, routed
# through the inverse row maps below.  Exact under the domain-constancy
# invariant compression already relies on, and exact in integer arithmetic:
# every count delta is an integer-valued f32, so accumulating in COUNT_DTYPE
# equals the dense f32 accumulate + truncating compress cast (pinned
# bit-identical against the expand→apply→recompress route by
# tests/test_state_deltas.py / tests/test_compact.py).


def node_dom_for(tensors, n: int) -> jnp.ndarray:
    """tensors.node_dom as a device array whose node axis is padded to `n`
    with -1 (absent), the full-domain-id companion of node_dom_small_for —
    the direct delta path gathers both maps at the chosen node, and sharded
    engines hand it a shard-padded carry width.  Memoized per width."""
    cache = getattr(tensors, "_ndom_pad_cache", None)
    if cache is None:
        cache = {}
        object.__setattr__(tensors, "_ndom_pad_cache", cache)
    got = cache.get(n)
    if got is None:
        ndom = np.asarray(tensors.node_dom, np.int32)
        if not ndom.shape[0]:
            ndom = np.full((1, ndom.shape[1]), -1, np.int32)
        pad = n - ndom.shape[1]
        if pad:
            ndom = np.pad(ndom, ((0, 0), (0, pad)), constant_values=-1)
        got = cache[n] = jnp.asarray(ndom)
    return got


class CompactDeltaSpec(NamedTuple):
    """Inverse row maps for scattering deltas into a CompactState: term axis
    → carried-plane row (or -1 when the term has no row on that plane).
    Device-resident, constant per tensors (memoized)."""

    t_tab_of: jnp.ndarray  # [T] → row in cm_tab, -1 if dense
    t_dense_of: jnp.ndarray  # [T] → row in cm_dense, -1 if tabular
    ip_tab_of: jnp.ndarray  # [Ti] → row in the *_tab interpod planes
    ip_dense_of: jnp.ndarray  # [Ti] → row in the *_dense interpod planes


def compact_delta_spec(tensors) -> CompactDeltaSpec:
    """The (memoized) inverse of compact_spec's row partition — built once
    host-side from the same t_tab/t_dense/ip_tab/ip_dense orderings so
    scatter targets agree with compression's row layout by construction."""
    cached = getattr(tensors, "_compact_delta_spec_cache", None)
    if cached is not None:
        return cached
    dev = compact_spec(tensors).dev
    t = int(tensors.n_terms)

    def inverse(ids, size):
        ids = np.asarray(ids, np.int32)
        of = np.full(size, -1, np.int32)
        of[ids] = np.arange(len(ids), dtype=np.int32)
        return jnp.asarray(of)

    ti = int(len(np.asarray(dev.ip_tab)) + len(np.asarray(dev.ip_dense)))
    spec = CompactDeltaSpec(
        t_tab_of=inverse(dev.t_tab, t),
        t_dense_of=inverse(dev.t_dense, t),
        ip_tab_of=inverse(dev.ip_tab, ti),
        ip_dense_of=inverse(dev.ip_dense, ti),
    )
    object.__setattr__(tensors, "_compact_delta_spec_cache", spec)
    return spec


def _scatter_rows_add(plane, rows, delta):
    """plane.at[rows].add(delta) with -1 rows masked to no-ops, casting the
    integer-valued f32 delta to the plane's dtype (exact below 2^24)."""
    return plane.at[jnp.clip(rows, 0)].add(
        jnp.where((rows >= 0)[:, None], delta, 0.0).astype(plane.dtype)
    )


def compact_delta_step(statics, dspec, ndom, nds, cstate: CompactState, entry):
    """placement_delta_step retargeted at the compact carry: identical
    continuous-plane updates (cast to the narrowed dtypes), topology counts
    as single-bucket histogram adds for tabular rows and [Rd, N]-row
    scatters for dense rows.  `ndom`/`nds` are the node_dom / node_dom_small
    maps at the CARRY's node width (shard-padded when the engine pads)."""
    g, node, w, req, vg_alloc, sdev_take, gpu_vec = entry
    safe = jnp.clip(node, 0)
    cd = COUNT_DTYPE
    updates = {"free": cstate.free.at[safe].add(-req * w)}
    if cstate.ports_used.shape[1]:
        updates["ports_used"] = cstate.ports_used.at[safe].add(
            (statics.ports_req[g] * w).astype(cd)
        )
    if cstate.vols_any.shape[1]:
        v_rw = statics.vol_rw_req[g]
        v_present = v_rw | statics.vol_ro_req[g] | statics.vol_att_req[g]
        updates["vols_any"] = cstate.vols_any.at[safe].add(
            (v_present * w).astype(cd)
        )
        updates["vols_rw"] = cstate.vols_rw.at[safe].add((v_rw * w).astype(cd))
    if cstate.vg_free.shape[1]:
        updates["vg_free"] = cstate.vg_free.at[safe].add(-vg_alloc * w)
    if cstate.sdev_free.shape[1]:
        row = cstate.sdev_free[safe]
        row = jnp.where(w > 0, row & ~sdev_take, row | sdev_take)
        updates["sdev_free"] = cstate.sdev_free.at[safe].set(row)
    if cstate.gpu_free.shape[1]:
        updates["gpu_free"] = cstate.gpu_free.at[safe].add(-gpu_vec * w)
    t_cap = statics.g_terms.shape[1]
    if t_cap:
        terms_g = statics.g_terms[g]
        tvalid = terms_g >= 0
        tsafe = jnp.clip(terms_g, 0)
        keys = jnp.clip(jnp.where(tvalid, statics.term_topo[tsafe], 0), 0)
        # domain of the chosen node under each term's key, in both the full
        # (node_dom) and small (node_dom_small) numbering — they agree on
        # validity, and the small id IS the histogram bucket
        dom_ch = jnp.where(tvalid, ndom[keys, safe], -1)
        ds_ch = jnp.where(tvalid, nds[keys, safe], -1)
        valid_ch = dom_ch >= 0
        s_val = statics.s_match[g] * jnp.where(valid_ch, w, 0.0)
        updates["cnt_total"] = cstate.cnt_total.at[tsafe].add(s_val.astype(cd))
        # tabular rows: the dense update adds the same value on every node
        # of the chosen domain, and compression gathers one representative —
        # so the whole row update is one bucket add at the small domain id
        t_row = jnp.where(tvalid, dspec.t_tab_of[tsafe], -1)
        tab_ok = (t_row >= 0) & (ds_ch >= 0) & valid_ch
        updates["cm_tab"] = cstate.cm_tab.at[
            jnp.clip(t_row, 0), jnp.clip(ds_ch, 0)
        ].add(jnp.where(tab_ok, s_val, 0.0).astype(cd))
        ip_eff = jnp.where(tvalid, statics.ip_of[tsafe], -1)
        wv = jnp.where(valid_ch, w, 0.0)
        ip_vals = (
            ("oa_tab", "oa_dense", statics.a_anti_req[g].astype(jnp.float32)),
            ("of_tab", "of_dense", statics.a_aff_req[g].astype(jnp.float32)),
            ("wa_tab", "wa_dense", statics.w_aff_pref[g]),
            ("wn_tab", "wn_dense", statics.w_anti_pref[g]),
        )
        if cstate.oa_tab.shape[0]:
            ip_row = jnp.where(
                ip_eff >= 0, dspec.ip_tab_of[jnp.clip(ip_eff, 0)], -1
            )
            ipt_ok = (ip_row >= 0) & (ds_ch >= 0) & valid_ch
            for tabf, _, vals in ip_vals:
                updates[tabf] = getattr(cstate, tabf).at[
                    jnp.clip(ip_row, 0), jnp.clip(ds_ch, 0)
                ].add(jnp.where(ipt_ok, vals * wv, 0.0).astype(cd))
        if cstate.cm_dense.shape[0] or cstate.oa_dense.shape[0]:
            # dense (kind 0/2) rows keep the per-node same-domain compare —
            # exactly placement_delta_step's, routed to the carried rows
            dom_sub = ndom[keys]  # [Tc, Ncarry]
            same = (
                (dom_sub >= 0)
                & tvalid[:, None]
                & (dom_sub == dom_ch[:, None])
                & valid_ch[:, None]
            )
            inc = jnp.where(same, w, 0.0)
            if cstate.cm_dense.shape[0]:
                d_row = jnp.where(tvalid, dspec.t_dense_of[tsafe], -1)
                updates["cm_dense"] = _scatter_rows_add(
                    cstate.cm_dense, d_row, statics.s_match[g][:, None] * inc
                )
            if cstate.oa_dense.shape[0]:
                ipd_row = jnp.where(
                    ip_eff >= 0, dspec.ip_dense_of[jnp.clip(ip_eff, 0)], -1
                )
                for _, densef, vals in ip_vals:
                    updates[densef] = _scatter_rows_add(
                        getattr(cstate, densef), ipd_row, vals[:, None] * inc
                    )
    return cstate._replace(**updates), ()


def _apply_placement_deltas_compact_fn(statics, dspec, ndom, nds, cstate, entries):
    cstate, _ = jax.lax.scan(
        partial(compact_delta_step, statics, dspec, ndom, nds), cstate, entries
    )
    return cstate


# NON-donating, like compress/expand above: the compact carry is routinely
# shared (the incremental planner hands one snapshot to every probe engine,
# the fault sweep reads the engine's carry without owning it) — donating it
# here would invalidate those aliases.  The copy is of the SMALL form, still
# a large net win over the dense round-trip.
apply_placement_deltas_compact = jax.jit(_apply_placement_deltas_compact_fn)


def ensure_dense(state, tensors):
    """The dense SchedState view of a FREE-STANDING carried state
    (expanding a CompactState through the memoized spec; dense states
    pass through).  For reading an ENGINE's carry use
    `Engine.carried_state()` instead — it enforces the dirty-carry and
    vocabulary-change preconditions this helper, which has no engine to
    consult, cannot."""
    if not isinstance(state, CompactState):
        return state
    spec = compact_spec(tensors)
    return expand_state(
        spec.dev, state, node_dom_small_for(tensors, state.free.shape[0])
    )


def diff_state_planes(a, b) -> list:
    """Names of carried planes whose values differ between two DENSE
    states, each tagged with its max absolute difference (or the shape
    mismatch) — the "differing state planes" witness of a divergence
    diagnostic (simtpu/audit): when a plan fails its audit and the serial
    fallback answers differently, this names WHICH state the diverging
    engine corrupted.  Audit-readable view only: callers hand in
    `Engine.carried_state()` / `build_state` outputs, never raw carries."""
    out = []
    for name, x, y in zip(a._fields, a, b):
        x = np.asarray(x)
        y = np.asarray(y)
        if x.shape != y.shape:
            out.append(f"{name}: shape {x.shape} vs {y.shape}")
        elif x.size and not np.array_equal(x, y):
            delta = np.max(np.abs(x.astype(np.float64) - y.astype(np.float64)))
            out.append(f"{name}: max|d|={float(delta):g}")
    return out


def state_nbytes(state) -> dict:
    """Per-plane byte sizes of a carried state (SchedState or CompactState)
    — shape/dtype arithmetic only, no device sync."""
    return {
        name: int(np.prod(arr.shape)) * np.dtype(arr.dtype).itemsize
        for name, arr in zip(state._fields, state)
    }


# Carried-state byte gauge: refreshed by Engine.place each time it stores a
# carry, read by bench.py (`state_bytes`) and the CLI's --json engine block.
# `dense_bytes` is what the SAME carry costs in the dense layout (the A/B
# denominator); `compact` records which form is stored.  Backing store
# since ISSUE 8: obs metrics registry gauges `state.carried_bytes` /
# `state.dense_bytes` / `state.compact` / `state.planes` — read them via
# `obs.metrics.family("state", STATE_KEYS)` (the legacy `state_gauge()`
# alias view is gone).
STATE_KEYS = ("carried_bytes", "dense_bytes", "compact", "planes")


def update_state_gauge(stored, dense_bytes: int) -> None:
    from ..obs.metrics import REGISTRY

    planes = state_nbytes(stored)
    REGISTRY.gauge("state.carried_bytes").set(sum(planes.values()))
    REGISTRY.gauge("state.dense_bytes").set(int(dense_bytes))
    REGISTRY.gauge("state.compact").set(isinstance(stored, CompactState))
    REGISTRY.gauge("state.planes").set(planes)
