"""Direct coverage for engine/state.py's batch apply/undo of placement
deltas: `apply_placement_deltas` with w = -1 then w = +1 over the same
entries must restore the carry BIT-identically — every plane, including
the topology count planes and the compacted per-key interpod histograms.
(Previously exercised only indirectly through the wavefront tests; the
fault subsystem's scenario drains ride the same arithmetic, ISSUE 4.)
"""

import numpy as np
import pytest

from simtpu.engine.scan import statics_from
from simtpu.engine.state import apply_placement_deltas, pack_delta_entries
from simtpu.faults import place_cluster
from simtpu.synth import synth_apps, synth_cluster


@pytest.fixture(scope="module")
def placed():
    cluster = synth_cluster(
        9, seed=41, zones=3, taint_frac=0.1, gpu_frac=0.3, storage_frac=0.4
    )
    apps = synth_apps(
        48,
        seed=42,
        zones=3,
        pods_per_deployment=8,
        selector_frac=0.2,
        toleration_frac=0.1,
        anti_affinity_frac=0.4,
        anti_affinity_hard_frac=0.5,
        spread_frac=0.3,
        spread_hard_frac=0.5,
        gpu_frac=0.2,
        storage_frac=0.2,
        affinity_frac=0.1,
    )
    return place_cluster(cluster, apps)


def _entries_of(eng, indices):
    """Saved-record tuples in Engine.remove_placements' layout, without
    touching the log."""
    ext = eng.ext_log
    return [
        (
            eng.placed_group[i],
            eng.placed_node[i],
            eng.placed_req[i],
            ext["node"][i],
            ext["vg_alloc"][i],
            ext["sdev_take"][i],
            ext["gpu_shares"][i],
            ext["gpu_mem"][i],
        )
        for i in indices
    ]


class TestApplyUndoRoundTrip:
    def test_apply_then_undo_bit_identical(self, placed):
        """evict (w=-1) then restore (w=+1) over the same entries returns
        every SchedState field bit-identically."""
        import jax
        import jax.numpy as jnp

        eng = placed.engine
        tensors = placed.tensors
        statics = statics_from(tensors, eng.sched_config)
        r = tensors.alloc.shape[1]
        ext = tensors.ext
        base = eng.carried_state()  # dense view of the (compact) carry
        assert base is not None and not eng._state_dirty
        # a mixed batch: every 3rd entry, which spans groups/nodes/extended
        indices = list(range(0, len(eng.placed_node), 3))
        assert len(indices) >= 8
        entries = _entries_of(eng, indices)

        def packed(sign):
            return pack_delta_entries(
                entries,
                r,
                ext.vg_cap.shape[1],
                ext.sdev_cap.shape[1],
                ext.gpu_dev_total.shape[1],
                sign,
            )

        copy = jax.tree_util.tree_map(jnp.copy, base)
        evicted = apply_placement_deltas(statics, copy, packed(-1.0))
        # the eviction must actually change the state
        assert not np.array_equal(
            np.asarray(evicted.free), np.asarray(base.free)
        )
        restored = apply_placement_deltas(statics, evicted, packed(+1.0))
        for name in base._fields:
            got = np.asarray(getattr(restored, name))
            want = np.asarray(getattr(base, name))
            assert got.dtype == want.dtype, name
            assert np.array_equal(got, want), (
                f"state field {name} not bit-identical after apply+undo "
                f"(max delta {np.max(np.abs(got.astype(np.float64) - want.astype(np.float64)))})"
            )

    def test_count_planes_and_histograms_change_under_apply(self, placed):
        """The eviction delta visibly updates the topology count planes and
        the compacted interpod ('own') histograms — the round-trip above
        is not vacuous for them."""
        import jax
        import jax.numpy as jnp

        eng = placed.engine
        tensors = placed.tensors
        statics = statics_from(tensors, eng.sched_config)
        r = tensors.alloc.shape[1]
        ext = tensors.ext
        base = eng.carried_state()
        entries = _entries_of(eng, range(len(eng.placed_node)))
        packed = pack_delta_entries(
            entries,
            r,
            ext.vg_cap.shape[1],
            ext.sdev_cap.shape[1],
            ext.gpu_dev_total.shape[1],
            -1.0,
        )
        copy = jax.tree_util.tree_map(jnp.copy, base)
        evicted = apply_placement_deltas(statics, copy, packed)
        # evicting the WHOLE log zeroes every count plane
        for name in ("cnt_match", "cnt_total", "cnt_own_anti", "cnt_own_aff"):
            before = np.asarray(getattr(base, name))
            after = np.asarray(getattr(evicted, name))
            if before.size and before.any():
                assert not np.array_equal(after, before), name
            assert np.allclose(after, 0.0, atol=1e-5), (
                f"{name} not zeroed by a full-log eviction"
            )

    def test_padding_rows_are_noops(self, placed):
        """w = 0 padding rows leave the state bit-identical (pack_delta_
        entries pads to pow2; the fault sweep pads every scenario)."""
        import jax
        import jax.numpy as jnp

        eng = placed.engine
        tensors = placed.tensors
        statics = statics_from(tensors, eng.sched_config)
        r = tensors.alloc.shape[1]
        ext = tensors.ext
        base = eng.carried_state()
        packed = pack_delta_entries(
            [],
            r,
            ext.vg_cap.shape[1],
            ext.sdev_cap.shape[1],
            ext.gpu_dev_total.shape[1],
            -1.0,
            pad_to=16,
        )
        copy = jax.tree_util.tree_map(jnp.copy, base)
        out = apply_placement_deltas(statics, copy, packed)
        for name in base._fields:
            assert np.array_equal(
                np.asarray(getattr(out, name)), np.asarray(getattr(base, name))
            ), name


class TestInterleavedApplyUndo:
    """Out-of-stack-order apply/undo (ISSUE 15): departures in a timeline
    undo event i after later events j > i applied — the delta-advanced
    carry must stay bit-identical to a state REBUILT from the equivalent
    log, for the dense carry and the compact (domain-tabular) carry."""

    def _packed(self, placed, entries, sign):
        tensors = placed.tensors
        ext = tensors.ext
        return pack_delta_entries(
            entries,
            tensors.alloc.shape[1],
            ext.vg_cap.shape[1],
            ext.sdev_cap.shape[1],
            ext.gpu_dev_total.shape[1],
            sign,
        )

    def _rebuilt(self, placed, keep_mask):
        """build_state over the placement log restricted to `keep_mask`
        entries — the from-scratch oracle of any delta sequence whose net
        effect removes the masked-out entries."""
        import numpy as np

        from simtpu.engine.state import build_state

        eng = placed.engine
        tensors = placed.tensors
        keep = np.flatnonzero(keep_mask)
        r = tensors.alloc.shape[1]
        req = eng.log_req_matrix(r)[keep]
        ext = {
            k: [eng.ext_log[k][int(i)] for i in keep] for k in eng.ext_log
        }
        return build_state(
            tensors,
            np.asarray(eng.placed_group, np.int32)[keep],
            np.asarray(eng.placed_node, np.int32)[keep],
            req,
            ext,
        )

    def _assert_states_equal(self, got, want, label):
        import numpy as np

        for name in want._fields:
            g = np.asarray(getattr(got, name))
            w = np.asarray(getattr(want, name))
            assert g.dtype == w.dtype, (label, name)
            assert np.array_equal(g, w), (
                f"{label}: plane {name} not bit-identical "
                f"(max delta "
                f"{np.max(np.abs(g.astype(np.float64) - w.astype(np.float64)))})"
            )

    def test_undo_i_after_apply_j_matches_rebuild(self, placed):
        """apply -A, apply -B (disjoint, B after A), undo +A — the state
        must equal a rebuild from the log minus B, dense AND compact."""
        import jax
        import jax.numpy as jnp
        import numpy as np

        from simtpu.engine.state import (
            compact_spec,
            compress_state,
            node_dom_small_for,
        )

        eng = placed.engine
        tensors = placed.tensors
        statics = statics_from(tensors, eng.sched_config)
        m = len(eng.placed_node)
        a_idx = list(range(0, m, 4))          # "event i"
        b_idx = list(range(1, m, 4))          # "event j > i", disjoint
        assert len(a_idx) >= 4 and len(b_idx) >= 4
        base = eng.carried_state()
        state = jax.tree_util.tree_map(jnp.copy, base)
        state = apply_placement_deltas(
            statics, state, self._packed(placed, _entries_of(eng, a_idx), -1.0)
        )
        state = apply_placement_deltas(
            statics, state, self._packed(placed, _entries_of(eng, b_idx), -1.0)
        )
        # out-of-stack-order undo: A comes back while B stays evicted
        state = apply_placement_deltas(
            statics, state, self._packed(placed, _entries_of(eng, a_idx), +1.0)
        )
        keep = np.ones(m, bool)
        keep[b_idx] = False
        want = self._rebuilt(placed, keep)
        self._assert_states_equal(state, want, "dense carry")
        spec = compact_spec(tensors)
        if spec.enabled:
            nds = node_dom_small_for(tensors, tensors.alloc.shape[0])
            got_c = compress_state(spec.dev, state)
            want_c = compress_state(spec.dev, want)
            self._assert_states_equal(got_c, want_c, "compact carry")
            # and the compact round trip loses nothing: the delta-advanced
            # state is still in the domain-constant class compression
            # assumes (what the timeline's carried compact state rides on)
            from simtpu.engine.state import expand_state

            back = expand_state(spec.dev, got_c, nds)
            self._assert_states_equal(back, want, "compact round trip")

    def test_full_out_of_order_round_trip(self, placed):
        """apply -A, apply -B, undo +A, undo +B returns to base
        bit-identically (the stack-order test's interleaved sibling)."""
        import jax
        import jax.numpy as jnp

        eng = placed.engine
        tensors = placed.tensors
        statics = statics_from(tensors, eng.sched_config)
        m = len(eng.placed_node)
        a_idx = list(range(0, m, 3))
        b_idx = list(range(1, m, 3))
        base = eng.carried_state()
        state = jax.tree_util.tree_map(jnp.copy, base)
        for idx, sign in ((a_idx, -1.0), (b_idx, -1.0),
                          (a_idx, +1.0), (b_idx, +1.0)):
            state = apply_placement_deltas(
                statics, state, self._packed(placed, _entries_of(eng, idx), sign)
            )
        self._assert_states_equal(state, base, "out-of-order round trip")

    def test_interleaved_apply_after_undo(self, placed):
        """undo (depart) then APPLY the same entries again (a re-admission
        landing on identical nodes) interleaved with another departure —
        the timeline's node-down/requeue shape — equals the rebuild."""
        import jax
        import jax.numpy as jnp
        import numpy as np

        eng = placed.engine
        tensors = placed.tensors
        statics = statics_from(tensors, eng.sched_config)
        m = len(eng.placed_node)
        a_idx = list(range(0, m, 5))
        b_idx = list(range(2, m, 5))
        base = eng.carried_state()
        state = jax.tree_util.tree_map(jnp.copy, base)
        state = apply_placement_deltas(
            statics, state, self._packed(placed, _entries_of(eng, a_idx), -1.0)
        )
        state = apply_placement_deltas(
            statics, state, self._packed(placed, _entries_of(eng, b_idx), -1.0)
        )
        state = apply_placement_deltas(
            statics, state, self._packed(placed, _entries_of(eng, a_idx), +1.0)
        )
        state = apply_placement_deltas(
            statics, state, self._packed(placed, _entries_of(eng, b_idx), +1.0)
        )
        state = apply_placement_deltas(
            statics, state, self._packed(placed, _entries_of(eng, b_idx), -1.0)
        )
        keep = np.ones(m, bool)
        keep[b_idx] = False
        want = self._rebuilt(placed, keep)
        self._assert_states_equal(state, want, "re-admission interleave")


def _pack(placed, entries, sign):
    tensors = placed.tensors
    ext = tensors.ext
    return pack_delta_entries(
        entries,
        tensors.alloc.shape[1],
        ext.vg_cap.shape[1],
        ext.sdev_cap.shape[1],
        ext.gpu_dev_total.shape[1],
        sign,
    )


def _rebuilt_from_log(placed, keep_mask):
    import numpy as np

    from simtpu.engine.state import build_state

    eng = placed.engine
    tensors = placed.tensors
    keep = np.flatnonzero(keep_mask)
    r = tensors.alloc.shape[1]
    req = eng.log_req_matrix(r)[keep]
    ext = {k: [eng.ext_log[k][int(i)] for i in keep] for k in eng.ext_log}
    return build_state(
        tensors,
        np.asarray(eng.placed_group, np.int32)[keep],
        np.asarray(eng.placed_node, np.int32)[keep],
        req,
        ext,
    )


@pytest.fixture(scope="module")
def placed_wide():
    """> DOM_SMALL (64) nodes with hostname-keyed anti-affinity terms: the
    hostname topology key has one value PER NODE, so its rows compress as
    kind-2 DENSE rows — the fixture that exercises compact_delta_step's
    dense-row branch (the 9-node fixture above is all-tabular)."""
    cluster = synth_cluster(
        80, seed=61, zones=4, taint_frac=0.0, gpu_frac=0.2, storage_frac=0.3
    )
    apps = synth_apps(
        40,
        seed=62,
        zones=4,
        pods_per_deployment=6,
        selector_frac=0.1,
        anti_affinity_frac=0.6,
        anti_affinity_hard_frac=0.4,
        spread_frac=0.4,
        spread_hard_frac=0.5,
        gpu_frac=0.1,
        storage_frac=0.2,
    )
    return place_cluster(cluster, apps)


class TestDirectCompactDelta:
    """ISSUE 16 tentpole: packed placement deltas applied DIRECTLY to the
    compact carry (per-domain scatter into the [Rt, D] tabular histograms,
    plain row updates for the dense rows) must be bit-identical to the
    expand -> apply_placement_deltas -> recompress round trip AND to a
    from-scratch build_state rebuild.  Preemption evictions/restores,
    timeline departures and fault drains all replay this arithmetic."""

    def _assert_equal(self, got, want, label):
        for name in want._fields:
            g = np.asarray(getattr(got, name))
            w = np.asarray(getattr(want, name))
            assert g.dtype == w.dtype, (label, name)
            assert np.array_equal(g, w), (
                f"{label}: compact plane {name} not bit-identical "
                f"(max delta "
                f"{np.max(np.abs(g.astype(np.float64) - w.astype(np.float64)))})"
            )

    def _run_interleave(self, placed, expect_dense):
        """-A, -B, +A out of stack order, then +B, -B re-admission churn:
        direct compact apply vs the dense round-trip oracle at every step."""
        import jax
        import jax.numpy as jnp

        from simtpu.engine.state import (
            apply_placement_deltas_compact,
            compact_delta_spec,
            compact_spec,
            compress_state,
            expand_state,
            node_dom_for,
            node_dom_small_for,
        )

        eng = placed.engine
        tensors = placed.tensors
        statics = statics_from(tensors, eng.sched_config)
        spec = compact_spec(tensors)
        assert spec.enabled, "fixture must be compact-eligible"
        n = tensors.alloc.shape[0]
        ndom = node_dom_for(tensors, n)
        nds = node_dom_small_for(tensors, n)
        dspec = compact_delta_spec(tensors)
        base = eng.carried_state()
        direct = compress_state(spec.dev, base)
        if expect_dense:
            assert int(direct.cm_dense.shape[0]) > 0, (
                "wide fixture grew no dense compact rows — the dense "
                "branch of compact_delta_step is not exercised"
            )
        else:
            assert int(direct.cm_dense.shape[0]) == 0
        dense = jax.tree_util.tree_map(jnp.copy, base)
        m = len(eng.placed_node)
        a_idx = list(range(0, m, 4))
        b_idx = list(range(1, m, 4))
        assert len(a_idx) >= 4 and len(b_idx) >= 4
        seq = (
            (a_idx, -1.0),
            (b_idx, -1.0),
            (a_idx, +1.0),  # out-of-stack-order undo
            (b_idx, +1.0),  # re-admission on identical nodes
            (b_idx, -1.0),
        )
        for step, (idx, sign) in enumerate(seq):
            packed = _pack(placed, _entries_of(eng, idx), sign)
            direct = apply_placement_deltas_compact(
                statics, dspec, ndom, nds, direct, packed
            )
            dense = apply_placement_deltas(statics, dense, packed)
            self._assert_equal(
                direct, compress_state(spec.dev, dense), f"step {step}"
            )
        keep = np.ones(m, bool)
        keep[b_idx] = False
        want = _rebuilt_from_log(placed, keep)
        self._assert_equal(
            direct, compress_state(spec.dev, want), "vs build_state rebuild"
        )
        # the direct-advanced compact state expands to the exact dense
        # rebuild: no information was lost to the scatter shortcut
        back = expand_state(spec.dev, direct, nds)
        self._assert_equal(back, want, "expansion of direct carry")

    def test_direct_interleave_tabular(self, placed):
        """9-node fixture: every compact row is tabular ([Rt, D] scatter)."""
        self._run_interleave(placed, expect_dense=False)

    def test_direct_interleave_dense_rows(self, placed_wide):
        """80-node fixture: hostname-keyed terms ride the dense-row branch."""
        self._run_interleave(placed_wide, expect_dense=True)

    def test_direct_is_non_donating(self, placed):
        """plan/incremental.py shares one compact snapshot across probes:
        the direct apply must NOT donate/overwrite its input buffers."""
        import jax
        import jax.numpy as jnp

        from simtpu.engine.state import (
            apply_placement_deltas_compact,
            compact_delta_spec,
            compact_spec,
            compress_state,
            node_dom_for,
            node_dom_small_for,
        )

        eng = placed.engine
        tensors = placed.tensors
        statics = statics_from(tensors, eng.sched_config)
        spec = compact_spec(tensors)
        n = tensors.alloc.shape[0]
        cstate = compress_state(spec.dev, eng.carried_state())
        before = jax.tree_util.tree_map(
            lambda a: np.asarray(a).copy(), cstate
        )
        packed = _pack(
            placed, _entries_of(eng, range(0, len(eng.placed_node), 3)), -1.0
        )
        out = apply_placement_deltas_compact(
            statics,
            compact_delta_spec(tensors),
            node_dom_for(tensors, n),
            node_dom_small_for(tensors, n),
            cstate,
            packed,
        )
        assert not np.array_equal(np.asarray(out.free), before.free)
        self._assert_equal(cstate, before, "input snapshot after apply")

    def test_engine_preemption_path_skips_expand_recompress(self, placed):
        """Engine.remove_placements/restore_placements on a compact carry:
        the direct path fires (state.delta_direct +2), expand/recompress
        stay untouched, and the compact carry returns bit-identically —
        and the mid-eviction carry equals an explicit expand ->
        apply_placement_deltas -> compress of the same packed deltas."""
        import jax

        from simtpu.engine.state import (
            CompactState,
            compact_spec,
            compress_state,
            expand_state,
            node_dom_small_for,
        )
        from simtpu.obs.metrics import REGISTRY

        eng = placed.engine
        base = eng.last_state
        if not isinstance(base, CompactState):
            pytest.skip("engine carry not compact under this config")
        base_np = jax.tree_util.tree_map(lambda a: np.asarray(a).copy(), base)
        idx = list(range(0, len(eng.placed_node), 3))

        # the round-trip reference, built before the log is touched
        tensors = placed.tensors
        statics = statics_from(tensors, eng.sched_config)
        spec = compact_spec(tensors)
        nds = node_dom_small_for(tensors, base.free.shape[0])
        packed = _pack(placed, _entries_of(eng, idx), -1.0)
        mid_ref = compress_state(
            spec.dev,
            apply_placement_deltas(
                statics, expand_state(spec.dev, base, nds), packed
            ),
        )

        snap0 = REGISTRY.snapshot()
        saved = eng.remove_placements(idx)
        mid_direct = jax.tree_util.tree_map(
            lambda a: np.asarray(a).copy(), eng.last_state
        )
        eng.restore_placements(saved)
        snap1 = REGISTRY.snapshot()
        assert isinstance(eng.last_state, CompactState)
        assert snap1.get("state.delta_direct", 0) - snap0.get(
            "state.delta_direct", 0
        ) == 2
        for name in ("state.expand", "state.compress"):
            assert snap1.get(name, 0) == snap0.get(name, 0), (
                f"{name} bumped on the direct preemption hot path"
            )
        self._assert_equal(eng.last_state, base, "direct carry round trip")
        self._assert_equal(mid_direct, mid_ref, "mid-eviction carry")
        # the log and carry are back to the fixture's original state for
        # the tests that share this module-scoped fixture
        self._assert_equal(eng.last_state, base_np, "fixture restored")
