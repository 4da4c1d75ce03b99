"""Partition-tolerant hierarchical multi-hop sync (parallel/hierarchy.py):
topology planning, the two aggregation tiers, the subtree lifecycle
(partition -> degraded continuation -> re-graft), the KV transport with
aggregator failover, the subtree-scoped fault grammar, and the trainer
integrations. tools/hierarchy_drill.py is the multi-process version of the
lifecycle assertions over a real DistributedKV."""

import numpy as np
import pytest

import jax

from ps_pytorch_tpu.compression.codecs import encode_leaves, get_grad_codec
from ps_pytorch_tpu.config import TrainConfig
from ps_pytorch_tpu.parallel.async_dp import StaleGradientAggregator
from ps_pytorch_tpu.parallel.hierarchy import (
    GroupAggregator, HierarchicalAggregator, HierarchicalKVTransport,
    HierarchyPlan, RootAggregator,
)
from ps_pytorch_tpu.resilience import (
    FaultInjector, ManualClock, TransientKVError, parse_fault_spec,
)
from ps_pytorch_tpu.runtime.coordinator import KVStore


def _grads(seed, size=32):
    rng = np.random.default_rng(seed)
    return {"w": rng.standard_normal(size).astype(np.float32),
            "b": rng.standard_normal(size // 4).astype(np.float32)}


def _encode(grads, slice_id, step, codec="int8lat"):
    leaves, treedef = jax.tree.flatten(grads)
    payloads = encode_leaves(codec, leaves, slice_id=slice_id, step=step)
    return jax.tree.unflatten(treedef, payloads)


def _decode_payload_tree(tree, codec="int8lat"):
    """Single-payload decode through the homomorphic sum surface."""
    from ps_pytorch_tpu.compression.codecs import is_payload
    c = get_grad_codec(codec)
    leaves, treedef = jax.tree.flatten(tree, is_leaf=is_payload)
    out = []
    for p in leaves:
        st = c.sum_init()
        c.sum_add(st, p, 1.0)
        out.append(c.sum_finish(st, 1.0, c.payload_shape(p)))
    return jax.tree.unflatten(treedef, out)


# ---- topology plan ----

def test_plan_contiguous_groups_and_preferred_aggregator():
    plan = HierarchyPlan(9, group_size=3)
    assert plan.n_groups == 3
    assert plan.members(1) == [3, 4, 5]
    assert plan.group_of(5) == 1
    # Preferred aggregator = lowest member id, the elastic tie-break.
    assert [plan.aggregator_of(g) for g in range(3)] == [0, 3, 6]
    assert plan.describe() == {"n_slices": 9, "group_size": 3,
                               "n_groups": 3, "aggregators": [0, 3, 6]}


def test_plan_auto_group_size_is_sqrt_and_ragged_tail():
    assert HierarchyPlan(9).group_size == 3          # ~sqrt(n)
    plan = HierarchyPlan(7, group_size=3)            # ragged last group
    assert plan.n_groups == 3
    assert plan.members(2) == [6]
    # group_size larger than n collapses to one group.
    assert HierarchyPlan(3, group_size=8).n_groups == 1


def test_plan_levels_extensible_to_n_tiers():
    assert HierarchyPlan(9, group_size=3).levels() == [
        [[0, 1, 2], [3, 4, 5], [6, 7, 8]], [[0, 1, 2]]]
    # 27 slices at group_size 3: members -> 9 groups -> 3 -> 1.
    lv = HierarchyPlan(27, group_size=3).levels()
    assert [len(t) for t in lv] == [9, 3, 1]


def test_plan_validation():
    with pytest.raises(ValueError):
        HierarchyPlan(0)
    with pytest.raises(ValueError):
        HierarchyPlan(4).group_of(4)
    with pytest.raises(ValueError):
        HierarchyPlan(4, group_size=2).members(2)


# ---- tier 1: group hop ----

def test_group_hop_identical_members_is_lattice_exact():
    """All members submit the SAME gradient: the group average sits on the
    codec lattice already, so the re-encode is bitwise-lossless."""
    plan = HierarchyPlan(4, group_size=2)
    g = GroupAggregator(plan, 0, "int8lat")
    grads = _grads(7)
    for sid in (0, 1):
        g.submit_encoded(sid, 1, _encode(grads, sid, 1))
    step, wsum, tree = g.collect_and_reencode(1)
    assert (step, wsum) == (1, 2.0)
    member = _decode_payload_tree(_encode(grads, 0, 1))
    hop = _decode_payload_tree(tree)
    for a, b in zip(jax.tree.leaves(member), jax.tree.leaves(hop)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert g.hops == 1


def test_group_hop_mean_within_one_lattice_step():
    """Distinct members: the re-encode may round the pooled mean by at most
    one int8lat lattice step (2^-8 of the per-leaf scale) per hop."""
    plan = HierarchyPlan(2, group_size=2)
    g = GroupAggregator(plan, 0, "int8lat")
    flat = StaleGradientAggregator(2, compress=True, codec="int8lat")
    for sid in (0, 1):
        enc = _encode(_grads(100 + sid), sid, 1)
        g.submit_encoded(sid, 1, enc)
        flat.submit_encoded(sid, 1, enc)
    _, _, tree = g.collect_and_reencode(1)
    want, _ = flat.collect(1)
    for a, b in zip(jax.tree.leaves(_decode_payload_tree(tree)),
                    jax.tree.leaves(want)):
        a, b = np.asarray(a), np.asarray(b)
        tol = float(np.max(np.abs(b))) * 2.0 ** -7 + 1e-7
        assert float(np.max(np.abs(a - b))) <= tol


def test_group_hop_rejects_foreign_member_and_empty_pool():
    plan = HierarchyPlan(4, group_size=2)
    g = GroupAggregator(plan, 0, "int8lat")
    with pytest.raises(ValueError, match="not in group"):
        g.submit_encoded(2, 1, _encode(_grads(1), 2, 1))
    assert g.collect_and_reencode(1) is None


def test_group_hop_ef_state_roundtrip_bitwise():
    plan = HierarchyPlan(2, group_size=2)
    g = GroupAggregator(plan, 0, "int8lat", hop_ef=True)
    for sid in (0, 1):
        g.submit_encoded(sid, 1, _encode(_grads(50 + sid), sid, 1))
    g.collect_and_reencode(1)
    state = g.ef_state_dict()
    assert state                      # distinct members -> nonzero residual
    g2 = GroupAggregator(plan, 0, "int8lat", hop_ef=True)
    g2.load_ef_state(state)
    got = g2.ef_state_dict()
    assert set(got) == set(state)
    for k in state:
        np.testing.assert_array_equal(np.asarray(state[k]),
                                      np.asarray(got[k]))


# ---- tier 2: root pool + subtree lifecycle ----

def test_root_weighting_reproduces_flat_average():
    """sum_g(w_g * avg_g) / sum_g(w_g) == sum_i(g_i) / N when fresh: the
    2-tier average must match the flat one up to per-hop lattice rounding."""
    n, gsz = 4, 2
    plan = HierarchyPlan(n, group_size=gsz)
    root = RootAggregator(plan.n_groups, "int8lat")
    flat = StaleGradientAggregator(n, compress=True, codec="int8lat")
    groups = [GroupAggregator(plan, g, "int8lat")
              for g in range(plan.n_groups)]
    for sid in range(n):
        enc = _encode(_grads(200 + sid), sid, 1)
        groups[plan.group_of(sid)].submit_encoded(sid, 1, enc)
        flat.submit_encoded(sid, 1, enc)
    for g in groups:
        step, wsum, tree = g.collect_and_reencode(1)
        root.submit_group(g.gid, step, wsum, tree)
    avg, info = root.collect(1)
    assert info["used"] == [0, 1] and not info["degraded"]
    assert info["weights"] == {0: 2.0, 1: 2.0}
    want, _ = flat.collect(1)
    for a, b in zip(jax.tree.leaves(avg), jax.tree.leaves(want)):
        a, b = np.asarray(a), np.asarray(b)
        tol = float(np.max(np.abs(b))) * 2.0 ** -6 + 1e-7
        assert float(np.max(np.abs(a - b))) <= tol


def _group_payload(plan, gid, step):
    """One single-member group hop -> (step, wsum, payload tree)."""
    g = GroupAggregator(plan, gid, "int8lat")
    sid = plan.members(gid)[0]
    g.submit_encoded(sid, step, _encode(_grads(step), sid, step))
    return g.collect_and_reencode(step)


def test_root_partition_degrade_regraft_lifecycle():
    events = []
    plan = HierarchyPlan(2, group_size=1)
    root = RootAggregator(2, "int8lat", staleness_limit=2,
                          on_event=lambda *a: events.append(a))

    def feed(gid, step):
        s, wsum, tree = _group_payload(plan, gid, step)
        root.submit_group(gid, s, wsum, tree)

    feed(0, 1)
    feed(1, 1)
    avg, info = root.collect(1)
    assert avg is not None and root.groups_healthy() == 2
    root.consume(info["used"])
    # Group 1 goes silent; group 0 keeps reporting. Silence crosses the
    # limit at step 4 -> partition declared ONCE, run continues degraded.
    for step in (2, 3, 4, 5):
        feed(0, step)
        avg, info = root.collect(step)
        assert avg is not None            # degraded-mode continuation
        root.consume(info["used"])
    assert root.counters["partitions"] == 1
    assert root.groups_healthy() == 1
    assert root.counters["degraded_steps"] >= 2
    assert [e for e in events if e[0] == "partition"] == [("partition", 1, 4, 3)]
    # Heal: one fresh contribution re-grafts, also exactly once.
    feed(1, 6)
    feed(0, 6)
    avg, info = root.collect(6)
    assert sorted(info["used"]) == [0, 1] and not info["degraded"]
    assert root.counters["regrafts"] == 1 and root.groups_healthy() == 2
    assert ("regraft", 1, 6, 0) in events
    snap = root.snapshot()
    assert snap["partitions"] == 1 and snap["groups_healthy"] == 2


def test_root_stale_pre_partition_aggregate_dropped_by_filter():
    """What a subtree published BEFORE partitioning is past the limit by
    construction at re-graft time: the normal staleness filter drops it, so
    catch-up needs no special path."""
    root = RootAggregator(1, "int8lat", staleness_limit=2)
    plan = HierarchyPlan(1, group_size=1)
    step, wsum, tree = _group_payload(plan, 0, 1)
    root.submit_group(0, step, wsum, tree)
    avg, info = root.collect(9)           # 8 versions later
    assert avg is None and info["dropped_stale"] == [0]
    assert root.counters["partitions"] == 1
    assert root.drop_older_than(9) == 1   # GC purges the stale aggregate


def test_root_k_of_n_over_groups():
    root = RootAggregator(3, "int8lat", num_aggregate=2)
    plan = HierarchyPlan(3, group_size=1)
    for gid, step in ((0, 5), (1, 4), (2, 3)):   # staleness 0, 1, 2
        g = GroupAggregator(plan, gid, "int8lat")
        g.submit_encoded(gid, step, _encode(_grads(gid), gid, step))
        s, w, t = g.collect_and_reencode(step)
        root.submit_group(gid, s, w, t)
    avg, info = root.collect(5)
    assert info["used"] == [0, 1]         # freshest 2 of 3 groups
    assert info["degraded"]               # < n_groups used counts degraded


def test_root_validation():
    with pytest.raises(ValueError):
        RootAggregator(0, "int8lat")
    with pytest.raises(ValueError):
        RootAggregator(2, "int8lat", num_aggregate=3)
    root = RootAggregator(2, "int8lat")
    with pytest.raises(ValueError, match="wsum"):
        root.submit_group(0, 1, 0.0, [])
    with pytest.raises(ValueError, match="out of range"):
        root.submit_group(2, 1, 1.0, [])
    with pytest.raises(ValueError):
        RootAggregator(2, "blosc")        # homomorphic codecs only


# ---- in-process composition ----

def test_hier_aggregator_matches_flat_within_hop_rounding():
    n = 4
    hier = HierarchicalAggregator(n, group_size=2, codec="int8lat")
    flat = StaleGradientAggregator(n, compress=True, codec="int8lat")
    for sid in range(n):
        g = _grads(300 + sid)
        hier.submit(sid, 1, g)
        flat.submit(sid, 1, g)
    avg_h, info = hier.collect(1)
    avg_f, _ = flat.collect(1)
    assert sorted(info["used"]) == list(range(n))
    assert info["used_groups"] == [0, 1]
    for a, b in zip(jax.tree.leaves(avg_h), jax.tree.leaves(avg_f)):
        a, b = np.asarray(a), np.asarray(b)
        tol = float(np.max(np.abs(b))) * 2.0 ** -6 + 1e-7
        assert float(np.max(np.abs(a - b))) <= tol


def test_hier_aggregator_deterministic_and_ef_roundtrip():
    """Same submissions -> bitwise-identical averages and EF state; the
    combined member+hop EF dict survives a save/load round trip bitwise
    (what --auto-resume relies on)."""
    def run():
        agg = HierarchicalAggregator(4, group_size=2, codec="int8lat",
                                     error_feedback=True, hop_ef=True)
        outs = []
        for step in (1, 2, 3):
            for sid in range(4):
                agg.submit(sid, step, _grads(17 * sid + step))
            avg, info = agg.collect(step)
            agg.consume(info["used"])
            outs.append(avg)
        return agg, outs

    a, outs_a = run()
    b, outs_b = run()
    for ta, tb in zip(outs_a, outs_b):
        for x, y in zip(jax.tree.leaves(ta), jax.tree.leaves(tb)):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    sa, sb = a.ef_state_dict(), b.ef_state_dict()
    assert "members" in sa and any(k.startswith("g") for k in sa)
    c = HierarchicalAggregator(4, group_size=2, codec="int8lat",
                               error_feedback=True, hop_ef=True)
    c.load_ef_state(sa)
    sc = c.ef_state_dict()

    def flat_items(d, pre=""):
        for k, v in sorted(d.items()):
            if isinstance(v, dict):
                yield from flat_items(v, f"{pre}{k}/")
            else:
                yield f"{pre}{k}", v

    ia, ic = dict(flat_items(sa)), dict(flat_items(sc))
    assert set(ia) == set(ic) and ia
    for k in ia:
        np.testing.assert_array_equal(np.asarray(ia[k]), np.asarray(ic[k]))
    # Flat-topology checkpoint back-compat: member-tier owns the residuals.
    d = HierarchicalAggregator(4, group_size=2, codec="int8lat",
                               error_feedback=True)
    d.load_ef_state(sa["members"])
    assert d.ef_state_dict()["members"].keys() == sa["members"].keys()


def test_hier_aggregator_inter_every_amortizes_upward_hops():
    agg = HierarchicalAggregator(2, group_size=2, codec="int8lat",
                                 inter_every=2)
    agg.submit(0, 1, _grads(1))
    agg.submit(1, 1, _grads(2))
    avg, _ = agg.collect(1)               # round 1: no uplink due, payloads
    assert avg is None                    # stay pooled (latest-wins)
    agg.submit(0, 2, _grads(3))
    agg.submit(1, 2, _grads(4))
    avg, info = agg.collect(2)            # round 2: group hop + uplink
    assert avg is not None and info["used_groups"] == [0]
    assert info["used"] == [0, 1]         # members whose grads reached root


def test_hier_inter_every_average_is_latest_wins_not_discarded():
    """With inter_every=2 the round the up-link skips must leave member
    payloads pooled: the round-2 average is exactly the flat average of
    the LATEST submissions, not half of them silently dropped."""
    hier = HierarchicalAggregator(4, group_size=2, codec="int8lat",
                                  inter_every=2)
    flat = StaleGradientAggregator(4, compress=True, codec="int8lat")
    for sid in range(4):
        hier.submit(sid, 1, _grads(50 + sid))
    avg, info = hier.collect(1)
    assert avg is None and info["used"] == []
    for sid in (0, 1):                    # slices 2,3 skip round 2: their
        g = _grads(60 + sid)              # round-1 payloads must survive
        hier.submit(sid, 2, g)
        flat.submit(sid, 2, g)
    for sid in (2, 3):
        flat.submit(sid, 1, _grads(50 + sid))
    avg_h, info = hier.collect(2)
    avg_f, _ = flat.collect(2)
    assert avg_h is not None and sorted(info["used"]) == [0, 1, 2, 3]
    for a, b in zip(jax.tree.leaves(avg_h), jax.tree.leaves(avg_f)):
        a, b = np.asarray(a), np.asarray(b)
        tol = float(np.max(np.abs(b))) * 2.0 ** -6 + 1e-7
        assert float(np.max(np.abs(a - b))) <= tol


def test_hier_num_aggregate_clamped_to_group_count():
    # Flat-semantics K (counted in members, e.g. 8 slices K=4) must not
    # crash the per-tier root, which counts groups: ceil(8/3) = 3.
    agg = HierarchicalAggregator(8, group_size=3, num_aggregate=4,
                                 codec="int8lat")
    assert agg.root.k == agg.plan.n_groups == 3


def test_hier_kofn_leftover_average_reports_its_members():
    """A group aggregate cut by the root's K this round applies on a later
    one — with its members reported in info['used'], so a trainer gating
    the update on a non-empty used list never drops a consumed average."""
    agg = HierarchicalAggregator(2, group_size=1, num_aggregate=1,
                                 codec="int8lat")
    agg.submit(0, 1, _grads(1))
    agg.submit(1, 1, _grads(2))
    avg, info = agg.collect(1)
    assert avg is not None and info["used_groups"] == [0]
    assert info["used"] == [0]
    avg, info = agg.collect(2)            # leftover group 1 applies now
    assert avg is not None and info["used_groups"] == [1]
    assert info["used"] == [1]
    avg, info = agg.collect(3)
    assert avg is None and info["used"] == []


def test_multislice_hier_accepts_flat_num_aggregate(tmp_path):
    """8-slice flat config with num_aggregate=4 (valid: K <= n_slices)
    must construct under sync_topology=hier too, where auto grouping
    yields 3 groups."""
    from ps_pytorch_tpu.config import TrainConfig as TC
    from ps_pytorch_tpu.runtime.multislice import MultiSliceTrainer
    cfg = TC(dataset="synthetic_mnist", network="LeNet", batch_size=8,
             compute_dtype="float32", mode="async", max_steps=1,
             eval_freq=0, train_dir=str(tmp_path / "ckpt"),
             compress_grad=True, grad_codec="int8lat",
             sync_topology="hier", num_aggregate=4)
    t = MultiSliceTrainer(cfg, n_slices=8)
    assert t.aggregator.root.k == t.aggregator.plan.n_groups == 3


# ---- cross-process transport over the KV ----

def _transports(kv, clock, n=4, gsz=2, **kw):
    # Channel template = a throwaway encode (payload shapes are
    # data-independent), same as the async trainer's wire setup.
    tpl = _encode(_grads(0), 0, 0)
    return [HierarchicalKVTransport(
        kv, n, tpl, {"params": _grads(0)}, run_id="t", pid=p, group_size=gsz,
        codec="int8lat", lease_interval_s=1.0, clock=clock.time,
        sleep=lambda _s: None, **kw) for p in range(n)]


def test_transport_pump_publish_poll_roundtrip():
    clock, kv = ManualClock(), KVStore()
    ts = _transports(kv, clock)
    for t in ts:
        t.submit_grads(t.pid, 1, 1, _encode(_grads(400 + t.pid), t.pid, 1))
    # Preferred aggregators (lowest member of each group) claim + pump.
    assert ts[0].pump(1) == 1 and ts[2].pump(1) == 1
    assert ts[0].is_aggregator and ts[2].is_aggregator
    assert not ts[1].is_aggregator
    got = ts[0].poll_new_aggs()
    assert [(g, s, w) for g, s, w, _ in got] == [(0, 1, 2.0), (1, 1, 2.0)]
    assert ts[0].poll_new_aggs() == []    # version-guarded: no re-reads
    assert ts[0].stats["group_publishes"] == 1
    ws = ts[0].wire_stats()
    assert ws["hier_hops"] == 1 and ws["hier_hop_giveups"] == 0


def test_transport_failover_member_adopts_aggregator_role():
    clock, kv = ManualClock(), KVStore()
    ts = _transports(kv, clock)
    assert ts[0].pump(1) == 0             # claims the lease, empty pool
    assert ts[0].is_aggregator
    # The aggregator goes silent past 3x the lease interval; its groupmate
    # campaigns on its next pump and adopts the role — a failover.
    clock.now += 10.0
    ts[1].submit_grads(1, 1, 1, _encode(_grads(9), 1, 1))
    assert ts[1].pump(1) == 1
    assert ts[1].is_aggregator and ts[1].stats["failovers"] == 1
    assert ts[0].stats["failovers"] == 0


def test_transport_ahead_member_step_not_dropped():
    """A member that fetched newer canonical params stamps a step AHEAD of
    the aggregator's local clock; the hop clock must follow the pool."""
    clock, kv = ManualClock(), KVStore()
    ts = _transports(kv, clock)
    ts[1].submit_grads(1, 1, 7, _encode(_grads(9), 1, 7))
    assert ts[0].pump(2) == 1             # aggregator's own clock lags at 2
    ((gid, step, wsum, _),) = ts[0].poll_new_aggs()
    assert (gid, step, wsum) == (0, 7, 1.0)


def test_transport_partition_window_degrades_not_crashes():
    """With the KV partitioned under the aggregator, pump() gives the hop
    up (degraded) instead of raising; the heal re-publishes normally."""
    clock, kv = ManualClock(), KVStore()
    inj = FaultInjector("kv_partition:r=0,step=5,steps=2", process_index=0,
                        sleep=lambda _s: None)
    ts = _transports(inj.wrap_kv(kv), clock, n=2, gsz=2, hop_retries=2)
    t0 = ts[0]
    assert t0.pump(1) == 0 and t0.is_aggregator
    inj.maybe_crash(5)                    # partition window opens
    t0._pool.submit_encoded(0, 5, _encode(_grads(5), 0, 5))
    assert t0.pump(5) == 0
    assert t0.stats["hop_giveups"] == 1
    assert inj.counters["kv_partition_drops"] > 0
    inj.maybe_crash(7)                    # window closes: heal
    t0._pool.submit_encoded(0, 7, _encode(_grads(7), 0, 7))
    assert t0.pump(7) == 1
    assert t0.stats["hop_giveups"] == 1


def test_pump_publish_version_survives_transient_read_error():
    """latest_version() returning None (a transient KV hiccup, same shape
    as 'nothing published') must not reset the up-link version counter:
    the root's high-water would then ignore the group's publishes."""
    clock, kv = ManualClock(), KVStore()
    ts = _transports(kv, clock, n=2, gsz=2)
    t0 = ts[0]
    t0.submit_grads(0, 1, 1, _encode(_grads(1), 0, 1))
    assert t0.pump(1) == 1
    assert [g for g, _, _, _ in t0.poll_new_aggs()] == [0]
    ch = t0._agg_chan(0)
    orig = ch.latest_version
    ch.latest_version = lambda: None      # the transient-error read shape
    t0.submit_grads(0, 2, 2, _encode(_grads(2), 0, 2))
    assert t0.pump(2) == 1
    ch.latest_version = orig
    got = t0.poll_new_aggs()              # high-water still sees v2 > v1
    assert [(g, s) for g, s, _, _ in got] == [(0, 2)]


# ---- subtree-scoped fault grammar ----

def test_kv_partition_group_scope_parses_and_self_scopes():
    faults = parse_fault_spec("kv_partition:group=1,gsize=2,step=3,steps=2")
    assert faults[0]["group"] == 1 and faults[0]["gsize"] == 2
    for pid, hit in ((0, False), (1, False), (2, True), (3, True),
                     (4, False)):
        inj = FaultInjector("kv_partition:group=1,gsize=2,step=3,steps=2",
                            process_index=pid, sleep=lambda _s: None)
        kv = inj.wrap_kv(KVStore())
        inj.maybe_crash(3)                # window open
        if hit:
            with pytest.raises(TransientKVError, match="kv_partition"):
                kv.set("k", "v")
            inj.maybe_crash(5)            # window closed
            kv.set("k", "v")
        else:
            kv.set("k", "v")
            assert inj.counters["kv_partition_drops"] == 0


def test_link_jitter_prefix_scoped_delay():
    sleeps = []
    inj = FaultInjector("link_jitter:s=0.02,prefix=t/hagg",
                        process_index=0, sleep=sleeps.append)
    kv = inj.wrap_kv(KVStore())
    kv.set("t/hgrad/0/1", "x")            # fast link: untouched
    assert sleeps == []
    kv.set("t/hagg/0", "x")               # slow up-link: jittered
    kv.get("t/hagg/0")
    assert sleeps == [0.02, 0.02]
    assert inj.counters["link_jitters"] == 2


def test_fault_spec_validation_errors():
    for bad in ("kv_partition:group=-1,step=1,steps=1",
                "kv_partition:group=1,gsize=0,step=1,steps=1",
                "kv_partition:group=1,r=0,step=1,steps=1",
                "link_jitter:prefix=x",
                "link_jitter:s=0,p=2"):
        with pytest.raises(ValueError):
            parse_fault_spec(bad)


# ---- config + trainer integration ----

def test_config_hier_requires_homomorphic_codec():
    with pytest.raises(ValueError, match="sync_topology=hier"):
        TrainConfig(sync_topology="hier")
    with pytest.raises(ValueError, match="sync_topology=hier"):
        TrainConfig(sync_topology="hier", compress_grad=True,
                    grad_codec="blosc")
    with pytest.raises(ValueError, match="sync_topology"):
        TrainConfig(sync_topology="ring")
    with pytest.raises(ValueError):
        TrainConfig(sync_intra_every=0)
    with pytest.raises(ValueError):
        TrainConfig(hier_hop_retries=0)
    cfg = TrainConfig(sync_topology="hier", compress_grad=True,
                      grad_codec="int8lat")
    assert cfg.sync_group_size == 0       # auto


def test_multislice_hier_topology_trains_and_checkpoints(tmp_path):
    """--sync-topology hier swaps HierarchicalAggregator into
    MultiSliceTrainer behind the flat surface: ticks apply updates from
    all slices and the hop-EF rides the checkpoint."""
    from ps_pytorch_tpu.runtime import checkpoint as ckpt
    from ps_pytorch_tpu.runtime.multislice import MultiSliceTrainer

    cfg = TrainConfig(dataset="synthetic_mnist", network="LeNet",
                      batch_size=64, lr=0.05, momentum=0.9,
                      compute_dtype="float32", mode="async", max_steps=4,
                      eval_freq=2, log_every=100,
                      train_dir=str(tmp_path / "ckpt"),
                      compress_grad=True, grad_codec="int8lat",
                      sync_topology="hier", sync_group_size=1)
    t = MultiSliceTrainer(cfg, n_slices=2)
    assert isinstance(t.aggregator, HierarchicalAggregator)
    info = t.tick()
    assert sorted(info["used"]) == [0, 1]
    t.train()
    assert t.applied == 4
    step = ckpt.latest_valid_step(cfg.train_dir)
    extra = ckpt.load_extra_state(cfg.train_dir, step)
    assert extra is not None and "ef" in extra
    t2 = MultiSliceTrainer(cfg, n_slices=2)
    t2.aggregator.load_ef_state(extra["ef"])   # shape-compatible reload


# ---- the drill's verdict ----

def test_hierarchy_drill_verdict(committed_record):
    from ps_pytorch_tpu.tools.hierarchy_drill import verdict
    good = committed_record("RESILIENCE_r14.json")
    assert verdict(good) == []
    # every lifecycle floor is demanded independently
    for key in ("partitions", "regrafts", "degraded_steps"):
        bad = dict(good, hierarchy=dict(good["hierarchy"], **{key: 0}))
        assert verdict(bad) == [f"partition: {key} >= 1"]
    # a CPU clock is no part of the rule: a tree that did not beat the flat
    # star in the record's old bench section is no violation
    tied = dict(good, hierarchy=dict(good["hierarchy"],
                                     bench={"speedup": 1.0}))
    assert verdict(tied) == []
    assert verdict(dict(good, bitwise_equal=False))
    assert verdict({"ok": True})   # no sections; its own "ok" is not believed


def test_hierarchy_drill_verdict_passes_committed_record(committed_record):
    """The committed round-14 record must pass the verdict of the drill that
    wrote it, with or without its old bench sections."""
    from ps_pytorch_tpu.tools.hierarchy_drill import verdict
    rec = committed_record("RESILIENCE_r14.json")
    assert "bench" in rec["hierarchy"] and "bench" in rec["phases"]
    assert verdict(rec) == []
    del rec["hierarchy"]["bench"], rec["phases"]["bench"]
    assert verdict(rec) == []
