"""What the gradient wire counts: bytes, messages and bitwise flags.

Computed in process on small pytrees over an in-memory KVStore, with no
sleep and no timer: a CPU run can give a count, a byte total or a bitwise
flag, never a rate. Not repeated here because another test holds them:
the sharded update bitwise equal to one shard at 2, 4 and 5 shards
(tests/test_zero_wire.py::test_sharded_equals_replicated_bitwise), and the
compressed-domain sum against the oracle from in-process submissions
(tests/test_codecs.py::test_aggregator_collect_bitwise_vs_oracle).
"""

import jax
import numpy as np
import pytest

from ps_pytorch_tpu.compression.codecs import (
    HOMOMORPHIC_GRAD_CODECS, decode_then_average, encode_leaves, is_payload,
)
from ps_pytorch_tpu.parallel.async_dp import StaleGradientAggregator
from ps_pytorch_tpu.parallel.hierarchy import HierarchicalKVTransport
from ps_pytorch_tpu.parallel.transport import KVPytreeChannel
from ps_pytorch_tpu.parallel.zero_wire import ZeroWireUpdater
from ps_pytorch_tpu.resilience import ManualClock
from ps_pytorch_tpu.runtime.coordinator import KVStore

FRAC = 0.01
# Armoured wire bytes a contributor may publish, as a share of its float32
# pytree: one int8 a value in base85 is 5/16; a sparse codec at 1% keeps a
# value and an index for one entry in a hundred.
MAX_WIRE_SHARE = {"int8lat": 0.35, "topk": 0.05, "randk": 0.05}


def _tree(seed, n_leaves=8, per_leaf=4096):
    rng = np.random.default_rng(seed)
    return {f"l{i:02d}": (rng.standard_normal(per_leaf) / 4.0)
            .astype(np.float32) for i in range(n_leaves)}


def _encoded(codec, tree, slice_id, step):
    leaves, treedef = jax.tree.flatten(tree)
    return jax.tree.unflatten(treedef, encode_leaves(
        codec, leaves, slice_id=slice_id, step=step, frac=FRAC))


def _publish_all(codec, contributors=4):
    """Every contributor encodes and publishes over its own channel; the
    leader reads them back. -> (payload trees as read, armoured bytes)."""
    kv = KVStore()
    trees = [_tree(7 + w) for w in range(contributors)]
    template = _encoded(codec, _tree(0), 0, 0)
    wire_bytes, got = 0, []
    for w, tree in enumerate(trees):
        ch = KVPytreeChannel(kv, f"agg/{w}", template, codec="blosc",
                             bucket_bytes=1 << 14, workers=2)
        ch.publish(1, _encoded(codec, tree, w, 1))
        wire_bytes += ch.last_publish_bytes
    for w in range(contributors):
        version, tree, _ = KVPytreeChannel(
            kv, f"agg/{w}", template, codec="blosc", bucket_bytes=1 << 14,
            workers=2).read()
        assert version == 1
        got.append(tree)
    raw_bytes = sum(l.nbytes for t in trees for l in t.values())
    return got, wire_bytes, raw_bytes


@pytest.mark.parametrize("codec", HOMOMORPHIC_GRAD_CODECS)
def test_codec_payload_bytes_against_float32(codec):
    contributors = 4
    _, wire_bytes, raw_bytes = _publish_all(codec, contributors)
    assert 0 < wire_bytes <= MAX_WIRE_SHARE[codec] * raw_bytes
    # and against one float tree through the same channel: blosc cannot
    # squeeze random mantissas, so a lossy codec is at least twice smaller
    kv, tree = KVStore(), _tree(7)
    dense = KVPytreeChannel(kv, "dense", tree, codec="blosc")
    dense.publish(1, tree)
    assert 2 * wire_bytes / contributors <= dense.last_publish_bytes


@pytest.mark.parametrize("codec", HOMOMORPHIC_GRAD_CODECS)
def test_compressed_sum_over_the_wire_equals_decode_then_average(codec):
    """The leader's path: payloads read off the channel, submit_encoded,
    one collect — bit for bit the decode-then-average oracle over the same
    payloads."""
    got, _, _ = _publish_all(codec)
    agg = StaleGradientAggregator(len(got), staleness_limit=4,
                                  num_aggregate=0, compress=True,
                                  codec=codec, topk_frac=FRAC)
    for w, tree in enumerate(got):
        agg.submit_encoded(w, 1, tree)
    avg, info = agg.collect(1)
    assert sorted(info["used"]) == list(range(len(got)))
    avg_leaves = [np.asarray(l) for l in jax.tree.leaves(avg)]
    oracle = decode_then_average(codec, [
        (1.0, jax.tree.leaves(tree, is_leaf=is_payload)) for tree in got])
    for a, o in zip(avg_leaves, oracle):
        np.testing.assert_array_equal(a, o.reshape(a.shape))


def _run_zero_wire(n_shards, rounds=3):
    kv, tree = KVStore(), _tree(0)
    members = list(range(n_shards))
    ups = [ZeroWireUpdater(inner=None, kv=kv, run_id="zw", params=tree,
                           optimizer="sgd", members=members, me=m,
                           n_shards=n_shards, lr=0.05, momentum=0.9)
           for m in members]
    grng = np.random.default_rng(1)
    for rnd in range(rounds):
        g = {k: (grng.standard_normal(v.shape) / 8.0).astype(np.float32)
             for k, v in tree.items()}
        for u in ups:
            u.apply_and_publish(g, version=rnd + 1)
        for u in ups:
            u.assemble_round()
    return ups


@pytest.mark.parametrize("n_shards", [2, 4])
def test_wire_shards_cut_publish_bytes_and_moments(n_shards):
    (whole,) = _run_zero_wire(1)
    ups = _run_zero_wire(n_shards)
    full_out = whole.wire_stats()["zw_bytes_out"]
    assert max(u.wire_stats()["zw_bytes_out"] for u in ups) <= 0.75 * full_out
    # together the owners publish the tree once, not n_shards times
    assert sum(u.wire_stats()["zw_bytes_out"] for u in ups) <= 1.05 * full_out
    moments = [u.opt_state_nbytes() for u in ups]
    assert sum(moments) == whole.opt_state_nbytes()
    assert max(moments) <= (1.0 / n_shards + 0.15) * whole.opt_state_nbytes()


@pytest.mark.parametrize("n_slices,n_groups", [(4, 2), (9, 3)])
def test_root_receives_one_message_a_group(n_slices, n_groups):
    clock, kv = ManualClock(), KVStore()
    template = _encoded("int8lat", _tree(0, n_leaves=2, per_leaf=64), 0, 0)
    ts = [HierarchicalKVTransport(
        kv, n_slices, template, {"params": _tree(0, 2, 64)}, run_id="h",
        pid=p, codec="int8lat", lease_interval_s=1.0, clock=clock.time,
        sleep=lambda _s: None) for p in range(n_slices)]
    assert ts[0].plan.n_groups == n_groups
    for t in ts:
        t.submit_grads(t.pid, 1, 1, _encoded(
            "int8lat", _tree(40 + t.pid, 2, 64), t.pid, 1))
    assert sum(t.pump(1) for t in ts) == n_groups
    got = ts[0].poll_new_aggs()
    assert [gid for gid, _, _, _ in got] == list(range(n_groups))
    assert sum(wsum for _, _, wsum, _ in got) == n_slices
    # the root's link carries the groups' channels and nobody else's
    up = {k.split("/")[2] for k in kv.keys("h/hagg/")}
    assert up == {str(g) for g in range(n_groups)}
    assert ts[0].poll_new_aggs() == []
