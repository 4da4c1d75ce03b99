"""ZeRO-over-the-wire (parallel/zero_wire.py): the sharded weight update on
the KV plane must equal the replicated update BIT-FOR-BIT — at every shard
count (1/2/4/uneven), for SGD and Adam, with codecs on and off, under
K-of-N with a straggler, across handoff/adopt resharding, and across a
SIGKILL -> resume of the sharded optimizer-state checkpoint. Plus the
satellite moves: armored base85 shard codec + wire-byte accounting in the
(re-exported) elastic primitive, and the --shard-wire config gates.
"""

import os
import pathlib
import signal
import subprocess
import sys
import time

import jax
import numpy as np
import pytest

from ps_pytorch_tpu.parallel.zero_wire import (
    ZeroWireUpdater,
    decode_array,
    encode_array,
    plan_wire_shards,
)
from ps_pytorch_tpu.runtime.coordinator import KVStore

REPO = pathlib.Path(__file__).resolve().parent.parent


# ---------------------------------------------------------------------------
# Fixtures: a small uneven pytree (leaf count not divisible by 2 or 4) and
# a deterministic gradient stream.
# ---------------------------------------------------------------------------

def _tree(seed=0):
    rng = np.random.default_rng(seed)
    return {"a": rng.standard_normal((37, 5)).astype(np.float32),
            "b": rng.standard_normal((128,)).astype(np.float32),
            "c": {"w": rng.standard_normal((64, 7)).astype(np.float32),
                  "bias": rng.standard_normal((7,)).astype(np.float32),
                  "s": np.float32(0.3)}}


def _grads(n, seed=1):
    rng = np.random.default_rng(seed)
    tpl = _tree()
    return [jax.tree.map(
        lambda a: rng.standard_normal(np.shape(a)).astype(np.float32), tpl)
        for _ in range(n)]


def _assert_trees_equal(a, b):
    for la, lb in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))


def _run_sharded(n_shards, grads, optimizer, workers=0, **kw):
    """Drive n_shards single-owner members over one KVStore; every member
    must assemble the identical full tree each round."""
    kv = KVStore()
    members = list(range(n_shards))
    ups = [ZeroWireUpdater(inner=None, kv=kv, run_id="t", params=_tree(),
                           optimizer=optimizer, members=members, me=m,
                           n_shards=n_shards, workers=workers, **kw)
           for m in members]
    out = None
    for step, g in enumerate(grads):
        for u in ups:                       # publish ALL before assembling
            u.apply_and_publish(g, version=step + 1)
        trees = [u.assemble_round() for u in ups]
        out = trees[0]
        for t in trees[1:]:
            _assert_trees_equal(out, t)
    return out, ups, kv


# ---------------------------------------------------------------------------
# Shard planning: bucket-edge snapping, balance, degenerate counts.
# ---------------------------------------------------------------------------

def test_plan_wire_shards_covers_and_monotone():
    leaves = jax.tree.leaves(_tree())
    for n in (1, 2, 3, 4, 5, 7):
        bounds = plan_wire_shards(leaves, n)
        assert len(bounds) == n
        assert bounds[0][0] == 0 and bounds[-1][1] == len(leaves)
        for (lo, hi), (lo2, hi2) in zip(bounds, bounds[1:]):
            assert lo <= hi == lo2 <= hi2      # contiguous, non-overlapping


def test_plan_wire_shards_snaps_to_bucket_edges():
    from ps_pytorch_tpu.parallel.buckets import plan_buckets
    rng = np.random.default_rng(3)
    leaves = [rng.standard_normal((256,)).astype(np.float32)
              for _ in range(32)]
    bucket_bytes = 4 * 256 * 4      # 4 leaves per bucket -> 8 buckets
    edges = {b.start for b in plan_buckets(leaves, bucket_bytes)} \
        | {len(leaves)}
    for n in (2, 3, 4):
        for lo, hi in plan_wire_shards(leaves, n, bucket_bytes):
            assert lo in edges and hi in edges


def test_plan_wire_shards_more_shards_than_leaves():
    leaves = [np.zeros(4, np.float32), np.zeros(4, np.float32)]
    bounds = plan_wire_shards(leaves, 5)
    assert bounds[0][0] == 0 and bounds[-1][1] == 2
    assert sum(hi - lo for lo, hi in bounds) == 2   # trailing shards empty


def test_plan_wire_shards_huge_bucket_falls_back_to_leaf_edges():
    # One 4MB bucket would leave n-1 shards empty; the plan must fall back
    # to leaf-granular edges and keep the split byte-balanced.
    leaves = [np.zeros(1000, np.float32) for _ in range(8)]
    bounds = plan_wire_shards(leaves, 4, bucket_bytes=4 << 20)
    assert all(hi > lo for lo, hi in bounds)


# ---------------------------------------------------------------------------
# The bitwise guarantee: sharded == replicated at every shard count, for
# the full SGD/Adam option matrix, on an uneven leaf count.
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("optimizer,kw", [
    ("sgd", dict(lr=0.05, momentum=0.0)),
    ("sgd", dict(lr=0.05, momentum=0.9)),
    ("sgd", dict(lr=0.05, momentum=0.9, nesterov=True)),
    ("sgd", dict(lr=0.05, momentum=0.9, weight_decay=1e-4)),
    ("adam", dict(lr=0.001)),
    ("adam", dict(lr=0.001, amsgrad=True, weight_decay=1e-3)),
])
def test_sharded_equals_replicated_bitwise(optimizer, kw):
    grads = _grads(6)
    ref, _, _ = _run_sharded(1, grads, optimizer, **kw)
    for n in (2, 4, 5):            # 5 shards over 5 leaves: uneven split
        got, ups, kv = _run_sharded(n, grads, optimizer,
                                    workers=2 if n == 4 else 0, **kw)
        _assert_trees_equal(ref, got)
        # 1/N optimizer memory: every member holds only its shards' moments.
        total = sum(u.opt_state_nbytes() for u in ups)
        for u in ups:
            assert u.opt_state_nbytes() <= total
        # A pure reader assembles the identical tree from the KV.
        reader = ZeroWireUpdater(inner=None, kv=kv, run_id="t",
                                 params=_tree(), optimizer=optimizer,
                                 members=list(range(n)), me=None,
                                 n_shards=n, **kw)
        version, tree = reader.fetch(-1)
        assert version == len(grads)
        _assert_trees_equal(ref, tree)


def test_codec_on_sharded_equals_replicated():
    """Homomorphic topk aggregation upstream, sharded update downstream:
    the collected average is decision-identical (aggregation is delegated
    untouched), so sharded == replicated holds with the codec on."""
    from ps_pytorch_tpu.parallel.async_dp import StaleGradientAggregator

    def collect_avg():
        agg = StaleGradientAggregator(2, compress=True, codec="topk",
                                      topk_frac=0.25)
        outs = []
        for step in range(4):
            for sid, gseed in ((0, 10 + step), (1, 20 + step)):
                agg.submit(sid, step, _grads(1, seed=gseed)[0])
            avg, pool = agg.collect(step)
            assert avg is not None and len(pool["used"]) == 2
            agg.consume(pool["used"])
            outs.append(avg)
        return outs

    avgs = collect_avg()
    kv1, kv4 = KVStore(), KVStore()
    rep = ZeroWireUpdater(inner=None, kv=kv1, run_id="r", params=_tree(),
                          optimizer="sgd", members=[0], me=0, n_shards=1,
                          lr=0.05, momentum=0.9)
    shd = [ZeroWireUpdater(inner=None, kv=kv4, run_id="s", params=_tree(),
                           optimizer="sgd", members=[0, 1, 2, 3], me=m,
                           n_shards=4, lr=0.05, momentum=0.9)
           for m in range(4)]
    for v, avg in enumerate(avgs):
        ref = rep.update_from(avg, version=v + 1)
        for u in shd:
            u.apply_and_publish(avg, version=v + 1)
        got = [u.assemble_round() for u in shd][0]
        _assert_trees_equal(ref, got)


def test_kofn_with_straggler_sharded_equals_replicated():
    """K-of-N (num_aggregate=1 of 2) with a stale straggler: the inner
    pool picks the same contributor either way, so the sharded and
    replicated updates stay bitwise equal."""
    from ps_pytorch_tpu.parallel.async_dp import StaleGradientAggregator

    def pooled_avgs():
        agg = StaleGradientAggregator(2, staleness_limit=4, num_aggregate=1)
        outs = []
        for step in range(5):
            agg.submit(0, step, _grads(1, seed=30 + step)[0])
            if step == 0:       # the straggler submits once, then stalls
                agg.submit(1, 0, _grads(1, seed=99)[0])
            avg, pool = agg.collect(step)
            assert avg is not None
            agg.consume(pool["used"])
            agg.drop_older_than(step)
            outs.append((avg, pool["used"]))
        return outs

    a1 = pooled_avgs()
    a2 = pooled_avgs()
    assert [u for _, u in a1] == [u for _, u in a2]  # same used-sets
    kv1, kv2 = KVStore(), KVStore()
    rep = ZeroWireUpdater(inner=None, kv=kv1, run_id="r", params=_tree(),
                          optimizer="sgd", members=[0], me=0, n_shards=1,
                          lr=0.05, momentum=0.9)
    shd = [ZeroWireUpdater(inner=None, kv=kv2, run_id="s", params=_tree(),
                           optimizer="sgd", members=[0, 1], me=m, n_shards=2,
                           lr=0.05, momentum=0.9) for m in range(2)]
    for v, ((avg, _), (avg2, _)) in enumerate(zip(a1, a2)):
        ref = rep.update_from(avg, version=v + 1)
        for u in shd:
            u.apply_and_publish(avg2, version=v + 1)
        _assert_trees_equal(ref, [u.assemble_round() for u in shd][0])


def test_handoff_adopt_mid_run_bitwise_neutral():
    """4 -> 2 members mid-run: params + optimizer moments move through the
    KV (values moved, never recomputed); the continued run equals the
    never-resharded replicated run bitwise."""
    grads = _grads(6)
    kv = KVStore()
    ups = [ZeroWireUpdater(inner=None, kv=kv, run_id="h", params=_tree(),
                           optimizer="sgd", members=[0, 1, 2, 3], me=m,
                           n_shards=4, lr=0.05, momentum=0.9)
           for m in range(4)]
    for step, g in enumerate(grads[:3]):
        for u in ups:
            u.apply_and_publish(g, version=step + 1)
        trees = [u.assemble_round() for u in ups]
    for u in ups:                       # collective: all handoff first
        u.handoff([0, 2])
    for u in ups:
        u.adopt([0, 2])
    live = [ups[0], ups[2]]
    assert all(u.counters["rebalances"] == 1 for u in ups)
    assert ups[1].opt_state_nbytes() == 0      # leaver went dormant
    for step, g in enumerate(grads[3:]):
        for u in live:
            u.apply_and_publish(g, version=10 + step)
        trees = [u.assemble_round() for u in live]
        _assert_trees_equal(trees[0], trees[1])
    ref, _, _ = _run_sharded(1, grads, "sgd", lr=0.05, momentum=0.9)
    _assert_trees_equal(ref, trees[0])


def test_state_dict_restores_bit_for_bit():
    """Interrupt/restore at the updater level: a fresh updater fed the
    saved params + state_dict continues EXACTLY like the uninterrupted
    one (moments + step are sufficient statistics)."""
    grads = _grads(8)
    for optimizer, kw in (("sgd", dict(lr=0.05, momentum=0.9)),
                          ("adam", dict(lr=0.001))):
        kv = KVStore()
        u = ZeroWireUpdater(inner=None, kv=kv, run_id="c", params=_tree(),
                            optimizer=optimizer, members=[0], me=0,
                            n_shards=4, **kw)
        mid = None
        for step, g in enumerate(grads[:4]):
            mid = u.update_from(g, version=step + 1)
        saved = u.state_dict()
        ref = None
        for step, g in enumerate(grads[4:]):
            ref = u.update_from(g, version=5 + step)
        # "Crash": rebuild from the saved params + optimizer state only.
        u2 = ZeroWireUpdater(inner=None, kv=KVStore(), run_id="c2",
                             params=mid, optimizer=optimizer, members=[0],
                             me=0, n_shards=4, **kw)
        u2.load_state_dict(saved, params=mid)
        got = None
        for step, g in enumerate(grads[4:]):
            got = u2.update_from(g, version=5 + step)
        _assert_trees_equal(ref, got)


# ---------------------------------------------------------------------------
# Satellite: the elastic primitive now rides the armored base85 codec and
# counts shard bytes into wire stats.
# ---------------------------------------------------------------------------

def test_rebalance_uses_armored_base85_and_counts_bytes():
    import base64

    from ps_pytorch_tpu.elastic.rebalance import (
        ShardedKVUpdate, _decode, _encode,
    )
    a = np.arange(1000, dtype=np.float32)
    text = _encode(a)
    assert text == base64.b85encode(a.tobytes()).decode("ascii")
    np.testing.assert_array_equal(_decode(text, np.float32), a)
    assert text == encode_array(a)      # one shard codec, both primitives
    np.testing.assert_array_equal(decode_array(text, np.float32), a)

    kv = KVStore()
    size, members = 1000, [0, 1]
    ups = [ShardedKVUpdate(kv, "rb", size, members, m, lr=0.05, momentum=0.9)
           for m in members]
    p0 = np.random.default_rng(5).standard_normal(size).astype(np.float32)
    for u in ups:
        u.init(p0)
    g = np.random.default_rng(6).standard_normal(size).astype(np.float32)
    for u in ups:
        u.publish(g)
    full = [u.assemble() for u in ups][0]
    np.testing.assert_array_equal(
        full, ShardedKVUpdate.replicated_reference(p0, [g], 0.05, 0.9))
    for u in ups:
        stats = u.wire_stats()
        assert stats["shard_bytes_out"] > 0
        assert u.counters["bytes_out"] > 0
    assert ups[0].wire_stats()["shard_bytes_in"] > 0 or \
        ups[1].wire_stats()["shard_bytes_in"] > 0


# ---------------------------------------------------------------------------
# Satellite: config-time gates — reject what can't hold the bitwise
# guarantee, accept what composes.
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kw,fragment", [
    (dict(shard_update=True), "shard-update"),
    (dict(mode="sync"), "async"),
    (dict(sync_topology="hier", compress_grad=True, grad_codec="int8lat"),
     "flat"),
    (dict(compress_grad=True, grad_codec="int8"), "int8"),
    (dict(lr_schedule="cosine"), "constant"),
])
def test_shard_wire_config_rejections(kw, fragment):
    from ps_pytorch_tpu.config import TrainConfig
    base = dict(mode="async", shard_wire=True)
    base.update(kw)
    with pytest.raises(ValueError, match=fragment):
        TrainConfig(**base)


def test_shard_wire_config_compositions():
    from ps_pytorch_tpu.config import TrainConfig
    TrainConfig(mode="async", shard_wire=True)
    TrainConfig(mode="async", shard_wire=True, compress_grad=True,
                grad_codec="topk", ef=True)          # EF is sender-side
    TrainConfig(mode="async", shard_wire=True, compress_grad=True,
                grad_codec="blosc")                  # lossless wire


# ---------------------------------------------------------------------------
# Trainer integration: sharded checkpoints restore bit-for-bit, including
# across a SIGKILL of the training process.
# ---------------------------------------------------------------------------

def _ms_cfg(train_dir, **kw):
    from ps_pytorch_tpu.config import TrainConfig
    base = dict(dataset="synthetic_mnist", network="LeNet", batch_size=64,
                lr=0.05, momentum=0.9, compute_dtype="float32",
                mode="async", max_steps=4, eval_freq=4, log_every=100,
                train_dir=str(train_dir), shard_wire=True, resume=True)
    base.update(kw)
    return TrainConfig(**base)


def test_multislice_shard_wire_checkpoint_restores_exactly(tmp_path):
    from ps_pytorch_tpu.runtime.multislice import MultiSliceTrainer

    t = MultiSliceTrainer(_ms_cfg(tmp_path), n_slices=2)
    t.train()
    saved = t.aggregator.state_dict()
    p_end = jax.device_get(t.params)

    t2 = MultiSliceTrainer(_ms_cfg(tmp_path, max_steps=8), n_slices=2)
    assert t2.maybe_resume() and t2.step == 4
    _assert_trees_equal(p_end, jax.device_get(t2.params))
    restored = t2.aggregator.state_dict()
    assert restored["step"] == saved["step"]
    assert restored["shards"].keys() == saved["shards"].keys()
    for k, fields in saved["shards"].items():
        for f, arr in fields.items():
            np.testing.assert_array_equal(arr, restored["shards"][k][f])
    t2.train()
    assert t2.step == 8


def test_async_shard_wire_trainer_runs_and_restores(tmp_path):
    from ps_pytorch_tpu.runtime.async_trainer import AsyncTrainer

    cfg = _ms_cfg(tmp_path / "ckpt", batch_size=128, max_steps=6,
                  eval_freq=3, resume=False)
    t = AsyncTrainer(cfg)
    t.train()
    assert t.version == 6 and t.applied == 6
    assert t.aggregator.wire_stats()["zw_bytes_out"] > 0
    assert np.isfinite(t.evaluate(max_batches=1)["loss"])
    saved = t.aggregator.state_dict()
    p_end = jax.device_get(t.params)

    t2 = AsyncTrainer(cfg.replace(resume=True))
    assert t2._maybe_resume() and t2.version == 6
    _assert_trees_equal(p_end, jax.device_get(t2.params))
    restored = t2.aggregator.state_dict()
    assert restored["step"] == saved["step"]
    for k, fields in saved["shards"].items():
        for f, arr in fields.items():
            np.testing.assert_array_equal(arr, restored["shards"][k][f])


_SIGKILL_DRIVER = """
import sys
from ps_pytorch_tpu.config import TrainConfig
from ps_pytorch_tpu.runtime.multislice import MultiSliceTrainer
cfg = TrainConfig(dataset="synthetic_mnist", network="LeNet", batch_size=64,
                  lr=0.05, momentum=0.9, compute_dtype="float32",
                  mode="async", max_steps=500, eval_freq=2, log_every=1000,
                  train_dir=sys.argv[1], shard_wire=True)
MultiSliceTrainer(cfg, n_slices=2).train()
"""


def test_sigkill_then_resume_restores_sharded_state(tmp_path):
    """SIGKILL the training process mid-run (no cleanup, no atexit): the
    committed checkpoint must survive and the sharded optimizer state in
    its extra_state must restore into the resumed trainer bit-for-bit."""
    from ps_pytorch_tpu.runtime import checkpoint as ckpt
    from ps_pytorch_tpu.runtime.multislice import MultiSliceTrainer

    env = dict(os.environ, JAX_PLATFORMS="cpu", JAX_NUM_CPU_DEVICES="8")
    proc = subprocess.Popen(
        [sys.executable, "-c", _SIGKILL_DRIVER, str(tmp_path)],
        cwd=str(REPO), env=env,
        stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL)
    try:
        deadline = time.monotonic() + 300.0
        while time.monotonic() < deadline:
            step = ckpt.latest_step(str(tmp_path))
            if step is not None and ckpt.verify_checkpoint(str(tmp_path),
                                                           step):
                break
            if proc.poll() is not None:
                pytest.fail("training process exited before a checkpoint")
            time.sleep(0.25)
        else:
            pytest.fail("no checkpoint appeared within the deadline")
        proc.send_signal(signal.SIGKILL)
        proc.wait(timeout=30)
    finally:
        if proc.poll() is None:
            proc.kill()

    step = ckpt.latest_step(str(tmp_path))
    # Walk back to the newest checkpoint that verifies (the kill may have
    # landed mid-save of a newer one — that torn write must be skipped,
    # never restored).
    saved_extra = None
    t = MultiSliceTrainer(_ms_cfg(tmp_path, max_steps=0), n_slices=2)
    assert t.maybe_resume(), "no valid checkpoint survived SIGKILL"
    assert t.step >= 2
    saved_extra = ckpt.load_extra_state(str(tmp_path), t.step)
    assert saved_extra and "zero" in saved_extra
    restored = t.aggregator.state_dict()
    assert restored["step"] == int(saved_extra["zero"]["step"])
    for k, fields in saved_extra["zero"]["shards"].items():
        for f, arr in fields.items():
            np.testing.assert_array_equal(
                np.asarray(arr), restored["shards"][k][f])
    # And the run continues from there.
    t2 = MultiSliceTrainer(
        _ms_cfg(tmp_path, max_steps=t.step + 2, eval_freq=0), n_slices=2)
    t2.train()
    assert t2.step == t.step + 2


@pytest.mark.slow
def test_async_two_processes_shard_wire(tmp_path):
    """Launch-driven --shard-wire: two OS processes; params cross the wire
    as per-shard KV keys (the transport canonical payload carries only BN
    stats); the follower contributes gradients and both ends evaluate the
    identical assembled canonical state."""
    from conftest import free_port

    from ps_pytorch_tpu.tools import launch

    ckpt_dir = tmp_path / "ckpt"
    common = [
        "--network", "LeNet", "--dataset", "synthetic_mnist",
        "--batch-size", "128", "--eval-freq", "4",
        "--train-dir", str(ckpt_dir), "--mode", "async",
        "--staleness-limit", "8", "--compute-dtype", "float32",
        "--lr", "0.05", "--log-every", "2", "--shard-wire", "true",
    ]

    def run(run_dir, max_steps, resume):
        rc = launch.main([
            "launch", "--run-dir", str(run_dir), "--simulate", "2",
            "--devices-per-host", "4", "--port", str(free_port()),
            "--entry", str(REPO / "train.py"), "--cwd", str(REPO),
            "--wait", "--timeout", "600",
            "--",
            *common, "--max-steps", str(max_steps), "--resume", resume,
        ])
        logs = [run_dir / f"proc_{i}.log" for i in range(2)]
        dump = "\n\n".join(f"== {l} ==\n{l.read_text()[-3000:]}"
                           for l in logs if l.exists())
        return rc, logs, dump

    rc, logs, dump = run(tmp_path / "run1", 8, "false")
    assert rc == 0, dump
    leader = logs[0].read_text()
    follower = logs[1].read_text()
    assert "FINAL" in leader and "FINAL" in follower, dump
    assert "participating 2" in leader, dump
    assert (ckpt_dir / "model_step_8").is_dir(), dump
    fin_l = [l for l in leader.splitlines() if l.startswith("FINAL")][-1]
    fin_f = [l for l in follower.splitlines() if l.startswith("FINAL")][-1]
    assert fin_l == fin_f, dump

    # Resume from the sharded optimizer-state checkpoint.
    rc2, logs2, dump2 = run(tmp_path / "run2", 12, "true")
    assert rc2 == 0, dump2
    leader2 = logs2[0].read_text()
    assert "RESUME from" in leader2 and "at step 8" in leader2, dump2
    assert (ckpt_dir / "model_step_12").is_dir(), dump2
