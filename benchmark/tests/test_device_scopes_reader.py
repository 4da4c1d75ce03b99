"""``readers/device_scopes.py``: the wire-format decoder and the attribution
on the one TPU xplane the repo has (``profile_r04``: ResNet-18, recorded before
the program had scopes, so flax's module names stand in for the vocabulary),
and on events built by hand where the arithmetic can be checked by eye."""

import os
import re
import struct
from types import SimpleNamespace as NS

import pytest

import harness
import trace_reduce as tr
from conftest import BENCH, ROOT

ds = harness.load_module(os.path.join(BENCH, "readers", "device_scopes.py"))
XPLANE = os.path.join(ROOT, "profile_r04", "plugins", "profile",
                      "2026_07_30_19_43_35", "vm.xplane.pb")
MODULES = ("conv1", "bn1", "linear") + tuple(f"BasicBlock_{i}"
                                             for i in range(8))
# The stat is TensorFlow's ``<op name>:<op type>``; JAX leaves the type empty.
TF_OP = re.compile(rb"jit\(\w+\)/[\x20-\x7e]+?:")


@pytest.fixture(scope="module")
def recorded():
    events, metadata = ds.device_ops(XPLANE)
    chip = tr.load(XPLANE).chip(0)
    return events, metadata, chip, chip.steady_window()


def test_the_decoder_finds_every_tf_op_the_planes_bytes_hold(recorded):
    events, metadata, _, _ = recorded
    assert len(events) == 4161
    with open(XPLANE, "rb") as f:
        buf = memoryview(f.read())
    spans = [v for n, _, v in ds.fields(buf) if n == 1]
    plane, = [v for v in spans if any(
        n == 2 and ds._text(buf, x) == "/device:TPU:0"
        for n, _, x in ds.fields(buf, *v))]
    in_bytes = {m.decode() for m in TF_OP.findall(bytes(buf[plane[0]:plane[1]]))}
    decoded = {m["tf_op"] for m in metadata.values() if m.get("tf_op")}
    assert decoded == in_bytes and len(decoded) > 100
    assert all(name.endswith(":") for name in decoded)
    named = [m for _, _, m in events if metadata[m].get("tf_op")]
    assert len(named) == 711
    # the names the other readers match are the ones ProfileData gives
    by_text = {text for text, _, _ in tr.load(XPLANE).chip(0).ops}
    assert {metadata[m]["name"] for _, _, m in events} == by_text
    some = next(m for m in metadata.values() if "BasicBlock_3/Conv_1" in
                (m.get("tf_op") or ""))
    assert float(some["flops"]) > 0 and float(some["bytes_accessed"]) > 0
    assert some["hlo_category"]


def test_the_decoders_clock_is_profile_datas(recorded):
    events, _, chip, _ = recorded
    mine = sorted((s, e) for s, e, _ in events)
    theirs = sorted((s, e) for _, s, e in chip.ops)
    assert [t for ev in mine for t in ev] == pytest.approx(
        [t for ev in theirs for t in ev], abs=3e-9)     # theirs: whole ns


def test_recorded_trace_by_module_adds_up_to_busy(recorded):
    events, metadata, chip, (lo, hi, periods) = recorded
    rows = ds.table(events, metadata, (lo, hi), MODULES)
    busy = chip.busy_s((lo, hi))
    assert sum(ds.seconds(r) for r in rows.values()) == \
        pytest.approx(busy, rel=1e-3)
    blocks = sum(ds.seconds(r) for n, r in rows.items()
                 if n.startswith("BasicBlock_"))
    # what ISSUE 33 read off the whole trace: 84.0 of 87.7 ms in the blocks
    assert blocks / busy == pytest.approx(0.958, abs=0.005)
    assert 1e3 * ds.seconds(rows["conv1"]) / periods == \
        pytest.approx(0.61, abs=0.02)
    assert ds.seconds(rows[ds.UNSCOPED]) / busy == pytest.approx(0.021, abs=0.002)
    no_tf_op = sum(t for t, _, m in ds.self_seconds(events, lo, hi)
                   if not metadata[m].get("tf_op"))
    assert no_tf_op / busy == pytest.approx(0.019, abs=0.001)
    # forward and backward by ``transpose(``; nothing was rematerialised
    for name in MODULES[3:]:
        assert rows[name]["backward"] > rows[name]["forward"] > 0
        assert rows[name]["recompute"] == 0
    assert rows[ds.UNSCOPED]["backward"] < 1e-6     # a transpose(jvp()) or two
    top_s, top = rows["BasicBlock_1"]["top"]
    assert tr.describe(top) == \
        "convert_reduce_fusion.7 kOutput bf16[1024,32,32,64]"
    lines = ds.lines(rows, periods, busy)
    assert lines[0].startswith("DEVICE_BY_SCOPE BasicBlock_1: fwd 1.75 bwd "
                               "4.21 recompute 0.00 ms/step, 20.4% of busy, ")
    assert lines[-1] == ("DEVICE_BY_SCOPE total 29.22 of busy 29.22 ms/step "
                         "over 2 steps of chip 0")
    assert len(lines) == len(rows) + 1


def test_scope_and_part_of_a_name_stack():
    vocab = ("loss", "attn_core", "moe_dispatch", "moe_experts")
    cases = {
        "jit(local_step)/jvp(loss)/reduce_sum": ("loss", "forward"),
        "jit(local_step)/transpose(jvp(loss))/mul": ("loss", "backward"),
        "jit(s)/transpose(jvp(LM))/jvp(LM)/checkpoint/block_1/attn_core/dot":
            ("attn_core", "backward"),
        "jit(s)/transpose(jvp(LM))/jvp(LM)/checkpoint/rematted_computation/"
        "block_1/attn_core/mul": ("attn_core", "recompute"),
        # under two names of the vocabulary: the innermost
        "jit(s)/jvp(LM)/block_2/moe/moe_dispatch/cond/branch_1_fun/"
        "moe_experts/pallas_call": ("moe_experts", "forward"),
        # XLA joined two ops: the first one's name stands
        "jit(s)/jvp(loss)/exp;jit(s)/jvp(LM)/head/dot": ("loss", "forward"),
        # a module that merely contains a scope's letters is not the scope
        "jit(s)/jvp(LM)/block_0/loss_scale/mul": (ds.UNSCOPED, "forward"),
        "jit(s)/jvp()/iota": (ds.UNSCOPED, "forward"),
        "": (ds.UNSCOPED, "forward"),
        None: (ds.UNSCOPED, "forward"),
    }
    for name, want in cases.items():
        assert ds.scope_of(name, vocab) == want, name


def test_a_while_events_body_counts_once():
    """A ``while`` op 0-10 us whose body's three ops cover 1-3, 4-6 and 7-9,
    then a plain op 12-15: the loop keeps 4 us, busy is 13."""
    events = [(0, 10e-6, 1), (1e-6, 3e-6, 2), (4e-6, 6e-6, 2), (7e-6, 9e-6, 3),
              (12e-6, 15e-6, 4)]
    metadata = {
        1: {"name": "%while.1 = (s32[]) while(...)", "tf_op": "jit(s)/jvp(loss)/while",
            "flops": "1000"},
        2: {"name": "%dynamic-slice.1 = f32[8] dynamic-slice(...)",
            "tf_op": "jit(s)/jvp(loss)/while/body/dynamic_slice", "flops": "7",
            "bytes_accessed": "64"},
        3: {"name": "%fusion.2 = f32[8] fusion(...), kind=kLoop",
            "tf_op": "jit(s)/jvp(LM)/head/lm_head/dot_general"},
        4: {"name": "%copy.9 = f32[8] copy(...)"},
    }
    own = ds.self_seconds(events, 0, 20e-6)
    assert [(round(t * 1e6, 6), kids) for t, kids, _ in own] == \
        [(4, True), (2, False), (2, False), (2, False), (3, False)]
    rows = ds.table(events, metadata, (0, 20e-6), ("loss", "head"))
    assert rows["loss"]["forward"] == pytest.approx(8e-6)
    assert rows["head"]["forward"] == pytest.approx(2e-6)
    assert rows[ds.UNSCOPED]["forward"] == pytest.approx(3e-6)
    assert sum(ds.seconds(r) for r in rows.values()) == pytest.approx(13e-6)
    # the loop's own flops would count its body twice
    assert rows["loss"]["flops"] == 14 and rows["loss"]["bytes"] == 128
    assert len(rows["loss"]["ops"]) == 2
    # the window cuts events, and an empty one holds nothing
    cut = ds.table(events, metadata, (5e-6, 8e-6), ("loss", "head"))
    assert cut["loss"]["forward"] == pytest.approx(2e-6)
    assert cut["head"]["forward"] == pytest.approx(1e-6)
    assert ds.table(events, metadata, (30e-6, 40e-6), ("loss",)) == {}


# ---- a hand-built xplane file, and the reader's ``read`` over it ----------

def _varint(v):
    out = bytearray()
    while True:
        out.append((v & 0x7F) | (0x80 if v > 0x7F else 0))
        v >>= 7
        if not v:
            return bytes(out)


def msg(*pairs):
    """Protobuf bytes of (field number, int | bytes | str | float) pairs."""
    out = b""
    for number, v in pairs:
        if isinstance(v, int):
            out += _varint(number << 3) + _varint(v)
        elif isinstance(v, float):
            out += _varint(number << 3 | 1) + struct.pack("<d", v)
        else:
            v = v.encode() if isinstance(v, str) else v
            out += _varint(number << 3 | 2) + _varint(len(v)) + v
    return out


def xspace(ops_line_events, step_ms=10, steps=5):
    """One ``/device:TPU:0`` plane, after a host plane to skip: each step a
    module event and the given (metadata id, offset ms, ms) op events."""
    stat_names = {1: "tf_op", 2: "flops", 3: "bytes_accessed",
                  4: "jit(step)/jvp(LM)/block_0/attn_core/flash_fwd"}
    metas = {
        1: ("%flash_fwd.1 = bf16[8,8] custom-call(...)", [(1, 7, 4), (2, 3, 2000)]),
        2: ("%fusion.3 = f32[8] fusion(...), kind=kLoop",
            [(1, 5, "jit(step)/transpose(jvp(LM))/head/ln_f/mul"), (3, 5, "512")]),
        3: ("%copy-start.1 = f32[8] copy-start(...)", []),
        9: ("jit_step(1)", []),
    }
    ms = 10 ** 9        # picoseconds
    ops, mods = [], []
    for k in range(steps):
        mods.append(msg((1, 9), (2, k * step_ms * ms), (3, (step_ms - 1) * ms)))
        for meta, off, dur in ops_line_events:
            ops.append(msg((1, meta), (2, int((k * step_ms + off) * ms)),
                           (3, int(dur * ms))))
    plane = msg(
        (1, 7), (2, "/device:TPU:0"),
        (3, msg((1, 1), (2, "XLA Modules"), (3, 1000),
                *[(4, e) for e in mods])),
        (3, msg((1, 2), (2, "XLA Ops"), (3, 1000), *[(4, e) for e in ops])),
        *[(5, msg((1, i), (2, msg((1, i), (2, name)))))
          for i, name in stat_names.items()],
        *[(4, msg((1, i), (2, msg((1, i), (2, name), *[
            (5, msg((1, stat), (field, value))) for stat, field, value in stats]))))
          for i, (name, stats) in metas.items()])
    host = msg((1, 1), (2, "/host:CPU"),
               (3, msg((2, "main"), (4, msg((1, 1), (2, 5), (3, 6))))))
    return msg((1, host), (1, plane))


@pytest.fixture
def run_over(tmp_path, monkeypatch):
    """A ``harness.Run`` over a hand-built xplane, with the reader pointed at
    the file and at a vocabulary."""
    def make(ops, vocab=("attn_core", "head")):
        path = tmp_path / "t.xplane.pb"
        path.write_bytes(xspace(ops))
        monkeypatch.setattr(ds, "vocabulary", lambda: vocab)
        monkeypatch.setattr(tr, "find_xplane", lambda d: str(path))
        said = []
        return harness.Run(trace=tr.load(str(path)), say=said.append), said
    return make


def test_read_over_a_hand_built_file(run_over):
    """Steps of 10 ms: a 6 ms kernel under attn_core, a 2 ms fusion under
    head's backward, a 1 ms copy with no name: the steady window holds 3."""
    run, said = run_over([(1, 0, 6), (2, 6, 2), (3, 8.5, 1)])
    assert run.steady()[2] == 3
    assert ds.read(run, "attn_core", "step_ms") == pytest.approx(6)
    assert ds.read(run, "head", "step_ms", part="backward") == pytest.approx(2)
    assert ds.read(run, "head", "step_ms", part="forward") is None
    assert ds.read(run, ["attn_core", "head"], "step_ms") == pytest.approx(8)
    assert ds.read(run, "all", "step_ms", part="recompute") is None
    assert ds.read(run, "unscoped", "busy_share") == pytest.approx(100 / 9)
    assert ds.read(run, "moe_route", "step_ms") is None     # nothing under it
    with pytest.raises(ValueError):
        ds.read(run, "head", "per_fortnight")
    # one table a run, however many metrics read it
    table = [line for line in said if line.startswith("DEVICE_BY_SCOPE")]
    assert len(table) == 4
    assert table[0].startswith(
        "DEVICE_BY_SCOPE attn_core: fwd 6.00 bwd 0.00 recompute 0.00 ms/step, "
        "66.7% of busy, 1 ops, 0.0 GFLOP, 0 MB; top: flash_fwd.1 custom-call "
        "bf16[8,8] 6.00")
    assert "head: fwd 0.00 bwd 2.00" in table[1] and "0 MB" in table[1]
    assert table[2].startswith("DEVICE_BY_SCOPE unscoped: fwd 1.00 ") and \
        table[2].endswith("copy-start f32[8] 1.00; 1.00 with no tf_op")
    assert re.match(r"DEVICE_BY_SCOPE total 9\.00 of busy 9\.00 ms/step over "
                    r"3 steps of chip 0; read in \d+\.\d\d s$", table[3])


def test_read_gives_none_without_scopes_or_without_a_trace(run_over):
    run, said = run_over([(1, 0, 6)], vocab=None)   # a program before PR 33
    assert ds.read(run, "attn_core", "step_ms") is None and said == []
    run, _ = run_over([(1, 0, 6)])
    run.trace = None
    assert ds.read(run, "attn_core", "step_ms") is None
    # a trace with one run of the step program has no steady window
    one = harness.Run(trace=tr.from_profile_data(NS(planes=[NS(
        name="/device:TPU:0", lines=[
            NS(name="XLA Ops", events=[NS(name="%a = f32[] add()",
                                          start_ns=0, duration_ns=10)]),
            NS(name="XLA Modules", events=[NS(name="jit_step(1)", start_ns=0,
                                              duration_ns=10)])])])),
        say=print)
    assert ds.read(one, "attn_core", "step_ms") is None


def test_every_dev_metric_file_names_this_reader_and_a_scope():
    from ps_pytorch_tpu.telemetry.trace import DEVICE_SCOPES
    bench = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    dev = [m for m in bench["per_layer"] if m["name"].startswith("dev_")]
    assert len(dev) == 15
    assert bench["per_layer"][-len(dev):] == dev    # appended, in one piece
    for m in dev:
        spec = harness.load_json(os.path.join(BENCH, "layer_metrics",
                                              m["name"] + ".json"))
        assert spec["reader"] == "device_scopes"
        scope = spec["params"]["scope"]
        assert scope in DEVICE_SCOPES + ("all", ds.UNSCOPED)
        assert m["source"] == "device_trace" and m["better"] == "lower"
        assert (m["unit"] == "%") == (spec["params"]["per"] == "busy_share")


def test_as_a_script_it_prints_the_table(capsys, monkeypatch):
    monkeypatch.setattr(ds, "vocabulary", lambda: MODULES)
    assert ds.main(["device_scopes.py", os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.dirname(XPLANE))))]) == 0
    out = capsys.readouterr().out.splitlines()
    assert out[0].startswith("DEVICE_BY_SCOPE BasicBlock_1: ")
    assert out[-1].startswith("DEVICE_BY_SCOPE total 29.22 of busy 29.22")
    assert ds.main(["device_scopes.py", os.path.dirname(__file__)]) == 1
    assert ds.main(["device_scopes.py"]) == 2
