"""The `device_kinds` reader: its arithmetic on plain lists, the need of
ResNet's convolutions, and the file's layout on the trace recorded on
the chip and reduced under `cellbench/testdata/` (`record_kinds.py`)."""
import json
import os

import pytest

from cellbench import run
from cellbench.ops import resnet, resnet_convs
from cellbench.readers import device_kinds as dk
from cellbench.readers import device_scope as ds
from cellbench.testdata import record_kinds

HERE = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "testdata")
KIND = "TPU v5 lite"
J = "jit(step_with_metric)/"

# one training step and one decode step; times in ns
OPS = [(J + "jvp(train.fwd)/train.cast/convert_element_type:", 0.0, 10.0),
       (J + "jvp(train.fwd)/conv0/op.Convolution/conv_general_dilated:",
        10.0, 40.0),
       (J + "jvp(train.fwd)/bn0/op.BatchNorm/mul:", 50.0, 20.0),
       (J + "transpose(jvp(train.fwd))/bn0/op.BatchNorm/mul:", 70.0, 30.0),
       (J + "transpose(jvp(train.fwd))/conv0/op.Convolution/transpose:",
        100.0, 60.0),
       (J + "transpose(jvp(train.fwd))/conv0/op.Convolution/"
        "conv_general_dilated:", 160.0, 20.0),
       (J + "train.update/mul:", 180.0, 10.0),
       (J + "train.metric/add:", 190.0, 5.0),
       (J + "broadcast_in_dim:", 195.0, 5.0),
       ("", 200.0, 10.0),
       ("jit(decode_step)/layer1_mamba/op._contrib_Mamba2Cached/"
        "mamba2.step/vmap()/mul:", 300.0, 25.0),
       ("jit(decode_step)/transpose(jvp(fc))/op.FullyConnected/"
        "dot_general:", 325.0, 15.0)]
CATS = ["loop fusion", "convolution fusion", "loop fusion", "loop fusion",
        "convolution fusion", "convolution fusion", "loop fusion",
        "loop fusion", "", "copy", "loop fusion", "convolution fusion"]
MODULES = [("jit_step_with_metric(3)", 0.0, 210.0),
           ("jit_decode_step(9)", 300.0, 40.0)]
VIEW = {"ops": OPS, "categories": CATS, "modules": MODULES,
        "prefills": []}
BUSY = 250e-9


def test_parts_take_the_wrappers_off():
    assert dk.parts(J + "transpose(jvp(train.fwd))/conv0/"
                    "op.Convolution/transpose:") == (
        "jit(step_with_metric)", "train.fwd", "conv0", "op.Convolution",
        "transpose")
    assert dk.parts("jit(f)/a/vmap()/mul:") == ("jit(f)", "a", "", "mul")
    assert dk.is_backward(OPS[4][0]) and not dk.is_backward(OPS[1][0])
    # an operator called transpose is no transform
    assert not dk.is_backward("jit(f)/t0/op.transpose/transpose:")


def test_kind_and_node_of_an_operation():
    assert [dk.kind_of(op[0]) for op in OPS] == [
        "train.cast", "op.Convolution", "op.BatchNorm", "op.BatchNorm",
        "op.Convolution", "op.Convolution", "train.update",
        "train.metric", None, None, "op._contrib_Mamba2Cached",
        "op.FullyConnected"]
    assert [dk.node_of(op[0]) for op in OPS[:3]] == [None, "conv0",
                                                     "bn0"]
    # a hand-placed scope is no kind; a node that took the wrapper
    assert dk.node_of(OPS[10][0]) == "layer1_mamba"
    assert dk.node_of(OPS[11][0]) == "fc"
    # the outermost node where graphs nest
    assert dk.kind_of("jit(f)/loop/op._foreach/body_fc/"
                      "op.FullyConnected/dot:") == "op._foreach"
    assert dk.node_of("jit(f)/mul:") is None


def test_a_set_of_scopes_and_its_complement():
    named = dk.under_any(OPS, dk.KINDS)
    loose = dk.outside(OPS, dk.KINDS)
    assert len(named) == 10 and len(loose) == 2
    assert sorted(named + loose) == sorted(OPS)
    assert dk.share(named, BUSY) + dk.share(loose, BUSY) == \
        pytest.approx(100.0)
    assert dk.share(loose, BUSY) == pytest.approx(100 * 15 / 250)
    # `train.fwd` alone is found under its wrapper
    assert len(dk.under_any(OPS, ["train.fwd"])) == 6
    # plain parts select what `device_scope.under` selects
    for scope in ("op.Convolution", "op.", "train.update", "mamba2.step",
                  "mamba2."):
        assert dk.under_any(OPS, [scope]) == ds.under(OPS, scope), scope
    assert dk.under_any(OPS, ["op.Conv"]) == []       # a whole part
    assert dk.under_any(OPS, ["op.Convolution", "op.BatchNorm"]) == \
        OPS[1:6]
    assert dk.share([], BUSY) is None


def test_forward_and_backward_apart():
    conv = dk.under_any(OPS, ["op.Convolution"])
    assert dk.one_way(conv, "forward") == [OPS[1]]
    assert dk.one_way(conv, "backward") == [OPS[4], OPS[5]]
    assert dk.one_way(conv, None) == conv
    assert dk.share(dk.one_way(conv, "forward"), BUSY) == \
        pytest.approx(100 * 40 / 250)
    assert dk.share(dk.one_way(conv, "backward"), BUSY) == \
        pytest.approx(100 * 80 / 250)
    with pytest.raises(ValueError):
        dk.one_way(conv, "sideways")


def test_the_two_tables():
    t = dk.tables(VIEW)
    kinds = t["device_by_kind"]["kinds"]
    assert t["device_by_kind"]["busy_s"] == pytest.approx(BUSY)
    assert list(kinds)[0] == "op.Convolution"         # most seconds first
    assert kinds["op.Convolution"] == pytest.approx(
        [3, 40e-9, 80e-9, 120e-9])
    assert kinds["op.BatchNorm"] == pytest.approx([2, 20e-9, 30e-9, 0.0])
    assert kinds["train.update"] == pytest.approx([1, 10e-9, 0.0, 0.0])
    assert kinds[dk.UNSCOPED] == pytest.approx([2, 15e-9, 0.0, 0.0])
    assert sum(r[1] + r[2] for r in kinds.values()) == \
        pytest.approx(BUSY)
    nodes = t["device_by_node"]
    assert nodes[0][:2] == ["conv0", "op.Convolution"]
    assert nodes[0][2:] == pytest.approx([40e-9, 80e-9])
    assert [r[0] for r in nodes] == ["conv0", "bn0", "layer1_mamba", "fc"]
    assert dk.by_node(OPS, top=1) == nodes[:1]
    assert t["unscoped_by_category"] == {
        "copy": [1, pytest.approx(10e-9)],
        "_none_": [1, pytest.approx(5e-9)]}


def test_the_need_comes_from_the_module_the_metric_names(capsys):
    readings = {"trace": {"busy_s": BUSY}, "_device_kinds": VIEW,
                "device_kind": KIND, "cfg": {}, "traffic": {}}
    got = dk.read(readings, "roofline", scope="op.Convolution",
                  module="step_with_metric",
                  need_module="cellbench.tests.test_op_scopes",
                  need="a_need")
    # 8190 bytes = 10 ns at 819 GB/s, over the step's 120 ns of convs
    assert got == pytest.approx(100 * 10 / 120)
    line = [ln for ln in capsys.readouterr().out.splitlines()
            if ln.startswith("cellbench: device_kinds_need ")][0]
    said = json.loads(line.split(" ", 2)[2])
    assert said["least_s_by_bytes"] == pytest.approx(10e-9)
    assert said["least_s_by_operations"] == pytest.approx(197 / 197e12)
    assert dk.read(readings, "roofline", scope="op.Pooling",
                   module="step_with_metric",
                   need_module="cellbench.tests.test_op_scopes",
                   need="a_need") is None
    with pytest.raises(ValueError):
        dk.read(readings, "nonsense")


def a_need(cfg, traffic):
    assert cfg == {} and traffic == {}
    return 197, 8190


def test_read_shares_and_nothing_on_a_program_without_scopes():
    readings = {"trace": {"busy_s": BUSY}, "_device_kinds": VIEW}
    assert dk.read(readings, "outside_share", scopes=["op.", "train."]) \
        == pytest.approx(100 * 15 / 250)
    assert dk.read(readings, "set_share", scopes=["op.Convolution",
                                                  "op.BatchNorm"]) \
        == pytest.approx(100 * 170 / 250)
    assert dk.read(readings, "set_share", scopes=["op.Convolution"],
                   direction="backward") == pytest.approx(100 * 80 / 250)
    assert dk.read(readings, "set_share", scopes=["op.Pooling"]) is None
    # names that cover the whole program read 0, not nothing
    covered = dict(readings, _device_kinds=dict(
        VIEW, ops=OPS[:8], categories=CATS[:8]))
    assert dk.read(covered, "outside_share", scopes=dk.KINDS) == 0.0
    # the parent: no trace, or a trace whose operations carry no kind
    assert dk.read({}, "outside_share", scopes=dk.KINDS) is None
    bare = [("jit(step_with_metric)/jvp()/mul:", 0.0, 10.0),
            ("jit(step_with_metric)/conv_general_dilated:", 10.0, 20.0)]
    parent = {"trace": {"busy_s": 30e-9}, "device_kind": KIND,
              "cfg": {}, "traffic": {},
              "_device_kinds": {"ops": bare, "categories": ["", ""],
                                "modules": MODULES[:1], "prefills": []}}
    assert dk.read(parent, "outside_share", scopes=dk.KINDS) is None
    assert dk.read(parent, "roofline", scope="op.Convolution",
                   module="step_with_metric",
                   need_module="cellbench.tests.test_op_scopes",
                   need="a_need") is None


# -- the need of ResNet's convolutions --------------------------------------

RESNET50 = {"num_layers": 50, "image_size": 224, "num_classes": 1000}


def test_resnet50_convolutions_by_hand():
    layers = resnet_convs.conv_layers(RESNET50)
    assert len(layers) == 53               # 1 + 16 x 3 + 4 shortcuts
    by = {name: (shape, a, b) for name, shape, a, b in layers}
    assert by["conv0_weight"] == ((64, 3, 7, 7), 224, 112)
    assert by["stage1_unit1_conv1_weight"] == ((64, 64, 1, 1), 56, 56)
    assert by["stage1_unit1_sc_weight"] == ((256, 64, 1, 1), 56, 56)
    # a unit that strides: the 1x1 reads the larger map, the 3x3 and
    # the shortcut halve it, the last 1x1 runs on the smaller one
    assert by["stage2_unit1_conv1_weight"] == ((128, 256, 1, 1), 56, 56)
    assert by["stage2_unit1_conv2_weight"] == ((128, 128, 3, 3), 56, 28)
    assert by["stage2_unit1_sc_weight"] == ((512, 256, 1, 1), 56, 28)
    assert by["stage2_unit1_conv3_weight"] == ((512, 128, 1, 1), 28, 28)
    assert by["stage4_unit3_conv2_weight"] == ((512, 512, 3, 3), 7, 7)
    # the same multiply-adds as the accepted count, less the classifier
    assert sum(resnet._conv_macs(s, b) for _n, s, _a, b in layers) == \
        resnet.forward_macs(RESNET50) - 2048 * 1000
    flops, nbytes = resnet_convs.convs_step_need(
        RESNET50, {"batch_per_chip": 256})
    assert flops == 3 * 2 * (resnet.forward_macs(RESNET50) -
                             2048 * 1000) * 256
    # conv0 alone: 3 passes of (3 x 224^2 + 64 x 112^2) x 256 + weights
    one = resnet_convs._elements((64, 3, 7, 7), 224, 112)
    assert one == (3 * 224 * 224 + 64 * 112 * 112, 64 * 3 * 49)
    maps = sum(resnet_convs._elements(s, a, b)[0]
               for _n, s, a, b in layers)
    weights = sum(resnet_convs._elements(s, a, b)[1]
                  for _n, s, a, b in layers)
    assert 23.4e6 < weights < 23.6e6       # 25.6 M less norms and head
    assert nbytes == 3 * 2 * (maps * 256 + weights)
    # on a v5e the bytes bound: 41.0 ms against 31.9
    assert ds.least_seconds(flops, 0, KIND) == pytest.approx(0.03187,
                                                             rel=1e-3)
    assert ds.least_seconds(0, nbytes, KIND) == pytest.approx(0.04101,
                                                              rel=1e-3)


def test_the_conv_shapes_are_the_programs():
    """Each convolution's output side against the shapes the program's
    own symbol infers, at the toy size of `cellbench/tests/toy.py`."""
    from cellbench.tests import toy
    from mxnet_tpu import models
    side = toy.RESNET["image_size"]
    sym = models.get_symbol(network="resnet", num_layers=50,
                            image_shape=(3, side, side),
                            num_classes=toy.RESNET["num_classes"])
    inner = sym.get_internals()
    _, shapes, _ = inner.infer_shape(data=(2, 3, side, side),
                                     softmax_label=(2,))
    got = dict(zip(inner.list_outputs(), shapes))
    layers = resnet_convs.conv_layers(toy.RESNET)
    assert len(layers) == 53
    for name, shape, _a, b in layers:
        node = name[:-len("_weight")]
        assert got[node + "_output"] == (2, shape[0], b, b), name


def test_the_five_metrics_point_at_their_files():
    manifest = run.load_json(run.ROOT, "BENCHMARK.json")
    names = ["conv_device_share.train", "batchnorm_device_share.train",
             "update_device_share.train", "unscoped_device_share.train",
             "conv_roofline.train"]
    mine = [m for m in manifest["per_layer"] if m["name"] in names]
    assert [m["name"] for m in mine] == names
    for m in mine:
        spec = run.load_json(run.HERE, "metrics", m["name"] + ".json")
        assert m["source"] == "device_trace"
        assert m["workloads"] == ["resnet-50.train"]
        assert spec["reader"] in ("device_scope", "device_kinds")
        if "need_module" in spec["args"]:
            fn = dk.need_from(spec["args"]["need_module"],
                              spec["args"]["need"], RESNET50,
                              {"batch_per_chip": 256})
            assert fn()[0] > 6e12


# -- the recorded trace -----------------------------------------------------

@pytest.fixture(scope="module")
def recorded():
    path = os.path.join(HERE, "kinds.xplane.pb")
    with open(os.path.join(HERE, "kinds.expected.json")) as f:
        return path, json.load(f)


def _same(got, want, key):
    if isinstance(got, float):
        assert got == pytest.approx(want, rel=1e-9), key
    elif isinstance(got, dict):
        assert list(got) == list(want), key
        for k in got:
            _same(got[k], want[k], key + "." + k)
    elif isinstance(got, (list, tuple)):
        assert len(got) == len(want), key
        for i, (a, b) in enumerate(zip(got, want)):
            _same(a, b, "%s[%d]" % (key, i))
    else:
        assert got == want, key


def test_the_recorded_trace_reads_as_it_did(recorded):
    path, want = recorded
    for key, value in record_kinds.expected(path).items():
        _same(value, want[key], key)


def test_the_recorded_trace_by_other_routes(recorded):
    """What the chip ran: one epoch of `BATCHES` steps, each an
    execution of `jit_step_with_metric`; every operator of the toy net
    forward, the differentiated ones backward; the convolutions in
    convolution fusions; the names cover nearly all of it."""
    path, want = recorded
    v = dk.load(path)
    assert want["steps"] == record_kinds.BATCHES
    assert "jit_step_with_metric" in want["modules"]
    assert "convolution fusion" in want["categories"]
    kinds = want["device_by_kind"]
    for kind in ("op.Convolution", "op.BatchNorm", "train.update"):
        ops, fwd, bwd, _conv = kinds[kind]
        assert ops > 0 and fwd + bwd > 0, kind
    assert kinds["op.Convolution"][1] > 0 and \
        kinds["op.Convolution"][2] > 0
    assert kinds["train.update"][2] == 0.0      # never under a transpose
    # most of the convolutions' seconds are in convolution fusions, and
    # most convolution-fusion seconds are the convolutions'
    conv = kinds["op.Convolution"]
    assert conv[3] > 0.5 * (conv[1] + conv[2])
    assert conv[3] >= 0.5 * sum(r[3] for r in kinds.values())
    assert {r[0] for r in want["device_by_node"]} >= {"conv1", "conv2",
                                                      "bn1", "bn2"}
    assert want["named_share"] + want["outside_share"] == \
        pytest.approx(100.0, rel=1e-6)
    assert want["outside_share"] < 20.0
    assert want["conv_forward_share"] + want["conv_backward_share"] == \
        pytest.approx(want["conv_share"], rel=1e-6)
    assert 0 < want["conv_roofline"] <= 100
    # the scope's seconds are those of the operations that carry it
    by_hand = sum(d for s, _t, d in v["ops"] if "/op.Convolution/" in s)
    assert by_hand * 1e-9 == pytest.approx(
        want["conv_share"] * want["busy_s"] / 100.0, rel=1e-6)
    # the existing reader sees the same operations and the same share
    old = ds.load(path)
    assert old["ops"] == v["ops"] and old["modules"] == v["modules"]
    assert 100.0 * ds.scope_seconds(old["ops"], "op.Convolution") / \
        want["busy_s"] == pytest.approx(want["conv_share"], rel=1e-9)
