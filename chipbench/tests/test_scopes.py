"""Device time by named scope and idle time by engine phase: the XSpace
reader on the trace recorded on a TPU v5e (``fixtures/tiny_engine.xplane.pb``)
and the reductions on hand-made events."""
import pytest

from chipbench.lib import scopes, trace
from chipbench.lib.scopes import Op
from chipbench.lib.trace import Event
from chipbench.tests.test_trace import FIXTURE

ms = 1e-3


def test_tf_op_reader_on_recorded_fixture():
    paths = scopes.tf_op_paths(FIXTURE)
    assert list(paths) == ["/device:TPU:0"]
    jit = [p for p in paths["/device:TPU:0"] if p.startswith("jit(")]
    assert len(jit) >= 300
    assert any(p.startswith("jit(_decode_segment)/while/body/") for p in jit)


def test_ops_match_profile_data():
    planes = scopes.read_planes(FIXTURE)
    devices, _ = trace.load(FIXTURE)
    ours = planes["/device:TPU:0"]["ops"]
    theirs = devices["/device:TPU:0"][scopes.OPS_LINE]
    assert len(ours) == len(theirs) > 10_000
    for a, b in zip(ours, theirs):      # theirs are rounded to the ns
        assert a.start == pytest.approx(b.start, abs=2e-9)
        assert a.end == pytest.approx(b.end, abs=2e-9)
    assert sum(1 for op in ours if op.path) > len(ours) // 2


def test_components_peel_wrappers():
    prog, names = scopes.components(
        "jit(train_step)/transpose(jvp(attention))/ops.matmul/dot_general")
    assert prog == "train_step"
    assert {"attention", "ops.matmul", "dot_general"} <= names
    prog, names = scopes.components(
        "jit(_decode_segment)/while/body/attention/reshape;attention/squeeze")
    assert prog == "_decode_segment" and "attention" in names


def test_scopes_take_the_union_of_nested_ops():
    seg = "jit(_decode_segment)/while/body"
    ops = [Op(0 * ms, 10 * ms, "jit(_decode_segment)/while"),
           Op(1 * ms, 3 * ms, f"{seg}/attention/ops.paged_decode_attention/a"),
           Op(2 * ms, 4 * ms, f"{seg}/attention/ops.paged_decode_attention/b"),
           Op(5 * ms, 6 * ms, f"{seg}/attention/ops.matmul/dot_general"),
           Op(7 * ms, 9 * ms, f"{seg}/sample/sort"),
           Op(9.5 * ms, 12 * ms, "jit(_prefill_one)/lm_head/dot_general"),
           Op(8 * ms, 9 * ms, "")]
    s = scopes.scopes({"/device:TPU:0": {"ops": ops}}, 0.0, 11 * ms)
    dec = s["_decode_segment"]
    # [1,3] ∪ [2,4] = 3 ms, not the 4 ms of the sum
    assert dec["ops.paged_decode_attention"] == pytest.approx(3 * ms)
    assert dec["attention"] == pytest.approx(4 * ms)
    assert dec["sample"] == pytest.approx(2 * ms)
    assert dec["while"] == pytest.approx(10 * ms)
    # clipped to the window's end
    assert s["_prefill_one"]["lm_head"] == pytest.approx(1.5 * ms)
    # the while encloses the others: its leaves are the five inside it
    leaves = scopes.leaves(ops[:5])
    assert ops[0] not in leaves and len(leaves) == 4
    share = scopes.named_share({"p": {"ops": ops[:5]}}, 0.0, 11 * ms,
                               "_decode_segment", ["attention"])
    assert share == pytest.approx(4 / 6)


def test_idle_phases_name_gaps_by_innermost_engine_span():
    ops = [Event("fusion.1", 1 * ms, 3 * ms), Event("copy.2", 6 * ms, 7 * ms),
           Event("fusion.3", 9 * ms, 9.002 * ms)]
    host = [Event(trace.WINDOW, 0.0, 10 * ms),
            Event("bench.step", 0.0, 10 * ms),
            Event("engine.step", 0.5 * ms, 9.5 * ms),
            Event("engine.harvest", 3.2 * ms, 5.8 * ms),
            Event("np.asarray(jax.Array)", 4 * ms, 5 * ms),
            Event("engine.wait", 7 * ms, 9 * ms)]
    got = dict(scopes.idle_phases({"/device:TPU:0": {"XLA Ops": ops}}, host))
    # [0,1] mid 0.5 → engine.step; [3,6] mid 4.5 → engine.harvest (the
    # runtime's event inside it is no phase); [7,9] → engine.wait;
    # [9.002,10] mid 9.501 → bench.step
    assert got["engine.step"] == pytest.approx(1 * ms)
    assert got["engine.harvest"] == pytest.approx(3 * ms)
    assert got["engine.wait"] == pytest.approx(2 * ms)
    assert got["bench.step"] == pytest.approx(0.998 * ms)
    host = [Event(trace.WINDOW, 0.0, 10 * ms)]
    got = dict(scopes.idle_phases({"/device:TPU:0": {"XLA Ops": ops}}, host))
    assert got == {scopes.NO_PHASE: pytest.approx(6.998 * ms)}
