"""The trace reduction: on traces built in the XSpace text form with
known intervals; on a small trace recorded on a TPU v5e
(`record_testdata.py`: granite-moe-3b-a800m cut to two layers, served
for half a second); and the name it finds the grouped-matmul kernel by,
against the engine's decode wave compiled for a described TPU v5e."""
import collections
import os
import re
from pathlib import Path

import pytest

from chipbench import trace_reduce as T

RECORDED = Path(__file__).resolve().parents[1] / "testdata" / \
    "small.xplane.pb.gz"
RECORDED_LAYERS = 2


def test_no_window_gives_nothing(tmp_path):
    import jax
    import jax.numpy as jnp
    with jax.profiler.trace(str(tmp_path)):
        jnp.ones(3).block_until_ready()
    assert T.reduce(T.find_trace(tmp_path)) is None


def test_union_name_and_family():
    assert T._union([(0, 2), (1, 3), (5, 6)]) == [(0, 3), (5, 6)]
    assert T.op_family("fusion.123") == "fusion"
    assert T.op_family("copy-start.4") == "copy-start"
    assert T.op_family("dynamic-slice_bitcast_fusion.14.remat") == \
        "dynamic-slice_bitcast_fusion"
    assert T.op_name("%moe_gmm_pallas.7 = bf16[40,40,1536]{2,1,0} "
                     "custom-call(bf16[40,40,1536]{2,1,0} %pad.64), "
                     'custom_call_target="tpu_custom_call"') == \
        "moe_gmm_pallas.7"
    assert T.op_name("fusion.12") == "fusion.12"


def _xspace(device_ops, host_spans):
    """A trace in XSpace text form: device ops (name, start_ns, dur_ns) on
    /device:TPU:0, host spans (name, start_ns, dur_ns) on /host:CPU."""
    def esc(n):
        return n.replace('"', '\\"')

    def plane(pid, name, line, events):
        names = sorted({n for n, _, _ in events})
        ids = {n: i + 1 for i, n in enumerate(names)}
        evs = "".join(f"events {{ metadata_id: {ids[n]} offset_ps: {a * 1000}"
                      f" duration_ps: {d * 1000} }}\n" for n, a, d in events)
        meta = "".join(f'event_metadata {{ key: {i} value {{ id: {i} '
                       f'name: "{esc(n)}" }} }}\n' for n, i in ids.items())
        return (f'planes {{ id: {pid} name: "{name}" lines {{ id: 1 '
                f'name: "{line}" timestamp_ns: 0 {evs} }} {meta} }}\n')
    from jax.profiler import ProfileData
    return ProfileData.from_text_proto(
        plane(1, "/device:TPU:0", "XLA Ops", device_ops)
        + plane(2, "/host:CPU", "python3", host_spans))


def test_synthetic_trace_reduces_exactly():
    # a loop's op holds the ops of its body, as on a TPU; ops are named by
    # their HLO text
    ops = [("%while.4 = (s32[]) while((s32[]) %t), body=%b", 100, 300),
           ("%fusion.1 = bf16[8] fusion(bf16[8] %x)", 100, 150),
           ("%moe_gmm_pallas.3 = bf16[8] custom-call(bf16[8] %x), "
            'custom_call_target="tpu_custom_call"', 250, 100),
           ("%fusion.2 = bf16[8] fusion(bf16[8] %y)", 360, 40),
           ("%copy.7 = bf16[8] copy(bf16[8] %z)", 700, 50),
           ("%fusion.9 = bf16[8] fusion(bf16[8] %w)", 990, 30),
           ("%pallas_call.2 = bf16[8] custom-call(bf16[8] %v)", 720, 10),
           ("%moe_gmm_pallas_pad.1 = bf16[8] pad(bf16[8] %u)", 730, 10)]
    spans = [("chipbench.window", 0, 1000), ("chipbench.step", 50, 800),
             ("chipbench.wave", 100, 320), ("chipbench.wait", 860, 120)]
    red = T.reduce_profile(_xspace(ops, spans))
    ns = 1e-9
    assert red["window_s"] == pytest.approx(1000 * ns)
    # union inside the window: [100, 400] + [700, 750] + [990, 1000]; only
    # the kernel's own custom call counts as the kernel
    assert red["busy_s"] == pytest.approx(360 * ns)
    assert red["kernel_s"]["moe_gmm"] == pytest.approx(100 * ns)
    assert red["kernel_events"] == {"moe_gmm": 1}
    assert red["ops"]["fusion"] == pytest.approx((150 + 40 + 10) * ns)
    # the loop's own time is what its body leaves: 300 - 150 - 100 - 40
    assert red["ops"]["while"] == pytest.approx(10 * ns)
    assert sum(red["ops"].values()) == pytest.approx(red["busy_s"])
    # gaps: [0,100] and [400,700] -> step (it covers half of the first);
    # [750,990] -> wait, which covers half of it and is the shorter span
    idle = red["idle_by_span"]
    assert idle["chipbench.step"] == pytest.approx((100 + 300) * ns)
    assert idle["chipbench.wait"] == pytest.approx(240 * ns)
    assert sum(idle.values()) == pytest.approx(640 * ns)


def _recorded_spans():
    """The harness's host spans in the recorded trace's window."""
    pd = T.load(RECORDED)
    spans = [ev for plane in pd.planes if plane.name.startswith("/host")
             for line in plane.lines for ev in line.events
             if ev.name.startswith(T.SPAN_PREFIX)]
    win = next(ev for ev in spans if ev.name == T.WINDOW_SPAN)
    lo, hi = win.start_ns, win.start_ns + win.duration_ns
    return pd, collections.Counter(ev.name for ev in spans
                                   if lo <= ev.start_ns < hi)


def test_recorded_trace():
    """The chip names each device op by its HLO text, and nests the ops of
    a loop's body inside the loop's op. The kernel runs once a layer in
    every decode wave and every prefill of the window; each op's own
    times add up to the device's busy time."""
    pd, spans = _recorded_spans()
    red = T.reduce_profile(pd)
    calls = RECORDED_LAYERS * (spans["chipbench.wave"]
                               + spans["chipbench.prefill"])
    assert calls > 0
    assert red["kernel_events"] == {"moe_gmm": calls}
    assert 0 < red["kernel_s"]["moe_gmm"] < red["busy_s"] < red["window_s"]
    assert sum(red["ops"].values()) == pytest.approx(red["busy_s"])
    assert red["ops"]["moe_gmm_pallas"] == red["kernel_s"]["moe_gmm"]
    assert all(" " not in op for op in red["ops"])
    assert sum(red["idle_by_span"].values()) == pytest.approx(
        red["window_s"] - red["busy_s"])
    raw = [ev for plane in pd.planes if T.DEVICE_PLANE.match(plane.name)
           for line in plane.lines if line.name == T.OP_LINE
           for ev in line.events]
    assert any(" = " in ev.name for ev in raw)
    assert any(T.op_family(T.op_name(ev.name)) == "while" for ev in raw)


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # any failure means no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def test_kernel_name_is_only_the_custom_call(one_chip, monkeypatch):
    """A device op in the trace is named as the compiled instruction. In
    the decode wave compiled for the chip (granite's widths, two layers,
    the Pallas kernel as on a TPU), the kernel's pattern names every
    `tpu_custom_call` and no other instruction: not the pads and slices
    that carry the kernel's jit in their metadata."""
    import dataclasses

    import jax
    import jax.numpy as jnp

    from repro.configs import get_arch
    from repro.kernels import ops
    from repro.kernels.moe_gmm import moe_gmm_pallas
    from repro.models import model as M
    from repro.serving.engine import Engine
    from repro.sharding.plans import null_plan

    monkeypatch.setattr(ops, "moe_gmm", moe_gmm_pallas)
    cfg = dataclasses.replace(get_arch("granite-moe-3b-a800m"), num_layers=2)
    like = jax.eval_shape(lambda k: M.init_model(cfg, null_plan("decode"),
                                                 k)[0], jax.random.PRNGKey(0))
    eng = Engine(cfg, like, max_batch=4, max_seq=64, eos_id=-1)

    def on_chip(t):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), t)

    text = eng._decode_wave.lower(
        on_chip(like), on_chip(eng.caches),
        on_chip(jax.ShapeDtypeStruct((4, 1), jnp.int32)),
        on_chip(jax.ShapeDtypeStruct((4,), jnp.int32))).compile().as_text()
    inst = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = ")
    names, custom, in_jit = set(), set(), set()
    for line in text.splitlines():
        m = inst.match(line)
        if m:
            names.add(m.group(1))
            if 'custom_call_target="tpu_custom_call"' in line:
                custom.add(m.group(1))
            elif "jit(moe_gmm_pallas)" in line:
                in_jit.add(m.group(1))
    pattern = T.KERNELS["moe_gmm"]
    assert custom and {n for n in names if pattern.match(n)} == custom
    assert in_jit, "no instruction beside the kernel carries its jit's name"
