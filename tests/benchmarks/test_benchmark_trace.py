"""``benchmarks/lib/trace.py``: the reduction from a profiler trace to busy
union, idle share, per-kernel sums and idle gaps, on a recorded trace cut
from a chip run and on a synthetic one."""

import json
import os

import pytest

from benchmarks.lib import tables
from benchmarks.lib import trace as T

FIXTURES = os.path.join(tables.BENCH_DIR, "fixtures")
XPLANE = os.path.join(FIXTURES, "slide_fwd_b16_10k.cut.xplane.pb")
with open(os.path.join(FIXTURES, "slide_fwd_b16_10k.cut.json")) as _f:
    RECORDED = json.load(_f)


def _reduce_recorded():
    return T.reduce_xplane(XPLANE, [tuple(s) for s in RECORDED["spans"]],
                           RECORDED["sync_host_ns"])


def test_recorded_trace_reduces_to_the_recorded_numbers():
    r, want = _reduce_recorded(), RECORDED["expected"]
    assert r.n_devices == want["n_devices"] == 1
    assert r.window_s == pytest.approx(want["window_s"], rel=1e-12)
    assert r.busy_s == pytest.approx(want["busy_s"], rel=1e-12)
    assert r.idle_share == pytest.approx(want["idle_share"], rel=1e-9)
    assert 0.0 < r.idle_share < 0.2
    assert len(r.op_total_s) == want["n_ops"]
    assert sum(r.op_total_s.values()) == pytest.approx(want["total_op_s"], rel=1e-9)
    assert dict(r.idle_gaps) == pytest.approx(want["idle_gaps"], rel=1e-9)
    kind, seconds = r.breakdown(1)["device_ops"][0]
    assert kind == want["top_kind"][0] and seconds == pytest.approx(want["top_kind"][1])
    # gaps add up to the idle time, less the gaps too short to count
    idle = r.window_s - r.busy_s
    assert sum(s for _, s in r.idle_gaps) == pytest.approx(idle, rel=0.02)


def test_recorded_trace_reduces_the_same_every_time():
    a, b = _reduce_recorded(), _reduce_recorded()
    assert (a.busy_s, a.window_s, a.idle_gaps) == (b.busy_s, b.window_s, b.idle_gaps)
    assert a.op_total_s == b.op_total_s and a.op_self_s == b.op_self_s


def test_kernel_table_takes_attention_kernels_and_leaves_pack_kernels():
    r, table = _reduce_recorded(), tables.kernel_table("dilated_attn")
    seconds = r.kernel_seconds(table)
    assert seconds == pytest.approx(RECORDED["expected"]["dilated_attn_s"], rel=1e-9)
    custom = sum(s for n, s in r.op_total_s.items() if "tpu_custom_call" in n)
    assert 0.5 * custom < seconds < custom  # the pack / unpack kernels are the rest
    assert 0.25 < seconds / r.busy_s < 0.45


@pytest.mark.parametrize("name,kind", [
    ("%convolution_add_fusion.39 = bf16[128,197,8192]{2,0,1:T(8,128)(2,1)} fusion(bf16[8192]{0} %x), kind=kOutput",
     "convolution_add_fusion fusion bf16[128,197,8192]"),
    ("%self_attn._attend.308 = (bf16[16,2,2,8,3072,48]{5,4,3,2,1,0:T(8,128)(2,1)}, f32[16,2,2,3072,128]{4,3,2,1,0:T(8,128)}) custom-call(bf16[1]{0} %q), custom_call_target=\"tpu_custom_call\"",
     "self_attn._attend custom-call (bf16[16,2,2,8,3072,48], f32[16,2,2,3072,128])"),
    ("%copy.7 = bf16[128,197,1536]{2,1,0} copy(bf16[128,197,1536]{1,2,0} %y)",
     "copy copy bf16[128,197,1536]"),
    ("not an hlo line", "not an hlo line"),
])
def test_op_kind(name, kind):
    assert T.op_kind(name) == kind


_SYNTHETIC = """
planes {
  id: 1 name: "/device:TPU:0"
  event_metadata { key: 1 value { id: 1 name: "jit_bench_clock_sync(7)" } }
  event_metadata { key: 2 value { id: 2 name: "%while.1 = f32[8]{0} while(f32[8]{0} %a)" } }
  event_metadata { key: 3 value { id: 3 name: "%fusion.2 = f32[8]{0} fusion(f32[8]{0} %b)" } }
  event_metadata { key: 4 value { id: 4 name: "%k.3 = (bf16[8]{0}, f32[8]{0}) custom-call(bf16[8]{0} %q), custom_call_target=\\"tpu_custom_call\\"" } }
  lines { id: 1 name: "XLA Modules" timestamp_ns: 0
          events { metadata_id: 1 offset_ps: 500000000 duration_ps: 500000000 } }
  lines { id: 2 name: "XLA Ops" timestamp_ns: 0
          events { metadata_id: 2 offset_ps: 2000000000 duration_ps: 4000000000 }
          events { metadata_id: 3 offset_ps: 3000000000 duration_ps: 1000000000 }
          events { metadata_id: 4 offset_ps: 8000000000 duration_ps: 2000000000 }
          events { metadata_id: 3 offset_ps: 11000000000 duration_ps: 5000000000 } }
}
"""


def test_synthetic_trace_nesting_clipping_and_gaps(tmp_path):
    from jax.profiler import ProfileData

    path = tmp_path / "t.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(_SYNTHETIC))
    # the marker ends at 1 ms on the trace's clock; the host saw it at 101 ms
    # of its own: host + (-100 ms) = trace. Window 1.5 ms .. 13 ms of the trace.
    ms = 1_000_000
    spans = [("window", 101 * ms + ms // 2, 113 * ms), ("h2d", 101 * ms + ms // 2, 102 * ms),
             ("fetch", 106 * ms, 108 * ms)]
    r = T.reduce_xplane(str(path), spans, 101 * ms)
    assert r.window_s == pytest.approx(11.5e-3)
    # busy: while 2..6, kernel 8..10, fusion 11..13 (clipped from 16)
    assert r.busy_s == pytest.approx(8e-3)
    assert r.idle_share == pytest.approx(1 - 8 / 11.5)
    whole = {T.op_kind(n): s for n, s in r.op_total_s.items()}
    own = {T.op_kind(n): s for n, s in r.op_self_s.items()}
    assert whole["while while f32[8]"] == pytest.approx(4e-3)
    assert own["while while f32[8]"] == pytest.approx(3e-3)  # less the fusion inside
    assert own["fusion fusion f32[8]"] == pytest.approx(3e-3)  # 1 nested + 2 clipped
    assert r.kernel_seconds(tables.kernel_table("dilated_attn")) == pytest.approx(2e-3)
    # gaps: 1.5..2 under h2d, 6..8 under fetch, 10..11 under nothing
    assert dict(r.idle_gaps) == pytest.approx(
        {"h2d": 0.5e-3, "fetch": 2e-3, "between_spans": 1e-3})
    assert r.host_span_s == pytest.approx({"h2d": 0.5e-3, "fetch": 2e-3})


def test_trace_without_a_device_timeline_reduces_to_nothing(tmp_path):
    from jax.profiler import ProfileData

    path = tmp_path / "t.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(
        'planes { id: 1 name: "/host:CPU" }'))
    assert T.reduce_xplane(str(path), [("window", 0, 10)], 0) is None


def test_host_spans_record_by_the_hosts_clock():
    spans = T.HostSpans()
    with spans.span("h2d"):
        pass
    (name, start, end), = spans.spans
    assert name == "h2d" and end >= start > 0
