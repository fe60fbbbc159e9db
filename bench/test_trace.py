"""The trace reduction on a small profiler trace recorded on the CPU.

``testdata/cpu_step.xplane.pb`` traces two calls of one jitted program
(gather, sort, scatter-add, elementwise) under the harness's spans: a
``window`` around both, each call in a ``run_call``, each followed by 20 ms
of sleep in a ``stats_readout``. ``testdata/cpu_step.hlo.txt`` is that
program's compiled HLO. The expected numbers below were summed by hand from
the trace's op events (their start and end in ns):

  call 1  select_bitcast_fusion  554985-652696      gather_bitcast_fusion
          654499-915527          wrapped_broadcast  1013152-1053489
          sort.0 1054168-12838046  wrapped_scatter  12840266-12998388
          broadcast_multiply_fusion 12999670-13436098
  call 2  select_bitcast_fusion  34576118-34721802  gather_bitcast_fusion
          34832233-35135553      wrapped_broadcast  35139304-35187802
          sort.0 35189238-48746846  wrapped_scatter 48749581-48934244
          broadcast_multiply_fusion 48936568-49441145
  window  211061-69997871
"""

from pathlib import Path

import pytest

from bench import trace

DATA = Path(__file__).resolve().parent / "testdata"


@pytest.fixture(scope="module")
def reduction():
    return trace.reduce(str(DATA / "cpu_step.xplane.pb"),
                        (DATA / "cpu_step.hlo.txt").read_text())


def test_hlo_kinds_look_inside_fusions():
    kinds = trace.hlo_kinds((DATA / "cpu_step.hlo.txt").read_text())
    assert kinds["gather_bitcast_fusion"] == {"gather"}
    assert kinds["wrapped_scatter"] == {"scatter"}
    assert kinds["sort.0"] == {"sort"}
    assert kinds["broadcast_multiply_fusion"] == frozenset()
    assert kinds["select_bitcast_fusion"] == frozenset()


def test_busy_and_window(reduction):
    assert reduction.window_s == pytest.approx(69_786_810e-9, abs=1e-12)
    assert reduction.busy_s == pytest.approx(27_521_854e-9, abs=1e-12)


@pytest.mark.parametrize("kind, ns", [
    ("sort", 11_783_878 + 13_557_608),
    ("scatter", 158_122 + 184_663),
    ("gather", 261_028 + 303_320),
    ("other", 97_711 + 145_684 + 40_337 + 48_498 + 436_428 + 504_577),
])
def test_time_per_kind(reduction, kind, ns):
    assert reduction.by_kind[kind] == pytest.approx(ns * 1e-9, abs=1e-12)


def test_time_per_name(reduction):
    assert reduction.by_name["sort.0"] == pytest.approx(25_341_486e-9,
                                                        abs=1e-12)
    assert reduction.by_name["gather_bitcast_fusion"] == pytest.approx(
        564_348e-9, abs=1e-12)
    assert reduction.kind_s("scatter", "sort") == pytest.approx(
        25_684_271e-9, abs=1e-12)


def test_idle_gaps_named_by_host_span(reduction):
    gaps = sorted(reduction.gaps, key=lambda g: -g[1])
    assert sum(s for _, s in gaps) == pytest.approx(42_264_956e-9, abs=1e-12)
    assert gaps[0] == ("stats_readout", pytest.approx(21_140_020e-9,
                                                      abs=1e-12))
    assert gaps[1] == ("stats_readout", pytest.approx(20_556_726e-9,
                                                      abs=1e-12))
    assert gaps[2] == ("run_call", pytest.approx(343_924e-9, abs=1e-12))
    top = reduction.breakdown(top=2)
    assert [g[0] for g in top["idle_gaps"]] == ["stats_readout"] * 2
    assert top["device_ops"][0][0] == "sort.0 [sort] jit(sort)/sort"


def test_a_device_plane_without_xla_ops_is_no_device(tmp_path):
    """A CPU trace made in a process that loaded the TPU library holds an
    empty ``/device:CUSTOM:Megascale Trace`` plane; the ops are still the
    host's ``hlo_op`` events."""
    from jax.profiler import ProfileData
    path = DATA / "cpu_step.xplane.pb"
    empty = ProfileData.text_proto_to_serialized_xspace(
        'planes { id: 9 name: "/device:CUSTOM:Megascale Trace" }')
    with_plane = tmp_path / "with_plane.xplane.pb"
    with_plane.write_bytes(path.read_bytes() + empty)   # a repeated field
    assert "/device:CUSTOM:Megascale Trace" in [
        p.name for p in ProfileData.from_file(str(with_plane)).planes]
    assert trace.load(str(with_plane)) == trace.load(str(path))
    hlo = (DATA / "cpu_step.hlo.txt").read_text()
    assert trace.reduce(str(with_plane), hlo).busy_s == pytest.approx(
        27_521_854e-9, abs=1e-12)
