"""``lib/xplane.py`` reads the ``.xplane.pb`` wire format itself, because
``ProfileData`` hands out no metadata stats: on a hand-made file it has
to agree with ``ProfileData`` and find the name stacks, and on a file
the chip wrote (a tiny traced job of PR 24) likewise."""

import gzip
import os

import pytest

from benchmark.lib import trace as tr
from benchmark.lib import xplane

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")

STACK = "jit(_als_iteration_body)/als.user_side/als.w8/while/body/closed_call/als.gather/gather:"
HANDMADE = """
planes {
  name: "/device:TPU:0"
  lines { name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 9000000 } }
  lines { name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 2 offset_ps: 1000000 duration_ps: 2000000 }
    events { metadata_id: 3 offset_ps: 4000000 duration_ps: 500000 }
    events { metadata_id: 2 offset_ps: 5000000 duration_ps: 2500000
      stats { metadata_id: 2 uint64_value: 7 } } }
  event_metadata { key: 1 value { id: 1 name: "jit__als_iteration_body(7)" } }
  event_metadata { key: 2 value { id: 2 name: "%%fusion.1 = f32[8]" display_name: "fusion.1"
      stats { metadata_id: 2 uint64_value: 3 }
      stats { metadata_id: 1 str_value: "%s" } } }
  event_metadata { key: 3 value { id: 3 name: "%%while.2 = ()" } }
  stat_metadata { key: 1 value { id: 1 name: "tf_op" } }
  stat_metadata { key: 2 value { id: 2 name: "flops" } }
}
planes {
  name: "/host:CPU"
  lines { name: "python3" timestamp_ns: 500
    events { metadata_id: 1 offset_ps: 0 duration_ps: 20000000 }
    events { metadata_id: 3 offset_ps: 50000 duration_ps: 10000 }
    events { metadata_id: 2 offset_ps: 100000 duration_ps: 3000000 } }
  event_metadata { key: 1 value { id: 1 name: "bench.window" } }
  event_metadata { key: 2 value { id: 2 name: "pio.als.stage side=user" } }
  event_metadata { key: 3 value { id: 3 name: "PjRt something else" } }
}
""" % STACK


def agrees_with_profile_data(path):
    """Every line ``lib/trace.load`` keeps, event for event (names equal,
    times within the nanosecond ``ProfileData`` rounds to)."""
    mine, ref = xplane.load(path), tr.load(path, keep_host=("bench.", "pio."))
    assert list(mine["devices"]) == list(ref["devices"])
    for plane, lines in ref["devices"].items():
        assert list(mine["devices"][plane]) == list(lines)
        for line, events in lines.items():
            got = mine["devices"][plane][line]
            assert [e[0] for e in got] == [e[0] for e in events]
            for a, b in zip(got, events):
                assert a[1] == pytest.approx(b[1], abs=1.5e-9)
                assert a[2] == pytest.approx(b[2], abs=1.5e-9)
    assert [e[0] for e in mine["host"]] == [e[0] for e in ref["host"]]
    return mine


def test_handmade_file_stacks_times_and_host_filter(tmp_path):
    from jax.profiler import ProfileData

    path = tmp_path / "t.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(HANDMADE))
    trace = agrees_with_profile_data(str(path))
    ops = trace["devices"]["/device:TPU:0"][tr.OP_LINE]
    assert [name for name, _, _ in ops] == [
        "%fusion.1 = f32[8]", "%while.2 = ()", "%fusion.1 = f32[8]"]
    assert ops[0][1:] == (pytest.approx(2e-6), pytest.approx(2e-6))
    # the stack is the operation's, not the event's: both fusion.1 events
    # carry it, the while has none
    assert trace["stacks"]["/device:TPU:0"] == [STACK, "", STACK]
    assert [name for name, _, _ in trace["host"]] == [
        "bench.window", "pio.als.stage side=user"]
    assert xplane.scopes_of(STACK, "als.") == ["als.user_side", "als.w8", "als.gather"]
    assert xplane.scopes_of("", "als.") == []


def test_a_file_the_chip_wrote(tmp_path):
    """A two-iteration job at 600 x 200, rank 16, traced on the v5e in PR
    24: the runtime's own planes, stat names and name stacks."""
    path = tmp_path / "tiny.xplane.pb"
    with gzip.open(os.path.join(DATA, "tiny_chip.xplane.pb.gz")) as f:
        path.write_bytes(f.read())
    trace = agrees_with_profile_data(str(path))
    (plane,) = trace["devices"]
    stacks = trace["stacks"][plane]
    assert len(stacks) == len(trace["devices"][plane][tr.OP_LINE])
    scopes = {s for stack in stacks for s in xplane.scopes_of(stack, "als.")}
    assert {"als.user_side", "als.item_side", "als.gramian", "als.solve",
            "als.scatter"} <= scopes
    assert any(s.startswith("als.w") for s in scopes)
    # the kernels' names are their instructions' names
    names = " ".join(name for name, _, _ in trace["devices"][plane][tr.OP_LINE])
    assert "%spd_solve_t" in names and "%gramian_fused" in names
    assert any(name.startswith("pio.train") for name, _, _ in trace["host"])
