"""A finding, held: the committed v5e capture names the JAX scope and the
source line of every device operation. The names stand on the EVENT METADATA
of the ``XLA Ops`` events (``tf_op``, ``source``, ``hlo_category``, ``flops``,
``bytes_accessed``, ``program_id``), which ``jax.profiler.ProfileData`` does
not expose (it gives an event's own stats) and the raw ``XSpace`` proto does.
So ``jax.named_scope`` DOES reach a device event (PERF.md section 7 said
otherwise from PR 24 to PR 54: that probe read the events' own stats)."""

import importlib.util
import os

import pytest

PROBE = os.path.join(os.path.dirname(__file__), "data", "v5e_probe.xplane.pb")


def xplane_pb2():
    """The proto's module, loaded from its file: the package around it takes
    ten seconds to import and the module needs none of it."""
    spec = importlib.util.find_spec("tensorflow")
    path = spec and os.path.join(os.path.dirname(spec.origin), "tsl", "profiler",
                                 "protobuf", "xplane_pb2.py")
    if not path or not os.path.exists(path):
        pytest.skip("no xplane_pb2 can be imported here")
    module_spec = importlib.util.spec_from_file_location("xplane_pb2", path)
    module = importlib.util.module_from_spec(module_spec)
    module_spec.loader.exec_module(module)
    return module


def test_an_xla_op_carries_its_jax_scope_and_source_line_on_its_metadata():
    space = xplane_pb2().XSpace()
    with open(PROBE, "rb") as capture:
        space.ParseFromString(capture.read())
    plane = next(p for p in space.planes if p.name == "/device:TPU:0")
    names = {key: meta.name for key, meta in plane.stat_metadata.items()}
    ops = next(line for line in plane.lines if line.name == "XLA Ops")
    kernel = next(plane.event_metadata[e.metadata_id] for e in ops.events
                  if plane.event_metadata[e.metadata_id].name.startswith(
                      "%paged_attention"))
    stats = {names[s.metadata_id]: getattr(s, s.WhichOneof("value"))
             for s in kernel.stats}
    assert stats["tf_op"].startswith(
        "jit(_decode_and_sample)/jit(paged_decode_attention_pallas)"
        "/paged_attention/pallas_call")
    assert stats["source"].rpartition(":")[0].endswith(
        "tpu_local/ops/paged_attention.py")
    assert stats["hlo_category"] == "custom-call" and stats["program_id"] > 0
    assert {"flops", "bytes_accessed"} <= set(stats)
