"""Device (what holds it drained): mean over the window's host-fed dispatches
of every host-to-device upload made for the call, ms: ``<p>.build.sampling``
(the rows' three sampling arrays) + ``decode.table_sync`` (the block table,
when dirty) + ``<p>.dispatch.upload`` (the call's four to six arrays)."""
from benchmark.harness import host_parts


def read(ctx):
    return host_parts.part_ms_mean(ctx, "upload")
