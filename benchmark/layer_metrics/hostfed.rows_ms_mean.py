"""Device (what holds it drained): mean over the window's host-fed dispatches
(every kind but ``decode_fb``) of the ``<p>.build.rows`` span's wall, ms: the
Python packing of a dispatch's rows with the allocator's pre-grants. The note
gives the mean by kind and the count. Nothing on a program whose timeline has
no child spans."""
from benchmark.harness import host_parts


def read(ctx):
    return host_parts.part_ms_mean(ctx, "rows")
