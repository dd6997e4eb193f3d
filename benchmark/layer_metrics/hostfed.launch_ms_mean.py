"""Device (what holds it drained): mean over the window's host-fed dispatches
of the ``<p>.dispatch.launch`` span's wall, ms: the jitted call alone, entry
to return. Its note also carries the check all four ``hostfed.*`` share
(``hostfed.sum_check_ms``): the four parts and the unnamed remainder of the
parent spans beside the mean of ``build.t0 -> dispatch.t1``."""
from benchmark.harness import host_parts


def read(ctx):
    return host_parts.part_ms_mean(ctx, "launch")
