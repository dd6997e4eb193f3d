"""Device (what holds it drained): the longest host-fed ``build.t0 ->
dispatch.t1`` of the window, ms. The note names it: step, kind, the child
span that held most of it with its wall and cpu, and the pauses (garbage
collections by generation) that overlap it; beside it the window's pauses
(count, total, longest, and the span of the dispatch thread each fell in) and
the dispatches beyond the program's stall limit."""
from benchmark.harness import host_parts


def read(ctx):
    return host_parts.stall_ms_max(ctx)
