"""Device (what holds it drained): of the wall time of the window's spans in
which the dispatch thread works (``*.build``, ``*.dispatch``, ``*.emit``,
``decode.table_sync``, ``admit``, ``loop.flush``; not the spans that wait for
the device or for work by design), the share the thread was NOT on the CPU
(wall - ``thread_time``), in %: waiting for the interpreter lock it shares
with the gateway's loop and the benchmark's client, or blocked in a call. The
note gives the same by span name, the child spans too."""
from benchmark.harness import host_parts


def read(ctx):
    return host_parts.off_cpu_share(ctx)
