"""Device (what holds it drained): mean over the window's host-fed dispatches
of the ``<p>.build.rng`` span's wall, ms: ``jax.random.split`` of the engine's
key, two tiny device programs launched with the device drained."""
from benchmark.harness import host_parts


def read(ctx):
    return host_parts.part_ms_mean(ctx, "rng")
