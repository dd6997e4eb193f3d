"""Device: idle share the host caused, in the saturated cells; the same
reading as ``device.idle_share.host.serve``."""
from benchmark.harness.layers import load_reader

read = load_reader("device.idle_share.host.serve")
