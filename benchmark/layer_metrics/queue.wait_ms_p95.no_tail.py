"""Admission queue: request.queue_ms p95 in the cells whose TTFT tail is too
noisy to stand end to end; the same reading as ``queue.wait_ms_p95``."""
from benchmark.harness.layers import load_reader

read = load_reader("queue.wait_ms_p95")
