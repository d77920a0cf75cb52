"""How late the benchmark's own generator submitted: submit time minus due
time, 99th percentile over steady ticks that came due while no burst was
being drained.  It is inside every latency; above 1 ms the generator is
starved or stuck behind a flush, and a fast server must not be read out
of it."""

from benchmark.harness import readers

NAME = "gen_lateness_p99_ms"
UNIT = "ms"
LAYER = "load generator"
BETTER = "lower"
SOURCE = "host_clock"
MOVES = "tick_p50_ms"
read = readers.gen_lateness_p99_ms


def warn(value: float):
    if value > 1.0:
        return (f"{value:.3f} ms: the generator ran late outside bursts; "
                "latencies include it")
    return None
