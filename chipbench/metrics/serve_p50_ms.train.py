"""Median of the service's request latency (due time to the end of the
request's iteration) beside a trainer, over every request due in the
window. It swings from run to run with where requests fall in the
trainer's steps, too widely for a bound, so it is read here and the tail
(``serve_p95_ms``) stands end to end. Layer: the executor."""
from chipbench import traffic


def read(run):
    lat = run.request_latencies_ms()
    return traffic.percentile(lat, 50) if lat else None
