"""Entry layer: 90th percentile of the client's task latency in cells
whose window completes only a dozen or so tasks, where it is close to a
maximum and too thin to hold a PR to."""

from harness import metrics


def read(ctx):
    lat = metrics.latencies_ms(ctx["tasks"])
    return metrics.percentile(lat, 90) if lat else None
