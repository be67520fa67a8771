"""Entry layer (`repro.kernels.ops`, host): median host time per batch
spent inside the lane's entry calls before they return (asynchronous
dispatch), from the harness's clock around each call."""
import statistics

UNIT = "us"


def read(r):
    return statistics.median(r.dispatch_s) * 1e6 if r.dispatch_s else None
