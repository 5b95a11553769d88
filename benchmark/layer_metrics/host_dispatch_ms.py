"""Median host time for one `feed` + step call to return, over the
window. The device is kept fed only while this stays under the device's
step time."""
import statistics


def compute(ctx):
    return statistics.median(ctx.phases["main"].dispatch_s) * 1e3
